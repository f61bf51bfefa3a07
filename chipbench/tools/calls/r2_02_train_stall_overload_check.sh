# PR 25, second session, chip call 2. (a) The train cell's far-off run (seed
# 3000007006 read 7% low once): the same seed three times and another once,
# each its own process, with the slowest steps in the line; then one traced
# run, whose profiler now runs over the window's last steps. (b) The overload
# cell with two requests that were prefilled inside the window added to the
# comparison: four seeds of 20 s, then the control on three.
mkdir -p chiprun_out
T=ernie45-0.3b.train-4k
for seed in 3000007006 3000007006 2147487001 3000007006; do
  python3 -m chipbench.run --workload $T --seed $seed --seconds 45 --trace 0 \
    2>> chiprun_out/r2_02_train.err | tail -1 >> chiprun_out/r2_02_train.out
done
python3 -m chipbench.run --workload $T --seed 3000007006 --seconds 45 --trace 1 \
  2>> chiprun_out/r2_02_train.err | tail -1 > chiprun_out/r2_02_train_traced.out
O=internlm2-1.8b.longgen-overload
python3 -m chipbench.tools.readings --workload $O --seeds 2147491001,2147491002,3000011003,3000011004 \
  --seconds 20 > chiprun_out/r2_02_overload.out 2> chiprun_out/r2_02_overload.err
python3 -m chipbench.tools.readings --workload $O --seeds 2147491001,2147491002,3000011003 \
  --seconds 20 --plant control > chiprun_out/r2_02_overload_control.out 2> chiprun_out/r2_02_overload_control.err
python3 chipbench/tools/calls/summarise.py chiprun_out/r2_02_train.out chiprun_out/r2_02_train_traced.out \
  chiprun_out/r2_02_overload.out chiprun_out/r2_02_overload_control.out
cut -c1-1500 chiprun_out/r2_02_train_traced.out
grep -h "first met" chiprun_out/r2_02_*.out | cut -c1-90 | sort | uniq -c
tail -n 4 chiprun_out/r2_02_*.err
