# PR 25, second session, chip call 5: train-4k from the final tree (the
# collector off inside the window, the profiler over the window's last
# steps): one set of six runs of 45 s on six new seeds (the two full sets of
# the first session stand beside it: the timed path is the same), then two
# traced runs.
W=ernie45-0.3b.train-4k
for seed in 2147494001 2147494002 3000014003 3000014004 3000014005 3000014006; do
  python3 -m chipbench.run --workload $W --seed $seed --seconds 45 --trace 0 \
    2>> chiprun_out/r2_05_train.err | tail -1 >> chiprun_out/r2_05_train.out
done
for seed in 2147494007 3000014008; do
  python3 -m chipbench.run --workload $W --seed $seed --seconds 45 --trace 1 \
    2>> chiprun_out/r2_05_train.err | tail -1 >> chiprun_out/r2_05_train_traced.out
done
python3 chipbench/tools/calls/summarise.py chiprun_out/r2_05_train.out chiprun_out/r2_05_train_traced.out
tail -n 3 chiprun_out/r2_05_train.err
