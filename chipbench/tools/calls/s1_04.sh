# PR 25, first session, chip call 4, as it was sent (written against the tree of that hour:
# options such as --rate are gone since; what it was for is in LOG.txt).
mkdir -p chiprun_out
S=2147481000
W=ernie45-0.3b.train-4k
python3 -m chipbench.tools.readings --workload $W --seconds 4 --seeds $((S+1)),$((S+2)),$((S+3)),$((S+4)),$((S+5)),$((S+6)),3000001007,3000001008,3000001009,3000001010,3000001011,3000001012 2> chiprun_out/t1.err | grep -v "^chipbench: programs" | cut -c1-1600
python3 -m chipbench.tools.readings --workload $W --seconds 2 --plant control --seeds $((S+1)),$((S+2)),3000001007 2> chiprun_out/t2.err | grep -v "^chipbench: programs" | cut -c1-1600
tail -3 chiprun_out/t2.err
python3 -m chipbench.tools.readings --workload $W --seconds 2 --plant half_batch --seeds $((S+1)),$((S+2)),3000001007 2> chiprun_out/t3.err | grep -v "^chipbench: programs" | cut -c1-1600
for seed in 2147482001 3000002002 3000002003; do
python3 -m chipbench.run --workload internlm2-1.8b.chat-shared --seed $seed --seconds 45 --trace 1 2> chiprun_out/c.err | tail -2 | cut -c1-3500
grep -v Warn chiprun_out/c.err | tail -3
done
