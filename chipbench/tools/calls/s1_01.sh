# PR 25, first session, chip call 1, as it was sent (written against the tree of that hour:
# options such as --rate are gone since; what it was for is in LOG.txt).
set -x
python3 chipbench/tools/probe_trace.py 2>&1 | tail -150
for seed in 2147483659 3100000001; do
  /usr/bin/time -v python3 -m chipbench.run --workload internlm2-1.8b.chat-shared --seed $seed --seconds 20 --trace 0 2> chiprun_out/chat_$seed.err | tail -3
  tail -12 chiprun_out/chat_$seed.err
done
python3 -m chipbench.run --workload internlm2-1.8b.chat-shared --seed 3100000002 --seconds 20 --trace 1 2> chiprun_out/chat_t.err | tail -3
tail -5 chiprun_out/chat_t.err
