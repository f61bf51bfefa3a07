# PR 25, second session, chip call 1: chat-shared with lengths, gaps and
# tenants permuted from --seed, five seeds of 45 s in one process; then the
# same with every 8 consecutive requests holding one value of each stratum
# (group=8). Which of the two keeps time to first token steady across seeds?
mkdir -p chiprun_out
W=internlm2-1.8b.chat-shared
SEEDS=2147490001,2147490002,3000010003,3000010004,3000010005
python3 -m chipbench.tools.readings --workload $W --seeds $SEEDS --seconds 45 \
  > chiprun_out/r2_01_plain.out 2> chiprun_out/r2_01_plain.err
python3 -m chipbench.tools.readings --workload $W --seeds $SEEDS --seconds 45 --set group=8 \
  > chiprun_out/r2_01_group8.out 2> chiprun_out/r2_01_group8.err
python3 chipbench/tools/calls/summarise.py chiprun_out/r2_01_plain.out chiprun_out/r2_01_group8.out
grep -h "first met" chiprun_out/r2_01_*.out | cut -c1-90 | sort | uniq -c
tail -3 chiprun_out/r2_01_plain.err chiprun_out/r2_01_group8.err
