#!/bin/sh
# PR 29, call 4: where chat-shared's knee lies now (Open question 1): one
# seed, 30 s windows, the change alone, the rate set on the command line
# (chipbench.tools.readings: not the measured command).
mkdir -p chiprun_out/pr29
for rate in 6 10 14 18 22 26; do
  python3 -m chipbench.tools.readings --workload internlm2-1.8b.chat-shared --seeds 3070000027 \
      --seconds 30 --set arrivals.rate_per_s=$rate > chiprun_out/pr29/sweep_$rate.out 2> chiprun_out/pr29/sweep_$rate.err
  echo "rate $rate exit=$? $(tail -1 chiprun_out/pr29/sweep_$rate.out | cut -c1-900)"
done
