#!/bin/sh
# PR 28: three seeds, 20 s windows, for the steps' split by program (what
# differs between seeds that read 880 and 925 tokens/s in set A).
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
for seed in 3300000019 3600000053 3500000041; do
  python3 -m chipbench.run --workload $CELL --seed $seed --seconds 20 --trace 0 > chiprun_out/pr28/04_$seed.out 2> chiprun_out/pr28/04_$seed.err
  echo "seed $seed exit=$?"
  grep "^chipbench: the window" chiprun_out/pr28/04_$seed.out | cut -c1-700
  tail -1 chiprun_out/pr28/04_$seed.out | cut -c1-500
done
