#!/bin/sh
# PR 28: one set of six untraced 45 s runs of the new cell, six seeds.
# usage: pr28_03_set.sh <tag>
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
for seed in 3100000007 3200000011 3300000019 3400000031 3500000041 3600000053; do
  t0=$(date +%s)
  python3 -m chipbench.run --workload $CELL --seed $seed --seconds 45 --trace 0 > chiprun_out/pr28/$1_$seed.out 2> chiprun_out/pr28/$1_$seed.err
  echo "$1 seed $seed exit=$? after $(( $(date +%s) - t0 )) s"
  grep "^chipbench:" chiprun_out/pr28/$1_$seed.out | cut -c1-400
  tail -1 chiprun_out/pr28/$1_$seed.out | cut -c1-900
done
