#!/bin/sh
# PR 28, call 2: the new cell with the Pallas grouped matmul (call 1 read
# lax.ragged_dot's kernel), traced, same seed; the control; one more seed.
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
run() {  # name seed seconds trace [plant]
  t0=$(date +%s)
  python3 -m chipbench.run --workload $CELL --seed $2 --seconds $3 --trace $4 ${5:+--plant $5} > chiprun_out/pr28/$1.out 2> chiprun_out/pr28/$1.err
  echo "$1 exit=$? after $(( $(date +%s) - t0 )) s"
  grep "^chipbench:" chiprun_out/pr28/$1.out chiprun_out/pr28/$1.err | cut -c1-300 | head -60
  tail -1 chiprun_out/pr28/$1.out | cut -c1-3500
  [ -s chiprun_out/pr28/$1.out ] || tail -30 chiprun_out/pr28/$1.err | cut -c1-500
}
run 02_traced 3100000007 20 1
run 02_control 3100000007 20 0 control
run 02_seed2 3200000011 20 0
