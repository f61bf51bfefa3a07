#!/bin/sh
# PR 28: the per-layer table (one traced 45 s run) and the control at 45 s
# on two seeds (the limit has to lie under both).
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
run() {  # name seed trace [plant]
  t0=$(date +%s)
  python3 -m chipbench.run --workload $CELL --seed $2 --seconds 45 --trace $3 ${4:+--plant $4} > chiprun_out/pr28/$1.out 2> chiprun_out/pr28/$1.err
  echo "$1 exit=$? after $(( $(date +%s) - t0 )) s"
  grep "^chipbench:" chiprun_out/pr28/$1.out chiprun_out/pr28/$1.err | cut -c1-420 | head -50
  tail -1 chiprun_out/pr28/$1.out | cut -c1-3000
}
run 07_traced 2900000017 1
run 07_control_a 3100000007 0 control
run 07_control_b 3300000019 0 control
