#!/bin/sh
# PR 28: the control with the expert stacks in int8 too, 45 s, two seeds
# (call 8's attempt ran out of memory quantizing the stacks whole).
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
for seed in 3100000007 3300000019; do
  python3 -m chipbench.run --workload $CELL --seed $seed --seconds 45 --trace 0 --plant control > chiprun_out/pr28/10_control_$seed.out 2> chiprun_out/pr28/10_control_$seed.err
  echo "control $seed exit=$? $(grep 'compared' chiprun_out/pr28/10_control_$seed.err | tail -1) $(tail -1 chiprun_out/pr28/10_control_$seed.out | cut -c1-330)"
  grep "^[A-Za-z.]*Error" chiprun_out/pr28/10_control_$seed.err | tail -2 | cut -c1-300
done
