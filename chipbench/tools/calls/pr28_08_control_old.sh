#!/bin/sh
# PR 28: the control with the expert stacks in int8 too (45 s, two seeds),
# then the three cells that were there, P C C P.
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
for seed in 3100000007 3300000019; do
  python3 -m chipbench.run --workload $CELL --seed $seed --seconds 45 --trace 0 --plant control > chiprun_out/pr28/08_control_$seed.out 2> chiprun_out/pr28/08_control_$seed.err
  echo "control $seed exit=$? $(grep 'compared' chiprun_out/pr28/08_control_$seed.err | tail -1) $(tail -1 chiprun_out/pr28/08_control_$seed.out | cut -c1-260)"
done
sh chipbench/tools/calls/pr28_overlay.sh
one() {  # side cell seed tag
  if [ $1 = P ]; then dir=artifacts/checkout/parent; else dir=.; fi
  ( cd $dir && python3 -m chipbench.run --workload $2 --seed $3 --seconds 45 --trace 0 ) > chiprun_out/pr28/09_$4.out 2> chiprun_out/pr28/09_$4.err
  echo "$4 $1 $2 seed $3 exit=$? $(tail -1 chiprun_out/pr28/09_$4.out | cut -c1-330)"
}
pair() {  # cell seed tag
  one P $1 $2 $3_P1; one C $1 $2 $3_C1; one C $1 $2 $3_C2; one P $1 $2 $3_P2
}
pair internlm2-1.8b.longgen-overload 3100000007 over_a
pair internlm2-1.8b.chat-shared 3200000011 chat_a
pair ernie45-0.3b.train-4k 3300000019 train_a
