#!/bin/sh
# PR 28: the control with int8 experts (call 10's ran its window and then
# could not make the weights again: the driver still held the engine), 45 s,
# two seeds; then two seeds of the set once more (does a seed repeat?).
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
run() {  # name seed [plant]
  python3 -m chipbench.run --workload $CELL --seed $2 --seconds 45 --trace 0 ${3:+--plant $3} > chiprun_out/pr28/$1.out 2> chiprun_out/pr28/$1.err
  echo "$1 seed $2 exit=$? $(grep 'compared' chiprun_out/pr28/$1.err | tail -1) $(tail -1 chiprun_out/pr28/$1.out | cut -c1-300)"
  grep "^[A-Za-z.]*Error" chiprun_out/pr28/$1.err | tail -1 | cut -c1-300
}
run 11_control_a 3100000007 control
run 11_control_b 3300000019 control
run 11_setB_3100000007 3100000007
run 11_setB_3200000011 3200000011
