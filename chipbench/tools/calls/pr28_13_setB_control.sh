#!/bin/sh
# PR 28, second session: the same six seeds a second time (set B), then the
# control (--plant control: the program's own w8/kv8 path, expert stacks
# left in bf16) on a seed of its own; it has to read over the limit.
CELL=mellum2-12b-a2.5b.repo-context-overload
sh chipbench/tools/calls/pr28_12_set_traced.sh 13_setB notrace || exit 1
t0=$(date +%s)
python3 -m chipbench.run --workload $CELL --seed 2750000059 --seconds 45 --trace 0 --plant control > chiprun_out/pr28/13_control.out 2> chiprun_out/pr28/13_control.err
echo "13_control seed 2750000059 exit=$? after $(( $(date +%s) - t0 )) s"
grep -h "^chipbench:" chiprun_out/pr28/13_control.out chiprun_out/pr28/13_control.err | cut -c1-900
tail -1 chiprun_out/pr28/13_control.out | cut -c1-2000
