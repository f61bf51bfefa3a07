#!/bin/sh
# PR 28, call 1: the parent (with this PR's benchmark files laid over it)
# must fail fast on the new cell; then the change's first traced run of it.
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
sh chipbench/tools/calls/pr28_overlay.sh
( cd artifacts/checkout/parent && t0=$(date +%s) && python3 -m chipbench.run --workload $CELL --seed 3100000007 --seconds 20 --trace 0 > /dev/null 2> ../../../chiprun_out/pr28/01_parent.err; echo "parent exit=$? after $(( $(date +%s) - t0 )) s"; tail -3 ../../../chiprun_out/pr28/01_parent.err )
t0=$(date +%s)
python3 -m chipbench.run --workload $CELL --seed 3100000007 --seconds 20 --trace 1 > chiprun_out/pr28/01_change.out 2> chiprun_out/pr28/01_change.err
echo "change exit=$? after $(( $(date +%s) - t0 )) s"
grep "^chipbench:" chiprun_out/pr28/01_change.out chiprun_out/pr28/01_change.err | cut -c1-400
tail -1 chiprun_out/pr28/01_change.out | cut -c1-6000
tail -25 chiprun_out/pr28/01_change.err | cut -c1-600
