#!/bin/sh
# PR 32, call 1: the parent (with this PR's benchmark files laid over it)
# must fail at once on the new cell, at arch.program_config; then the
# change's first traced run of it at the timed sizes.
CELL=nemotron3-super-120b-a12b.reasoning-overload
mkdir -p chiprun_out/pr32
sh chipbench/tools/calls/pr32_overlay.sh
( cd artifacts/checkout/parent && t0=$(date +%s) && python3 -m chipbench.run --workload $CELL --seed 3200000007 --seconds 20 --trace 0 > /dev/null 2> ../../../chiprun_out/pr32/01_parent.err; echo "parent exit=$? after $(( $(date +%s) - t0 )) s"; tail -3 ../../../chiprun_out/pr32/01_parent.err | cut -c1-400 )
t0=$(date +%s)
python3 -m chipbench.run --workload $CELL --seed 3200000007 --seconds 30 --trace 1 > chiprun_out/pr32/01_change.out 2> chiprun_out/pr32/01_change.err
echo "change exit=$? after $(( $(date +%s) - t0 )) s"
grep -h "^chipbench:" chiprun_out/pr32/01_change.out chiprun_out/pr32/01_change.err | cut -c1-400
tail -1 chiprun_out/pr32/01_change.out | cut -c1-6000
tail -25 chiprun_out/pr32/01_change.err | cut -c1-600
