#!/bin/sh
# PR 34, call 1: the parent (with this PR's benchmark files laid over it)
# must fail at once on the new cell, at arch.program_config; then the
# change's first traced run of it at the timed sizes.
CELL=kimi-k2-instruct.longdoc-overload
mkdir -p chiprun_out/pr34
sh chipbench/tools/calls/pr34_overlay.sh
( cd artifacts/checkout/parent && t0=$(date +%s) && python3 -m chipbench.run --workload $CELL --seed 3400000007 --seconds 20 --trace 0 > /dev/null 2> ../../../chiprun_out/pr34/01_parent.err; echo "parent exit=$? after $(( $(date +%s) - t0 )) s"; tail -3 ../../../chiprun_out/pr34/01_parent.err | cut -c1-400 )
sh chipbench/tools/calls/pr34_run.sh $CELL 30 01_change:3400000007:1
tail -25 chiprun_out/pr34/01_change.err | cut -c1-600
