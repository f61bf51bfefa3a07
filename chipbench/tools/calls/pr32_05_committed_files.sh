#!/bin/sh
# PR 32, call 5: the new cell from the committed files alone: artifacts/
# checkout/final is `git archive $(git write-tree) | tar -x` of the final tree,
# made in the sandbox. One traced run and further seeds, with the limit as
# committed: every line has to read "correct": true.
#   sh chipbench/tools/calls/pr32_05_committed_files.sh <tag:seed:trace> ...
top=$PWD
cd artifacts/checkout/final || exit 1
sh chipbench/tools/calls/pr32_run.sh nemotron3-super-120b-a12b.reasoning-overload 45 "$@"
rc=$?
mkdir -p $top/chiprun_out/pr32 && cp chiprun_out/pr32/* $top/chiprun_out/pr32/
exit $rc
