#!/bin/sh
# PR 34, call 5: the new cell from the committed files alone: artifacts/
# checkout/final is `git archive $(git write-tree) | tar -x` of the final tree,
# made in the sandbox. One traced run and further seeds, with the limit as
# committed: every sound line has to read "correct": true.
#   sh chipbench/tools/calls/pr34_05_committed_files.sh <tag:seed:trace[:plant]> ...
top=$PWD
cd artifacts/checkout/final || exit 1
sh chipbench/tools/calls/pr34_run.sh kimi-k2-instruct.longdoc-overload 45 "$@"
rc=$?
mkdir -p $top/chiprun_out/pr34 && cp chiprun_out/pr34/* $top/chiprun_out/pr34/
exit $rc
