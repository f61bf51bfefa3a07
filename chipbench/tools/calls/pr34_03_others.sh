#!/bin/sh
# PR 34, calls 3 and 4: the five cells the benchmark had, parent (artifacts/
# checkout/parent, git archive 28905f1, the same benchmark files for these
# cells) against the working tree, P C C P on two seeds a cell, untraced:
# none may move. Arguments: the cells' specs "<cell>:<seed>:<seed>".
#   sh chipbench/tools/calls/pr34_03_others.sh <cell:seed:seed> ...
for spec in "$@"; do
  cell=$(echo $spec | cut -d: -f1)
  sh chipbench/tools/calls/pr29_pairs.sh pr34_$(echo $cell | tr . _) $cell 0 \
      $(echo $spec | cut -d: -f2) $(echo $spec | cut -d: -f3)
done
