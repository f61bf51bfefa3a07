#!/bin/sh
# PR 32, call 3: the four cells the benchmark had, parent (artifacts/checkout/
# parent, git archive 9cebb73, its own benchmark files) against the working
# tree, P C C P on two seeds a cell, untraced: none may move.
for spec in internlm2-1.8b.longgen-overload:3270000029:3280000031 \
            mellum2-12b-a2.5b.repo-context-overload:3290000033:3300000037 \
            internlm2-1.8b.chat-shared:3310000039:3320000041 \
            ernie45-0.3b.train-4k:3330000043:3340000047; do
  cell=$(echo $spec | cut -d: -f1)
  sh chipbench/tools/calls/pr29_pairs.sh pr32_$(echo $cell | tr . _) $cell 0 \
      $(echo $spec | cut -d: -f2) $(echo $spec | cut -d: -f3)
done
