#!/bin/sh
# PR 29, call 5: both dense cells once from the files git would commit and
# nothing else (before the call: rm -rf artifacts/checkout/final; mkdir -p
# artifacts/checkout/final; git archive $(git write-tree) | tar -x -C
# artifacts/checkout/final), then call 4's sweep, which found no machine.
top=$PWD
mkdir -p chiprun_out/pr29
cd artifacts/checkout/final || exit 9
for cs in internlm2-1.8b.longgen-overload:3130000019 internlm2-1.8b.chat-shared:3140000021; do
  cell=${cs%%:*}; seed=${cs##*:}
  python3 -m chipbench.run --workload $cell --seed $seed --seconds 45 --trace 1 \
      > $top/chiprun_out/pr29/final_$cell.out 2> $top/chiprun_out/pr29/final_$cell.err
  echo "final $cell seed $seed trace=1 exit=$? $(tail -1 $top/chiprun_out/pr29/final_$cell.out | cut -c1-2600)"
done
cd $top && sh chipbench/tools/calls/pr29_04_chat_sweep.sh
