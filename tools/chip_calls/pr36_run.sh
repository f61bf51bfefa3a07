#!/bin/sh
# PR 36: runs of serving cells in one call on one chip, each through
# tools/chip_calls/pr36_hunt.py (chipbench.run with the harness's steps and
# the two snapshots kept and read afterwards; nothing added inside a step).
#   sh tools/chip_calls/pr36_run.sh <tag> <side>:<cell>:<seed>:<seconds>:<trace> ...
# side P runs from artifacts/checkout/parent (git archive 149af1d | tar -x),
# side C from the working tree or, where it is there, from
# artifacts/checkout/final (git archive $(git write-tree)). Every run's
# result line, stall warnings and hunt: lines are printed and kept under
# chiprun_out/pr36/; at the end each side's untraced runs as one table.
tag=$1; shift
top=$PWD
out=$top/chiprun_out/pr36
mkdir -p $out
change=$top; [ -d artifacts/checkout/final ] && change=$top/artifacts/checkout/final
echo "the change runs from $change; cache dir ${JAX_COMPILATION_CACHE_DIR:-unset}; $(nproc) cores"
for spec in "$@"; do
  side=${spec%%:*}; rest=${spec#*:}
  W=${rest%%:*}; rest=${rest#*:}
  seed=${rest%%:*}; rest=${rest#*:}
  secs=${rest%%:*}; tr=${rest#*:}
  d=$change; [ $side = P ] && d=$top/artifacts/checkout/parent
  f=$out/${tag}_${side}_${W}_${seed}_t$tr
  t0=$(date +%s)
  ( cd $d && python3 $top/tools/chip_calls/pr36_hunt.py --workload $W --seed $seed --seconds $secs --trace $tr ) > $f.out 2> $f.err
  echo "== $tag $side $W seed $seed seconds $secs trace=$tr exit=$? after $(( $(date +%s) - t0 )) s"
  if [ $tr = 1 ]; then tail -1 $f.out | cut -c1-9000; else tail -1 $f.out | cut -c1-420; fi
  grep -h "steps by program" $f.out | cut -c1-700
  grep -h "^stall in\|^hunt:" $f.err | cut -c1-1600
  [ $tr = 0 ] && [ $secs = 45 ] && echo "{\"set\": \"$side\", \"seed\": $seed, \"trace\": 0, \"line\": $(tail -1 $f.out)}" >> $out/${tag}_${side}_$W.jsonl
done
for f in $out/${tag}_*.jsonl; do [ -f $f ] && python3 chipbench/tools/calls/summarise.py $f; done
exit 0
