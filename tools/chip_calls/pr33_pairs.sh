#!/bin/sh
# PR 33: parent against change in one cell, in one call on one chip. The
# parent is artifacts/checkout/parent (git archive 2067c2e | tar -x, made in
# the sandbox; both trees hold the same benchmark files), the change is the
# working tree or, where it is there, artifacts/checkout/final (git archive
# $(git write-tree): the files git would commit and no others).
#   sh tools/chip_calls/pr33_pairs.sh <tag> <cell> <traced 0|1> [--plant control] <seed> ...
# traced 1: first one traced run a side (seed 3300000033, per-layer line and
# the largest operations printed). Then each seed once a side, the order
# P C, C P, P C, ...; every pair has a seed of its own.
tag=$1; W=$2; TR=$3; shift 3
extra=""
if [ "$1" = "--plant" ]; then extra="--plant $2"; shift 2; fi
top=$PWD
out=$top/chiprun_out/pr33
mkdir -p $out
change=$top; [ -d artifacts/checkout/final ] && change=$top/artifacts/checkout/final
echo "the change runs from $change; cache dir ${JAX_COMPILATION_CACHE_DIR:-unset}"
one() {  # side seed trace
  d=$change; [ $1 = P ] && d=$top/artifacts/checkout/parent
  t0=$(date +%s)
  prog="-m chipbench.run"
  [ $3 = 1 ] && prog=$top/tools/chip_calls/pr33_ops.py
  ( cd $d && PR33_OPS=$out/${tag}_$1_ops.json python3 $prog --workload $W --seed $2 --seconds 45 --trace $3 $extra ) \
      > $out/${tag}_$1_$2_t$3.out 2> $out/${tag}_$1_$2_t$3.err
  echo "$tag $1 seed $2 trace=$3 exit=$? after $(( $(date +%s) - t0 )) s: $(tail -1 $out/${tag}_$1_$2_t$3.out | cut -c1-420)"
  if [ $3 = 1 ]; then
    grep -h "^chipbench:" $out/${tag}_$1_$2_t$3.out $out/${tag}_$1_$2_t$3.err | cut -c1-3000
    tail -1 $out/${tag}_$1_$2_t$3.out | cut -c1-6000
  fi
  echo "{\"set\": \"$1\", \"seed\": $2, \"trace\": $3, \"line\": $(tail -1 $out/${tag}_$1_$2_t$3.out)}" >> $out/${tag}_$1.jsonl
}
if [ "$TR" = 1 ]; then one P 3300000033 1; one C 3300000033 1; fi
i=0
for seed in "$@"; do
  if [ $((i % 2)) = 0 ]; then one P $seed 0; one C $seed 0; else one C $seed 0; one P $seed 0; fi
  i=$((i + 1))
done
python3 chipbench/tools/calls/summarise.py $out/${tag}_P.jsonl $out/${tag}_C.jsonl
