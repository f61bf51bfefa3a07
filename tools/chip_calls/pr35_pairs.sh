#!/bin/sh
# PR 35: parent against change in one cell, in one call on one chip. The
# parent is artifacts/checkout/parent (git archive ef71475 | tar -x, made in
# the sandbox; both trees hold the same benchmark files), the change is the
# working tree or, where it is there, artifacts/checkout/final (git archive
# $(git write-tree): the files git would commit and no others).
#   sh tools/chip_calls/pr35_pairs.sh <tag> <cell> <traced seed|0> <seed> ...
# traced seed: first one traced run a side at that seed, the per-layer line
# and the forty largest device operations printed (the whole table through
# tools/chip_calls/pr33_ops.py in chiprun_out/pr35/<tag>_{P,C}_ops.json). Then
# each seed once a side, the order P C, C P, P C, ...; a seed a pair.
tag=$1; W=$2; TR=$3; shift 3
top=$PWD
out=$top/chiprun_out/pr35
mkdir -p $out
change=$top; [ -d artifacts/checkout/final ] && change=$top/artifacts/checkout/final
echo "the change runs from $change; cache dir ${JAX_COMPILATION_CACHE_DIR:-unset}"
one() {  # side seed trace
  d=$change; [ $1 = P ] && d=$top/artifacts/checkout/parent
  t0=$(date +%s)
  prog="-m chipbench.run"
  [ $3 = 1 ] && prog=$top/tools/chip_calls/pr33_ops.py
  ( cd $d && PR33_OPS=$out/${tag}_$1_ops.json python3 $prog --workload $W --seed $2 --seconds 45 --trace $3 ) \
      > $out/${tag}_$1_$2_t$3.out 2> $out/${tag}_$1_$2_t$3.err
  echo "$tag $1 seed $2 trace=$3 exit=$? after $(( $(date +%s) - t0 )) s: $(tail -1 $out/${tag}_$1_$2_t$3.out | cut -c1-420)"
  if [ $3 = 1 ]; then
    grep -h "^chipbench:" $out/${tag}_$1_$2_t$3.out $out/${tag}_$1_$2_t$3.err | cut -c1-3000
    tail -1 $out/${tag}_$1_$2_t$3.out | cut -c1-6000
    python3 - $out/${tag}_$1_ops.json <<'PY'
import json, sys
t = json.load(open(sys.argv[1]))
print("busy_s", t["busy_s"], "window_s", t["window_s"])
for name, sec, n in t["ops"][:40]:
    print("  %.4f s %6d  %s" % (sec, n, name[:150]))
PY
  fi
  echo "{\"set\": \"$1\", \"seed\": $2, \"trace\": $3, \"line\": $(tail -1 $out/${tag}_$1_$2_t$3.out)}" >> $out/${tag}_$1.jsonl
}
if [ "$TR" != 0 ]; then one P $TR 1; one C $TR 1; fi
i=0
for seed in "$@"; do
  if [ $((i % 2)) = 0 ]; then one P $seed 0; one C $seed 0; else one C $seed 0; one P $seed 0; fi
  i=$((i + 1))
done
python3 chipbench/tools/calls/summarise.py $out/${tag}_P.jsonl $out/${tag}_C.jsonl
