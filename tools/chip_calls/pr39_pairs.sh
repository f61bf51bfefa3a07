#!/bin/sh
# One cell, parent against change, in one call on one chip. The parent is
# artifacts/checkout/parent (git archive of the parent commit); the change is
# artifacts/checkout/final where it is there (git archive $(git write-tree):
# the files git would commit and no others), else the working tree. Each
# run's output under chiprun_out/pr39/.
#   sh tools/chip_calls/pr39_pairs.sh <tag> <cell> <[P:|C:]traced seed|0> <seed> ...
# traced seed: first one traced run a side (one side alone with P: or C:)
# through tools/chip_calls/pr39_chunk_split.py (device time by program, the
# chunk's attention apart: the change's by its scope, the parent's by the
# source lines of its jnp attention and of the temp cache's gather); then
# each seed once a side, in the order P C, C P, ...
tag=$1; W=$2; TR=$3; shift 3
top=$PWD
out=$top/chiprun_out/pr39
mkdir -p $out
change=$top; [ -d artifacts/checkout/final ] && change=$top/artifacts/checkout/final
# the parent's generate.py: _attn_with_cache, _take_pages and the gather
# of the chunk's temp cache
PLINES=models/generate.py:1687-1787,models/generate.py:273-296,models/generate.py:864-893
echo "the change runs from $change; cache dir ${JAX_COMPILATION_CACHE_DIR:-unset}; $(nproc) cores"
one() {  # side seed trace
  d=$change; [ $1 = P ] && d=$top/artifacts/checkout/parent
  f=$out/${tag}_$1_$2_t$3
  t0=$(date +%s)
  if [ $3 = 1 ]; then
    dump=$top/artifacts/pr37_dump_${tag}_$1
    lines=""; [ $1 = P ] && lines=$PLINES
    ( cd $d && PR37_SPLIT=$f.split.json PR37_DUMP=$dump PR39_LINES=$lines \
        XLA_FLAGS="--xla_dump_to=$dump --xla_dump_hlo_as_text --xla_dump_hlo_module_re=prefill_chunk" \
        python3 $top/tools/chip_calls/pr39_chunk_split.py --workload $W \
        --seed $2 --seconds 45 --trace 1 ) > $f.out 2> $f.err
  else
    ( cd $d && python3 -m chipbench.run --workload $W --seed $2 \
        --seconds 45 --trace 0 ) > $f.out 2> $f.err
  fi
  echo "== $tag $1 $W seed $2 trace=$3 exit=$? after $(( $(date +%s) - t0 )) s"
  if [ $3 = 1 ]; then
    grep -h "steps by program" $f.out | cut -c1-700
    grep -h "^split:" $f.err | cut -c1-300
    tail -1 $f.out | cut -c1-6000
  else
    tail -1 $f.out | cut -c1-420
    echo "{\"set\": \"$1\", \"seed\": $2, \"trace\": 0, \"line\": $(tail -1 $f.out)}" >> $out/${tag}_$1.jsonl
  fi
  grep -h "stall in" $f.err | cut -c1-300
}
if [ "$TR" != 0 ]; then
  sides="P C"
  case $TR in C:*) sides=C; TR=${TR#C:};; P:*) sides=P; TR=${TR#P:};; esac
  for side in $sides; do one $side $TR 1; done
  case $sides in *C*) tail -1 $out/${tag}_C_${TR}_t1.out | grep -q '"correct"' || exit 1;; esac
fi
i=0
for seed in "$@"; do
  if [ $((i % 2)) = 0 ]; then one P $seed 0; one C $seed 0; else one C $seed 0; one P $seed 0; fi
  i=$((i + 1))
done
[ $# -gt 0 ] && python3 chipbench/tools/calls/summarise.py $out/${tag}_P.jsonl $out/${tag}_C.jsonl
exit 0
