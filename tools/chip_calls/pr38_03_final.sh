#!/bin/sh
# The third chip call, all from checkouts made before it:
# artifacts/checkout/parent (git archive of the parent commit),
# artifacts/checkout/parent_bench (the same with this change's BENCHMARK.json and
# chipbench/ laid over it) and artifacts/checkout/final (git archive
# $(git write-tree): the files git would commit and no others).
# (1) The parent on the new cell: it has to fail at once, not hang; then an
# older cell traced there, through this change's benchmark files. (2) The older
# cells whose serving programs this PR's layer bodies share, parent against
# change, one pair each. (3) The new cell from the committed files.
#   sh tools/chip_calls/pr38_03_final.sh
top=$PWD
R="sh $top/tools/chip_calls/pr38_run.sh"
P=$top/artifacts/checkout/parent
B=$top/artifacts/checkout/parent_bench
C=$top/artifacts/checkout/final
out=$top/chiprun_out/pr38
mkdir -p $out
NEW=trinity-large-preview.longctx-overload
t0=$(date +%s)
( cd $B && timeout 600 python3 -m chipbench.run --workload $NEW \
    --seed 3800000909 --seconds 45 --trace 0 ) \
    > $out/c3_parent_new.out 2> $out/c3_parent_new.err
echo "== the parent on $NEW: exit $? after $(( $(date +%s) - t0 )) s"
tail -3 $out/c3_parent_new.err | cut -c1-300
TREE=$B $R c3_parent_bench internlm2-1.8b.longgen-overload 45 3800001010:1
LG=internlm2-1.8b.longgen-overload
ME=mellum2-12b-a2.5b.repo-context-overload
TREE=$P $R c3_P_lg1 $LG 45 3800001111:0
TREE=$C $R c3_C_lg1 $LG 45 3800001111:0
TREE=$P $R c3_P_me1 $ME 45 3800001313:0
TREE=$C $R c3_C_me1 $ME 45 3800001313:0
TREE=$C $R c3_C_new $NEW 45 3800001414:0
