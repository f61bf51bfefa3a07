#!/bin/sh
# The first chip call: the K/V chunk kernel alone against its twin at
# Trinity's and Mellum's calls in two query-block sizes
# (tools/chip_calls/pr39_kernel.py, from artifacts/checkout/final where it is
# there, else the working tree; stop if it fails or differs from the twin),
# then Trinity's cell traced a side with the chunk programs' device time split
# and an untraced pair (tools/chip_calls/pr39_pairs.sh), then, where their
# seeds are given, a pair of Mellum's cell and of the dense overload cell.
#   sh tools/chip_calls/pr39_01_first.sh <traced seed> <seed> [<seed> <seed>]
top=$PWD
out=$top/chiprun_out/pr39
mkdir -p $out
change=$top; [ -d artifacts/checkout/final ] && change=$top/artifacts/checkout/final
t0=$(date +%s)
( cd $change && python3 $top/tools/chip_calls/pr39_kernel.py trinity mellum 4096 8192 ) > $out/c1_kernel.out 2> $out/c1_kernel.err
rc=$?
echo "kernel exit=$rc after $(( $(date +%s) - t0 )) s"
grep -v -i warn $out/c1_kernel.out | tail -40
[ $rc = 0 ] || { tail -30 $out/c1_kernel.err | cut -c1-400; exit 1; }
sh tools/chip_calls/pr39_pairs.sh c1 trinity-large-preview.longctx-overload $1 $2 || exit 1
[ -n "$3" ] && sh tools/chip_calls/pr39_pairs.sh c1me mellum2-12b-a2.5b.repo-context-overload 0 $3
[ -n "$4" ] && sh tools/chip_calls/pr39_pairs.sh c1lg internlm2-1.8b.longgen-overload 0 $4
exit 0
