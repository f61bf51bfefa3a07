#!/bin/sh
# The first chip call: the convolution step alone at each block of rows,
# then the Jamba cell traced and untraced, parent (artifacts/checkout/parent)
# and change in the order parent, change, change, parent.
#   sh tools/chip_calls/pr42_01_first.sh <traced seed> <untraced seed>
top=$PWD
out=$top/chiprun_out/pr42
mkdir -p $out
cell=ai21-jamba2-3b.longthink-overload
t0=$(date +%s)
timeout 300 python3 tools/chip_calls/pr42_kernels.py > $out/c1_kernels.out 2> $out/c1_kernels.err
echo "kernels exit=$? after $(( $(date +%s) - t0 )) s"
grep -v -i warn $out/c1_kernels.out | tail -12
export GO_ON=1 RUN_DIR=chiprun_out/pr42 RUN_LIMIT=720
TREE=$top/artifacts/checkout/parent sh tools/chip_calls/pr41_run.sh c1p $cell 45 $1:1
sh tools/chip_calls/pr41_run.sh c1c $cell 45 $1:1 $2:0
TREE=$top/artifacts/checkout/parent sh tools/chip_calls/pr41_run.sh c1p2 $cell 45 $2:0
