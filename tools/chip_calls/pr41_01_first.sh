#!/bin/sh
# The first chip call: the Mamba-1 kernels alone at the published widths
# (tools/chip_calls/pr41_kernels.py), the parent on the new cell (it must
# fail at once; artifacts/checkout/parent with this tree's benchmark files
# laid over it), then the cell traced, untraced and under the control.
#   sh tools/chip_calls/pr41_01_first.sh <traced seed> <seed> <control seed>
top=$PWD
out=$top/chiprun_out/pr41
mkdir -p $out
cell=ai21-jamba2-3b.longthink-overload
t0=$(date +%s)
python3 tools/chip_calls/pr41_kernels.py > $out/c1_kernels.out 2> $out/c1_kernels.err
echo "kernels exit=$? after $(( $(date +%s) - t0 )) s"
grep -v -i warn $out/c1_kernels.out | tail -30
GO_ON=1 TREE=$top/artifacts/checkout/parent sh tools/chip_calls/pr41_run.sh c1p $cell 45 $1:0
sh tools/chip_calls/pr41_run.sh c1 $cell 45 $1:1 $2:0 $3:0:control
