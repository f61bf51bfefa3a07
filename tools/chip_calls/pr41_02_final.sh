#!/bin/sh
# The second chip call: the Mamba-1 kernels alone again (the pool donated
# and handed on), the cell traced and on two more seeds, then an old cell
# traced on the parent with this tree's benchmark files laid over it.
#   sh tools/chip_calls/pr41_02_final.sh <traced seed> <seed> <seed> <old cell's seed>
top=$PWD
out=$top/chiprun_out/pr41
mkdir -p $out
cell=ai21-jamba2-3b.longthink-overload
t0=$(date +%s)
python3 tools/chip_calls/pr41_kernels.py > $out/c2_kernels.out 2> $out/c2_kernels.err
echo "kernels exit=$? after $(( $(date +%s) - t0 )) s"
grep -v -i warn $out/c2_kernels.out | tail -30
sh tools/chip_calls/pr41_run.sh c2 $cell 45 $1:1 $2:0 $3:0
TREE=$top/artifacts/checkout/parent sh tools/chip_calls/pr41_run.sh c2p \
    internlm2-1.8b.longgen-overload 45 $4:1
