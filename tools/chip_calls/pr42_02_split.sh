#!/bin/sh
# The second chip call: the convolution step alone (26 layers' calls in one
# program), then the Jamba cell traced with the device time split by
# program on the change and on the parent (artifacts/checkout/parent), then
# one more untraced pair, change first.
#   sh tools/chip_calls/pr42_02_split.sh <traced seed> <untraced seed>
top=$PWD
out=$top/chiprun_out/pr42
mkdir -p $out
cell=ai21-jamba2-3b.longthink-overload
t0=$(date +%s)
timeout 300 python3 tools/chip_calls/pr42_kernels.py > $out/c2_kernels.out 2> $out/c2_kernels.err
echo "kernels exit=$? after $(( $(date +%s) - t0 )) s"
grep -v -i warn $out/c2_kernels.out | tail -12
for side in c p; do
  tree=$top; [ $side = p ] && tree=$top/artifacts/checkout/parent
  t0=$(date +%s)
  ( cd $tree && timeout 600 python3 $top/tools/chip_calls/pr42_trace_split.py \
      --workload $cell --seed $1 --seconds 45 --trace 1 ) \
      > $out/c2${side}_split.out 2> $out/c2${side}_split.err
  echo "== c2$side traced split seed $1: exit $? after $(( $(date +%s) - t0 )) s"
  grep "^split:" $out/c2${side}_split.err | cut -c1-220
  grep "^chipbench: \(the window\|device op\)" $out/c2${side}_split.out | head -12 | cut -c1-300
  tail -1 $out/c2${side}_split.out | cut -c1-1500
done
export GO_ON=1 RUN_DIR=chiprun_out/pr42 RUN_LIMIT=600
sh tools/chip_calls/pr41_run.sh c2c $cell 45 $2:0
TREE=$top/artifacts/checkout/parent sh tools/chip_calls/pr41_run.sh c2p $cell 45 $2:0
