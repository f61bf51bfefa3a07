# PR 26, chip call 4: the train cell, whose program and kernels only got
# names: one traced run of the change through pr26_look.py (XLA Modules line,
# kernel_metadata, the flash rooflines), then two pairs of parent and change,
# untraced.
mkdir -p chiprun_out
W=ernie45-0.3b.train-4k
top=$PWD
change=.; [ -d artifacts/checkout/change ] && change=artifacts/checkout/change
(cd $change && python3 chipbench/tools/calls/pr26_look.py --workload $W --seed 3000026021 --seconds 45 --trace 1 \
  2> $top/chiprun_out/pr26_04_traced.err | tail -1 > $top/chiprun_out/pr26_04_traced.out)
grep "^look:" chiprun_out/pr26_04_traced.err | cut -c1-260
python3 chipbench/tools/calls/pr26_line.py chiprun_out/pr26_04_traced.out
bash chipbench/tools/calls/pr26_pairs.sh pr26_04 $W 2147526022 3000026023
