#!/bin/sh
# PR 33: the three flash kernels alone at the train cell's call, the parent
# (artifacts/checkout/parent, git archive 2067c2e; skipped with --no-parent)
# and the working tree. Block settings: bq,bk,bq_bwd,bk_bwd (0 = default).
top=$PWD
mkdir -p $top/chiprun_out/pr33
if [ "$1" = "--no-parent" ]; then shift; else
( cd artifacts/checkout/parent && mkdir -p chiprun_out/pr33 && python3 $top/tools/chip_calls/pr33_kernels.py parent 0,0,0,0 ) 2>&1 | grep '^{' | tee -a $top/chiprun_out/pr33/kernels.jsonl
fi
python3 tools/chip_calls/pr33_kernels.py change "$@" 2>&1 | grep '^{\|Error\|error' | tee -a $top/chiprun_out/pr33/kernels.jsonl
