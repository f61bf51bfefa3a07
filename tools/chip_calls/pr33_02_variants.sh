#!/bin/sh
# PR 33: experiments behind a temporary switch (PR33_PAIR etc., not in the
# final tree): each value of the variable runs the same block settings.
#   sh tools/chip_calls/pr33_02_variants.sh VAR "v1 v2" settings...
top=$PWD; var=$1; vals=$2; shift 2
mkdir -p $top/chiprun_out/pr33
for v in $vals; do
  env $var=$v python3 tools/chip_calls/pr33_kernels.py "$var=$v" "$@" 2>&1 | grep '^{\|Error\|error' | tee -a $top/chiprun_out/pr33/kernels.jsonl
done
