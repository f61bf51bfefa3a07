#!/bin/sh
# PR 33: one traced run of the train cell from the working tree, with the
# whole table of device operations written to chiprun_out/pr33/.
mkdir -p chiprun_out/pr33
PR33_OPS=$PWD/chiprun_out/pr33/ops_change.json python3 tools/chip_calls/pr33_ops.py --workload ernie45-0.3b.train-4k --seed ${1:-3300000201} --seconds 45 --trace 1 > chiprun_out/pr33/ops_change.out 2> chiprun_out/pr33/ops_change.err
echo exit $?; tail -1 chiprun_out/pr33/ops_change.out | cut -c1-600
