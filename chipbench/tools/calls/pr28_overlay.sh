#!/bin/sh
# Lay this tree's benchmark files over the parent's checkout under
# artifacts/checkout/parent (made with: git archive <parent> | tar -x -C ...),
# as the driver does for the new cell and the traced runs.
set -e
P=artifacts/checkout/parent
cp BENCHMARK.json $P/BENCHMARK.json
cp -r chipbench/. $P/chipbench/
