#!/bin/sh
# PR 34: lay this PR's benchmark (BENCHMARK.json and chipbench/) over the
# parent's checkout artifacts/checkout/parent (git archive 28905f1 | tar -x,
# made in the sandbox), as the driver does before it tries a new cell there.
cp BENCHMARK.json artifacts/checkout/parent/BENCHMARK.json
cp -r chipbench/. artifacts/checkout/parent/chipbench/
