#!/bin/sh
# PR 35, call 3: Mellum's cell (expected to improve, not claimed). One traced
# run a side, then three pairs.
sh tools/chip_calls/pr35_pairs.sh 03_mellum mellum2-12b-a2.5b.repo-context-overload 3500000035 \
    3500011069 3500012073 3500013087
