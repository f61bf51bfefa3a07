#!/bin/sh
# PR 29, call 3: the cells whose programs this PR leaves as they were
# (pr29_hlo_ops.py: equal operation counts), P C C P on one seed each.
sh chipbench/tools/calls/pr29_pairs.sh repo mellum2-12b-a2.5b.repo-context-overload 0 3110000017 3110000017
sh chipbench/tools/calls/pr29_pairs.sh train ernie45-0.3b.train-4k 0 3120000013 3120000013
