#!/bin/sh
# PR 33, final call: the claimed cell from the committed files alone
# (artifacts/checkout/final = git archive $(git write-tree)) against the
# parent (artifacts/checkout/parent = git archive 2067c2e): one traced run a
# side, then six pairs, each with a seed of its own, P C, C P, ...; then one
# pair of a serving cell, which runs none of the change.
sh tools/chip_calls/pr33_pairs.sh train ernie45-0.3b.train-4k 1 \
    3300001009 3300002017 3300003023 3300004027 3300005039 3300006043
sh tools/chip_calls/pr33_pairs.sh longgen internlm2-1.8b.longgen-overload 0 3300007057
