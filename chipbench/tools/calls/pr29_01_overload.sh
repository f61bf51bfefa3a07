#!/bin/sh
# PR 29, call 1: longgen-overload, one traced run a side, six pairs on six
# seeds, then one pair of the int8 control (--plant control: w8/kv8, the
# int8 pools through the same carry).
sh chipbench/tools/calls/pr29_pairs.sh over internlm2-1.8b.longgen-overload 1 \
    2910000011 2920000013 2930000017 2940000019 2950000023 2960000029
sh chipbench/tools/calls/pr29_pairs.sh over_ctl internlm2-1.8b.longgen-overload 0 --plant control 2970000031
