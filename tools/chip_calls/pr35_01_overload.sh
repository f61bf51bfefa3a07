#!/bin/sh
# PR 35, call 1: the claimed cell. One traced run a side, then six pairs.
sh tools/chip_calls/pr35_pairs.sh 01_longgen internlm2-1.8b.longgen-overload 3500000035 \
    3500001003 3500002011 3500003017 3500004021 3500005029 3500006037
