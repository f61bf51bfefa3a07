#!/bin/sh
# PR 36, call 3: six pairs parent / change of longgen-overload, untraced
# 45 s windows, a seed a pair, the order P C, C P, ...: the always-on part
# (kinds, max_ns, the stall check, the step's sample) against the parent's
# spans; every run's longest step and, on the change's side, its stalls.
W=internlm2-1.8b.longgen-overload
sh tools/chip_calls/pr36_run.sh 03 \
    P:$W:3600000301:45:0 C:$W:3600000301:45:0 \
    C:$W:3600000302:45:0 P:$W:3600000302:45:0 \
    P:$W:3600000303:45:0 C:$W:3600000303:45:0 \
    C:$W:3600000304:45:0 P:$W:3600000304:45:0 \
    P:$W:3600000305:45:0 C:$W:3600000305:45:0 \
    C:$W:3600000306:45:0 P:$W:3600000306:45:0
