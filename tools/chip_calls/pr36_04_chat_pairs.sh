#!/bin/sh
# PR 36, call 4: four pairs parent / change of chat-shared, untraced 45 s
# windows, a seed a pair, the order P C, C P, ...
W=internlm2-1.8b.chat-shared
sh tools/chip_calls/pr36_run.sh 04 \
    P:$W:3600000401:45:0 C:$W:3600000401:45:0 \
    C:$W:3600000402:45:0 P:$W:3600000402:45:0 \
    P:$W:3600000403:45:0 C:$W:3600000403:45:0 \
    C:$W:3600000404:45:0 P:$W:3600000404:45:0
