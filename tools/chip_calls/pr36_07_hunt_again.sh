#!/bin/sh
# PR 36, call 7, from the committed files: the hunt once more, for a pause
# of 0.5 s or more on the change's side (51 windows so far held 59 stalls of
# 105-148 ms and none longer). Five windows of 225 s of longgen-overload, the
# cheapest windows there are (25 s of set-up a process).
mkdir -p chiprun_out/pr36
W=internlm2-1.8b.longgen-overload
sh tools/chip_calls/pr36_run.sh 07 \
    C:$W:3600000701:225:0 C:$W:3600000702:225:0 C:$W:3600000703:225:0 \
    C:$W:3600000704:225:0 C:$W:3600000705:225:0 > chiprun_out/pr36/call07.txt 2>&1
grep "^== \|longest step [0-9]\{4\}\|^stall in" chiprun_out/pr36/call07.txt | cut -c1-300 | tail -80
