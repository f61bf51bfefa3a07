#!/bin/sh
mkdir -p chiprun_out/pr28
python3 chipbench/tools/calls/pr28_05_profile.py > chiprun_out/pr28/05_profile.out 2> chiprun_out/pr28/05_profile.err
echo "exit=$?"; grep "^==\|^   " chiprun_out/pr28/05_profile.out | cut -c1-150; tail -5 chiprun_out/pr28/05_profile.err | cut -c1-300
