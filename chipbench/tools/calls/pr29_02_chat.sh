#!/bin/sh
# PR 29, call 2: chat-shared, one traced run a side, six pairs on six seeds.
sh chipbench/tools/calls/pr29_pairs.sh chat internlm2-1.8b.chat-shared 1 \
    3010000019 3020000021 3030000001 3040000007 3050000011 3060000023
# the int8 control of call 1 again on the change alone, same seed (the parent
# read 929.2 tokens/s there, the change before its scale pools rode in the
# kernel's layout 165.1)
python3 -m chipbench.run --workload internlm2-1.8b.longgen-overload --seed 2970000031 --seconds 45 --trace 0 --plant control \
    > chiprun_out/pr29/over_ctl2_C.out 2> chiprun_out/pr29/over_ctl2_C.err
echo "over_ctl2 C exit=$? $(tail -1 chiprun_out/pr29/over_ctl2_C.out | cut -c1-400)"
