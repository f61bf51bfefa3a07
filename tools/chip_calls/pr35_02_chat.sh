#!/bin/sh
# PR 35, call 2: chat-shared (expected to improve, not claimed). One traced
# run a side, then four pairs.
sh tools/chip_calls/pr35_pairs.sh 02_chat internlm2-1.8b.chat-shared 3500000035 \
    3500007043 3500008051 3500009057 3500010061
