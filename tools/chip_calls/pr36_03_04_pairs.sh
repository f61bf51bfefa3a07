#!/bin/sh
# PR 36, calls 3 and 4 in one: six pairs of longgen-overload, then four of
# chat-shared (no chip was free when call 3 was first asked for).
sh tools/chip_calls/pr36_03_longgen_pairs.sh
sh tools/chip_calls/pr36_04_chat_pairs.sh
