#!/bin/sh
# PR 35, call 4: the cells in which nothing should move. Kimi's (W_qb's copy
# gone, W_kvb's stays): one traced run a side and one pair; Nemotron's (one
# attention layer in eleven) and the train cell (runs none of it): one pair each.
sh tools/chip_calls/pr35_pairs.sh 04_kimi kimi-k2-instruct.longdoc-overload 3500000035 3500014091
sh tools/chip_calls/pr35_pairs.sh 04_nemo nemotron3-super-120b-a12b.reasoning-overload 0 3500015099
sh tools/chip_calls/pr35_pairs.sh 04_train ernie45-0.3b.train-4k 0 3500016103
