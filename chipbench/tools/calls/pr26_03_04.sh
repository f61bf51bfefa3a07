# PR 26: chip calls 3 and 4 sent as one command (no chip was free when call 3
# was first asked for, and nothing was charged).
bash chipbench/tools/calls/pr26_03_chat_pairs.sh
bash chipbench/tools/calls/pr26_04_train.sh
