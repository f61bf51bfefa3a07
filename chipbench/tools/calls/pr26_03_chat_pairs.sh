# PR 26, chip call 3: chat-shared, six pairs of parent and change.
bash chipbench/tools/calls/pr26_pairs.sh pr26_03 internlm2-1.8b.chat-shared \
  2147526011 2147526012 3000026013 3000026014 3000026015 3000026016
