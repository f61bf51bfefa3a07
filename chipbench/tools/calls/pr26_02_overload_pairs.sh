# PR 26, chip call 2: longgen-overload, six pairs of parent and change.
bash chipbench/tools/calls/pr26_pairs.sh pr26_02 internlm2-1.8b.longgen-overload \
  2147526001 2147526002 3000026003 3000026004 3000026005 3000026006
# and one more traced run of the change, for where in a wait the device idles
python3 chipbench/tools/calls/pr26_look.py --workload internlm2-1.8b.longgen-overload --seed 3000026007 --seconds 45 --trace 1 \
  2> chiprun_out/pr26_02_traced.err | tail -1 > chiprun_out/pr26_02_traced.out
grep "^look:" chiprun_out/pr26_02_traced.err
python3 chipbench/tools/calls/pr26_line.py chiprun_out/pr26_02_traced.out
