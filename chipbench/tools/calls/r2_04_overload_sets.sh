# PR 25, second session, chip call 4: longgen-overload from the final tree
# (order from --seed, the staggered start's depths the same for every seed,
# two requests prefilled inside the window in the comparison): two sets of six
# runs of 45 s on the same six seeds, then two traced runs on two more.
W=internlm2-1.8b.longgen-overload
SETS_TAG=r2_04_sets bash chipbench/tools/sets.sh $W 45 \
  2147493001 2147493002 3000013003 3000013004 3000013005 3000013006
for seed in 2147493007 3000013008; do
  python3 -m chipbench.run --workload $W --seed $seed --seconds 45 --trace 1 \
    2>> chiprun_out/r2_04_traced.err | tail -1 >> chiprun_out/r2_04_traced.out
done
python3 chipbench/tools/calls/summarise.py chiprun_out/r2_04_sets_$W.jsonl chiprun_out/r2_04_traced.out
tail -n 3 chiprun_out/r2_04_traced.err
