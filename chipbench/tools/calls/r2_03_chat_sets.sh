# PR 25, second session, chip call 3: chat-shared from the final tree (order
# of lengths, gaps and tenants from --seed; itl_p95_ms the one end-to-end
# metric beside setup_s): two sets of six runs of 45 s on the same six seeds,
# then three traced runs on three more.
W=internlm2-1.8b.chat-shared
SETS_TAG=r2_03_sets bash chipbench/tools/sets.sh $W 45 \
  2147492001 2147492002 3000012003 3000012004 3000012005 3000012006
for seed in 2147492007 3000012008 3000012009; do
  python3 -m chipbench.run --workload $W --seed $seed --seconds 45 --trace 1 \
    2>> chiprun_out/r2_03_traced.err | tail -1 >> chiprun_out/r2_03_traced.out
done
python3 chipbench/tools/calls/summarise.py chiprun_out/r2_03_sets_$W.jsonl chiprun_out/r2_03_traced.out
tail -n 3 chiprun_out/r2_03_traced.err
