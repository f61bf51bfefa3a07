# PR 25, first session, chip call 10, as it was sent (written against the tree of that hour:
# options such as --rate are gone since; what it was for is in LOG.txt).
mkdir -p chiprun_out
bash chipbench/tools/sets.sh internlm2-1.8b.longgen-overload 45 2147488001 2147488002 3000008003 3000008004 3000008005 3000008006
for seed in 2147488011 3000008012; do
python3 -m chipbench.run --workload internlm2-1.8b.longgen-overload --seed $seed --seconds 45 --trace 1 2> chiprun_out/o.err | tail -1 | cut -c1-2600
done
python3 -m chipbench.run --workload internlm2-1.8b.chat-shared --seed 3000008013 --seconds 45 --trace 1 2> chiprun_out/c.err | tail -1 | cut -c1-2600
