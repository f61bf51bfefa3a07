# PR 25, first session, chip call 5, as it was sent (written against the tree of that hour:
# options such as --rate are gone since; what it was for is in LOG.txt).
mkdir -p chiprun_out
for seed in 2147483001 2147483001 3000003002 3000003002 3000003003 3000003003; do
python3 -m chipbench.run --workload internlm2-1.8b.chat-shared --seed $seed --seconds 45 --trace 0 2> chiprun_out/c.err | tail -1 | cut -c1-1200
done
python3 -m chipbench.tools.readings --workload internlm2-1.8b.longgen-overload --seconds 20 --plant control --seeds 2147484001,2147484002,3000004003 2> chiprun_out/oc.err | grep -v "^chipbench: programs" | cut -c1-900
tail -3 chiprun_out/oc.err
