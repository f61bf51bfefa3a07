# PR 25, first session, chip call 8, as it was sent (written against the tree of that hour:
# options such as --rate are gone since; what it was for is in LOG.txt).
mkdir -p chiprun_out
python3 -m chipbench.tools.readings --workload ernie45-0.3b.train-4k --seconds 2 --plant state_unchanged --seeds 2147481001,2147481002,3000001007 2> chiprun_out/t4.err > /dev/null
grep compared chiprun_out/t4.err
bash chipbench/tools/sets.sh internlm2-1.8b.chat-shared 45
