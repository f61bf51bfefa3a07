# PR 25, first session, chip call 9, as it was sent (written against the tree of that hour:
# options such as --rate are gone since; what it was for is in LOG.txt).
mkdir -p chiprun_out
bash chipbench/tools/sets.sh ernie45-0.3b.train-4k 45 2147487001 2147487002 3000007003 3000007004 3000007005 3000007006
for seed in 2147487011 3000007012; do
python3 -m chipbench.run --workload ernie45-0.3b.train-4k --seed $seed --seconds 45 --trace 1 2> chiprun_out/e.err | tail -1 | cut -c1-2500
done
