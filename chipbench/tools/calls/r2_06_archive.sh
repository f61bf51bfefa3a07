# PR 25, second session, chip call 6: the committed files are enough. Before
# the call, in the sandbox:
#   git add -A; rm -rf artifacts/checkout; mkdir -p artifacts/checkout
#   git archive $(git write-tree) | tar -x -C artifacts/checkout
# (artifacts/checkout is in .gitignore; it is no git repository). Here: one
# run of chat-shared, traced, and one of train-4k from that directory, and
# the refusal in a directory that holds only BENCHMARK.json and chipbench/.
cd artifacts/checkout || exit 1
mkdir -p ../../chiprun_out
python3 -m chipbench.run --workload internlm2-1.8b.chat-shared --seed 3000015001 --seconds 45 --trace 1 \
  2> ../../chiprun_out/r2_06.err | tail -1 > ../../chiprun_out/r2_06.out
python3 -m chipbench.run --workload ernie45-0.3b.train-4k --seed 2147495002 --seconds 45 --trace 0 \
  2>> ../../chiprun_out/r2_06.err | tail -1 >> ../../chiprun_out/r2_06.out
cut -c1-2500 ../../chiprun_out/r2_06.out
grep compared ../../chiprun_out/r2_06.err
mkdir -p ../only && cp -r BENCHMARK.json chipbench ../only/ && cd ../only
python3 -m chipbench.run --workload ernie45-0.3b.train-4k --seed 1 --seconds 5 --trace 0 > only.out 2> only.err
echo "only BENCHMARK.json and chipbench/: exit $? stdout bytes $(wc -c < only.out)"; tail -n 2 only.err
