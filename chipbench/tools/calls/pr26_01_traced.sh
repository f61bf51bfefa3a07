# PR 26, chip call 1: the change's traced run of each serving cell through
# pr26_look.py (the trace opened before the harness deletes it), then one
# traced chat-shared run of the parent commit with this PR's BENCHMARK.json
# and chipbench/ laid over it (artifacts/checkout/parent_overlay, made in the
# sandbox with git archive 60da363 and cp): the new readers find nothing there
# and the line leaves their metrics out.
mkdir -p chiprun_out
for W in internlm2-1.8b.longgen-overload internlm2-1.8b.chat-shared; do
  python3 chipbench/tools/calls/pr26_look.py --workload $W --seed ${SEED:-3000026001} --seconds 45 --trace 1 \
    2> chiprun_out/pr26_01_$W.err | tail -1 > chiprun_out/pr26_01_$W.out
  grep "^look:" chiprun_out/pr26_01_$W.err
  python3 chipbench/tools/calls/pr26_line.py chiprun_out/pr26_01_$W.out
done
cd artifacts/checkout/parent_overlay || exit 1
python3 -m chipbench.run --workload internlm2-1.8b.chat-shared --seed 3000026002 --seconds 45 --trace 1 \
  2> ../../../chiprun_out/pr26_01_parent_overlay.err | tail -1 > ../../../chiprun_out/pr26_01_parent_overlay.out
echo "parent with this PR's benchmark files: exit $?"
cd ../../..
python3 chipbench/tools/calls/pr26_line.py chiprun_out/pr26_01_parent_overlay.out
