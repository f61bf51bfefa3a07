#!/bin/sh
# PR 36, call 8: the parent's program under this PR's benchmark files
# (artifacts/checkout/overlay = git archive 149af1d, then BENCHMARK.json and
# chipbench/ of the committed files laid over it, as the driver does for the
# traced runs of both sides): one traced run of a dense cell and of an
# expert cell as the driver runs them. The new readers find nothing to read
# there and must leave their metrics out without raising.
out=$PWD/chiprun_out/pr36; mkdir -p $out
cd artifacts/checkout/overlay
for W in internlm2-1.8b.longgen-overload mellum2-12b-a2.5b.repo-context-overload; do
  t0=$(date +%s)
  python3 -m chipbench.run --workload $W --seed 3600000801 --seconds 45 --trace 1 > $out/08_O_$W.out 2> $out/08_O_$W.err
  echo "== overlay $W exit=$? after $(( $(date +%s) - t0 )) s"
  tail -1 $out/08_O_$W.out | python3 -c "
import json, sys
l = json.loads(sys.stdin.read())
print(l['correct'], l['failed'], len(l['metrics']), sorted(l['metrics']))"
done
