#!/bin/sh
# PR 28, second session: one 45 s run of the new cell from the files git
# would commit and nothing else. Before the call:
#   rm -rf artifacts/checkout/final && mkdir -p artifacts/checkout/final
#   git archive $(git write-tree) | tar -x -C artifacts/checkout/final
cd artifacts/checkout/final || exit 9
echo "cache dir: ${JAX_COMPILATION_CACHE_DIR:-unset}"
t0=$(date +%s)
python3 -m chipbench.run --workload mellum2-12b-a2.5b.repo-context-overload --seed 2850000061 --seconds 45 --trace 0 > /tmp/final.out 2> /tmp/final.err
echo "exit=$? after $(( $(date +%s) - t0 )) s"
grep -h "^chipbench:" /tmp/final.out /tmp/final.err | cut -c1-700
tail -1 /tmp/final.out | cut -c1-1500
