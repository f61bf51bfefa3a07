#!/bin/sh
# Runs of one cell, one process a run, in turn, from the tree given by
# $TREE (default: this checkout); each run's standard output and error under
# $RUN_DIR (default chiprun_out/pr41)/<tag>_<n>.{out,err}, its result line
# and the harness's own summary lines printed here. A run is cut at
# $RUN_LIMIT seconds where that is set. Stops at the first run that fails,
# unless $GO_ON is set.
#   sh tools/chip_calls/pr41_run.sh <tag> <cell> <seconds> <seed>:<trace 0|1>[:<plant>] ...
top=$PWD
out=$top/${RUN_DIR:-chiprun_out/pr41}
mkdir -p $out
tree=${TREE:-$top}
tag=$1; cell=$2; secs=$3; shift 3
n=0
for spec in "$@"; do
  n=$((n + 1))
  seed=$(echo $spec | cut -d: -f1)
  trace=$(echo $spec | cut -d: -f2)
  plant=$(echo $spec | cut -s -d: -f3)
  extra=""
  [ -n "$plant" ] && extra="--plant $plant"
  t0=$(date +%s)
  ( cd $tree && ${RUN_LIMIT:+timeout $RUN_LIMIT} python3 -m chipbench.run --workload $cell --seed $seed \
      --seconds $secs --trace $trace $extra ) \
      > $out/${tag}_$n.out 2> $out/${tag}_$n.err
  rc=$?
  echo "== $tag run $n: $cell seed $seed trace $trace plant ${plant:-none}: exit $rc after $(( $(date +%s) - t0 )) s"
  grep "^chipbench: \(programs\|seconds by\|the window\|compared\|device op\)" $out/${tag}_$n.out | head -60 | cut -c1-600
  tail -1 $out/${tag}_$n.out | cut -c1-3000
  if [ $rc != 0 ]; then
    tail -8 $out/${tag}_$n.err | cut -c1-400
    [ -z "$GO_ON" ] && exit $rc
  fi
done
exit 0
