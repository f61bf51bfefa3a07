#!/bin/sh
# PR 34: runs of one cell from the working tree, one after another in one
# call (the first that fails ends it); each argument is
# "<tag>:<seed>:<trace 0|1>[:<plant>]".
#   sh chipbench/tools/calls/pr34_run.sh <cell> <seconds> <tag:seed:trace[:plant]> ...
W=$1; S=$2; shift 2
mkdir -p chiprun_out/pr34
for spec in "$@"; do
  tag=$(echo $spec | cut -d: -f1); seed=$(echo $spec | cut -d: -f2)
  tr=$(echo $spec | cut -d: -f3); plant=$(echo $spec | cut -d: -f4)
  extra=""; [ -n "$plant" ] && extra="--plant $plant"
  t0=$(date +%s)
  python3 -m chipbench.run --workload $W --seed $seed --seconds $S --trace $tr $extra \
      > chiprun_out/pr34/$tag.out 2> chiprun_out/pr34/$tag.err
  rc=$?
  echo "$tag seed $seed trace=$tr $plant exit=$rc after $(( $(date +%s) - t0 )) s: $(tail -1 chiprun_out/pr34/$tag.out | cut -c1-2600)"
  grep -h "compared\|steps by program\|programs first met\|seconds by JAX" chiprun_out/pr34/$tag.out chiprun_out/pr34/$tag.err | cut -c1-700
  if [ "$tr" = 1 ]; then grep -h "device op" chiprun_out/pr34/$tag.out | head -40; fi
  # a run that fails stops the call: the rest would fail alike
  if [ $rc != 0 ]; then tail -30 chiprun_out/pr34/$tag.err | cut -c1-400; exit $rc; fi
done
