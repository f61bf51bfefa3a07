#!/bin/sh
# PR 28, second session: the tree after the review (expert stacks left in
# bf16 by the w8 path, runs of one layer kind scanned, lengths in one order
# for every seed). One untraced run first, and nothing more if it failed or
# served under 900 tokens/s; then the traced run for the per-layer table;
# then the other five seeds of the set.
# usage: pr28_12_set_traced.sh <tag> [notrace]
CELL=mellum2-12b-a2.5b.repo-context-overload
mkdir -p chiprun_out/pr28
run() {  # name seed trace
  t0=$(date +%s)
  python3 -m chipbench.run --workload $CELL --seed $2 --seconds 45 --trace $3 > chiprun_out/pr28/$1.out 2> chiprun_out/pr28/$1.err
  echo "$1 seed $2 exit=$? after $(( $(date +%s) - t0 )) s"
  grep -h "^chipbench:" chiprun_out/pr28/$1.out chiprun_out/pr28/$1.err | grep -v "device op" | cut -c1-900
  tail -1 chiprun_out/pr28/$1.out | cut -c1-2600
}
run $1_2150000011 2150000011 0
rate=$(tail -1 chiprun_out/pr28/$1_2150000011.out | sed -n 's/.*"serve_tokens_per_s": {"value": \([0-9]*\).*/\1/p')
if [ -z "$rate" ] || [ "$rate" -lt 900 ]; then
  echo "first run gave '$rate' tokens/s: stopping"; tail -30 chiprun_out/pr28/$1_2150000011.err; exit 1
fi
if [ "$2" != notrace ]; then
  run $1_traced 2950000019 1
  grep -h "device op" chiprun_out/pr28/$1_traced.out | head -24
fi
for seed in 2250000017 2350000029 2450000039 2550000047 2650000051; do
  run $1_$seed $seed 0
done
