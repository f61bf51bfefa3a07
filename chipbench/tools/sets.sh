# Scratch: two sets of six runs of one cell, the same six seeds in both, each
# run its own process; result lines go to chiprun_out/<tag>_<cell>.jsonl
# (tag: $SETS_TAG, or "sets").
#   [SETS_TAG=tag] bash chipbench/tools/sets.sh <cell> <seconds> [seed ...]
cell=$1; seconds=$2; shift 2
seeds=${@:-2147486001 2147486002 3000006003 3000006004 3000006005 3000006006}
mkdir -p chiprun_out
out=chiprun_out/${SETS_TAG:-sets}_$cell.jsonl
for set in 1 2; do
  for seed in $seeds; do
    python3 -m chipbench.run --workload $cell --seed $seed --seconds $seconds --trace 0 > chiprun_out/stdout.txt 2> chiprun_out/sets.err
    tail -1 chiprun_out/stdout.txt > chiprun_out/line.json
    echo "{\"set\": $set, \"seed\": $seed, \"line\": $(cat chiprun_out/line.json)}" >> $out
    python3 - <<PY
import json
d = json.load(open("chiprun_out/line.json"))
print("set $set seed $seed", d["correct"], {k: round(v["value"], 3) for k, v in d["metrics"].items()},
      [(c["name"], round(c["value"], 6)) for c in d["compared"]])
PY
    grep "first met" chiprun_out/stdout.txt | grep -v "first met in window: 0" | cut -c1-120
  done
done
