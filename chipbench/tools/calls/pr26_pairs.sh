# Scratch (PR 26): parent against change in one cell, untraced, in one call on
# one chip, in the order parent, change, change, parent, ... Each pair shares a
# seed; every pair has another. The parent is artifacts/checkout/parent (git
# archive of 60da363, made in the sandbox). The change runs from the working
# tree or, where artifacts/checkout/change is there (git archive $(git
# write-tree), made in the sandbox after git add -A: the files git would
# commit and no others), from that directory. Its runs go through
# pr26_look.py, which also prints the program's own phases a step.
#   bash chipbench/tools/calls/pr26_pairs.sh <tag> <cell> <seed> [<seed> ...]
tag=$1; W=$2; shift 2
mkdir -p chiprun_out
top=$PWD
change=.; [ -d artifacts/checkout/change ] && change=artifacts/checkout/change
echo "the change runs from $change"
# LOOK_STEPS=1: both sides through pr26_look.py, which then times every step
# from outside and prints the longest
parent_cmd="python3 -m chipbench.run"
[ -n "$LOOK_STEPS" ] && parent_cmd="python3 $top/chipbench/tools/calls/pr26_look.py"
i=0
one() {  # side seed
  if [ "$1" = parent ]; then
    (cd artifacts/checkout/parent && $parent_cmd --workload $W --seed $2 --seconds 45 --trace 0 \
      2> $top/chiprun_out/${tag}_last.err > $top/chiprun_out/${tag}_stdout.txt)
    grep "^look:" chiprun_out/${tag}_last.err | sed "s/^/parent seed $2 /" >> chiprun_out/${tag}_phases.txt
  else
    (cd $change && python3 chipbench/tools/calls/pr26_look.py --workload $W --seed $2 --seconds 45 --trace 0 \
      2> $top/chiprun_out/${tag}_last.err > $top/chiprun_out/${tag}_stdout.txt)
    grep "^look:" chiprun_out/${tag}_last.err | sed "s/^/change seed $2 /" >> chiprun_out/${tag}_phases.txt
  fi
  echo "{\"set\": \"$1\", \"seed\": $2, \"line\": $(tail -1 chiprun_out/${tag}_stdout.txt)}" >> chiprun_out/${tag}_$1.jsonl
  echo "$1 $2: $(grep 'first met' chiprun_out/${tag}_stdout.txt | cut -c1-120)"
}
for seed in "$@"; do
  if [ $((i % 2)) = 0 ]; then one parent $seed; one change $seed; else one change $seed; one parent $seed; fi
  i=$((i + 1))
done
python3 chipbench/tools/calls/summarise.py chiprun_out/${tag}_parent.jsonl chiprun_out/${tag}_change.jsonl
cat chiprun_out/${tag}_phases.txt
