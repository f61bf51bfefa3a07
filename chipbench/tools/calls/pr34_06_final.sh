#!/bin/sh
# PR 34, call 6: the new cell once more from the committed files (artifacts/
# checkout/final = git archive $(git write-tree) of the tree as handed in),
# then the hybrid cell again, change first: C P C P on two new seeds, after
# call 4's two runs of the change each held one host pause of 2.0 and 3.6 s.
sh chipbench/tools/calls/pr34_05_committed_files.sh 06_s1:3660000077:0 06_s2:3670000079:0 || exit 1
W=nemotron3-super-120b-a12b.reasoning-overload
top=$PWD; mkdir -p chiprun_out/pr34
for spec in C:3680000083 P:3680000083 C:3690000087 P:3690000087; do
  side=${spec%%:*}; seed=${spec##*:}
  d=$top/artifacts/checkout/final; [ $side = P ] && d=$top/artifacts/checkout/parent
  t0=$(date +%s)
  ( cd $d && python3 -m chipbench.run --workload $W --seed $seed --seconds 45 --trace 0 ) \
      > chiprun_out/pr34/06_nemo_${side}_$seed.out 2> chiprun_out/pr34/06_nemo_${side}_$seed.err
  echo "$W $side seed $seed exit=$? after $(( $(date +%s) - t0 )) s: $(tail -1 chiprun_out/pr34/06_nemo_${side}_$seed.out | cut -c1-260)"
  grep -h "steps by program" chiprun_out/pr34/06_nemo_${side}_$seed.out | cut -c1-330
done
