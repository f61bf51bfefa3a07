#!/bin/sh
# PR 35, final call: the claimed cell from the committed files alone
# (artifacts/checkout/final = git archive $(git write-tree)) against the
# parent (artifacts/checkout/parent = git archive ef71475): one traced run a
# side, then three pairs, a seed a pair. Then Nemotron's cell again, the
# change first (C P C P on two new seeds): call 4's run of the change held
# one host pause of 1.5 s.
sh tools/chip_calls/pr35_pairs.sh 05_longgen internlm2-1.8b.longgen-overload 3500017111 \
    3500018123 3500019131 3500020147
W=nemotron3-super-120b-a12b.reasoning-overload
top=$PWD; out=$top/chiprun_out/pr35
for spec in C:3500021153 P:3500021153 C:3500022161 P:3500022161; do
  side=${spec%%:*}; seed=${spec##*:}
  d=$top/artifacts/checkout/final; [ $side = P ] && d=$top/artifacts/checkout/parent
  t0=$(date +%s)
  ( cd $d && python3 -m chipbench.run --workload $W --seed $seed --seconds 45 --trace 0 ) \
      > $out/05_nemo_${side}_$seed.out 2> $out/05_nemo_${side}_$seed.err
  echo "$W $side seed $seed exit=$? after $(( $(date +%s) - t0 )) s: $(tail -1 $out/05_nemo_${side}_$seed.out | cut -c1-260)"
  grep -h "steps by program" $out/05_nemo_${side}_$seed.out | cut -c1-330
done
