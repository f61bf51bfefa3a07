#!/bin/sh
# PR 36, call 9 (after the driver's check found chat-shared's itl_p95_ms spread
# past its bound on the change's side): six pairs parent / change of
# chat-shared, each run the benchmark's own command as BENCHMARK.json gives it
# (python3 -m chipbench.run, untraced, 45 s), a seed a pair, the order
# P C, C P, ...  Side P runs from artifacts/checkout/parent (git archive
# 149af1d), side C from artifacts/checkout/final (git archive $(git write-tree)).
W=internlm2-1.8b.chat-shared
top=$PWD
out=$top/chiprun_out/pr36
mkdir -p $out
rm -f $out/09_P_$W.jsonl $out/09_C_$W.jsonl
n=0
for seed in 3600000901 2147484902 3600000903 1234567904 3600000905 2900000906; do
  n=$((n + 1))
  order="P C"; [ $((n % 2)) = 0 ] && order="C P"
  for side in $order; do
    d=$top/artifacts/checkout/final; [ $side = P ] && d=$top/artifacts/checkout/parent
    f=$out/09_${side}_${W}_${seed}_t0
    t0=$(date +%s)
    ( cd $d && python3 -m chipbench.run --workload $W --seed $seed --seconds 45 --trace 0 ) > $f.out 2> $f.err
    echo "== 09 $side $W seed $seed exit=$? after $(( $(date +%s) - t0 )) s"
    tail -1 $f.out | cut -c1-600
    grep -h "^stall in" $f.err | cut -c1-300
    echo "{\"set\": \"$side\", \"seed\": $seed, \"trace\": 0, \"line\": $(tail -1 $f.out)}" >> $out/09_${side}_$W.jsonl
  done
done
for f in $out/09_*.jsonl; do python3 chipbench/tools/calls/summarise.py $f; done
exit 0
