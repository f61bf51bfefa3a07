# PR 26, chip call 5: where the change's set-up went in call 4 (chat-shared
# medians 41.6 s against the parent's 37.4, after 37.7 against 38.7 in call
# 3). Ten-second windows; what is read is setup_s and the driver's own line
# of compile requests, cache hits and compile seconds.
mkdir -p chiprun_out
top=$PWD
W=internlm2-1.8b.chat-shared
one() {  # label directory command...
  label=$1; dir=$2; shift 2
  (cd $dir && "$@" --workload $W --seed 3000026041 --seconds 10 --trace 0 \
    2> $top/chiprun_out/pr26_06_last.err > $top/chiprun_out/pr26_06_stdout.txt)
  echo "$label: $(grep 'first met' chiprun_out/pr26_06_stdout.txt | cut -d';' -f2) $(tail -1 chiprun_out/pr26_06_stdout.txt | grep -o '"setup_s": {"value": [0-9.]*')"
}
for round in 1 2; do
  one "parent plain          " artifacts/checkout/parent python3 -m chipbench.run
  one "change plain (archive)" artifacts/checkout/change python3 -m chipbench.run
  LOOK_STEPS=1 one "change look+steps     " artifacts/checkout/change python3 chipbench/tools/calls/pr26_look.py
  one "change plain (tree)   " . python3 -m chipbench.run
  one "parent look+steps     " artifacts/checkout/parent env LOOK_STEPS=1 python3 $top/chipbench/tools/calls/pr26_look.py
done
