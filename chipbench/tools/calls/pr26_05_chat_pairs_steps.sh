# PR 26, chip call 4: chat-shared again, six more pairs on new seeds, both
# sides through pr26_look.py with every scheduler step timed from outside:
# in call 3 three of the change's six runs held seconds of stall inside
# engine.wait (245-288 steps where the others made 305-310); this shows
# whether the parent's runs hold such steps too.
LOOK_STEPS=1 bash chipbench/tools/calls/pr26_pairs.sh pr26_05 internlm2-1.8b.chat-shared \
  2147526031 2147526032 3000026033 3000026034 3000026035 3000026036
grep "steps between the snapshots:" chiprun_out/pr26_05_phases.txt
