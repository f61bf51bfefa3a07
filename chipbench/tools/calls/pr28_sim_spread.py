"""PR 28: why six seeds of ``mellum2-12b-a2.5b.repo-context-overload``
spread by 2.5-5% where ``longgen-overload`` spreads by 0.5%. No chip and no
program: the scheduler's loop replayed over the generator's own stream with
two measured step costs (a decode step, and what a prefill chunk adds to
it). It gives each seed's tokens/s in the measured order, so the spread is
the ORDER of prompt and answer lengths: a window of 45 s holds some 43
admissions of 2k-8k-token prompts (8 to 32 chunks each), and which of them
fall inside it moves the chunks a window holds by +-4%. Since the second
session the traffic file's ``lengths_seed`` gives every seed one order
(``drivers/serve_arch.py``); this replay still draws the order by seed, as
``traffic/gen.py`` does. Its last lines: would it have been enough to
balance the order (every 4 or 8 consecutive requests take one length of each
quarter or eighth of the range)? No: sets of six still spread 1-2%.

    python3 chipbench/tools/calls/pr28_sim_spread.py
"""
import json
import os
import statistics
import sys

sys.path.insert(0, os.getcwd())

import numpy as np

from chipbench.traffic import gen

MIX = json.load(open("chipbench/traffic/repo-context-overload.json"))
MEASURED = {3100000007: 998.6, 3200000011: 999.9, 3300000019: 976.5,
            3400000031: 1009.2, 3500000041: 988.1, 3600000053: 1015.1}


def balanced(reqs, seed, b, group):
    """The block's prompt and answer lengths, each arranged so that every
    ``group`` consecutive requests hold one length of each ``group``-th of
    the sorted range, in an order drawn by the seed."""
    rng = np.random.default_rng([seed, 9, b])
    per = len(reqs) // group

    def arrange(vals):
        vals = np.sort(np.asarray(vals))
        classes = [list(rng.permutation(vals[c * per:(c + 1) * per]))
                   for c in range(group)]
        return [int(v) for g in range(per)
                for v in rng.permutation([cl[g] for cl in classes])]
    return [{"pre": p, "out": o} for p, o in zip(
        arrange([r.prompt_tokens for r in reqs]),
        arrange([r.output_tokens for r in reqs]))]


def sim(seed, window=45.0, td=0.0224, tc=0.0163, chunk=256, group=0):
    """(tokens/s, chunks in the window): batch 32, one chunk a step for the
    oldest pending prefill, a step costs ``td`` and a chunk ``tc`` more."""
    stream = gen.stream(MIX, seed, 98304)
    rows = [{"pre": 0, "out": r.max_new - 1}
            for r in gen.stagger(next(stream), seed, 98304)]
    queue, t, tokens, chunks = [], 0.0, 0, 0
    while t < window:
        while len(queue) < 32:
            block = next(stream)
            queue += (balanced(block, seed, block[0].block, group) if group
                      else [{"pre": r.prompt_tokens, "out": r.output_tokens}
                            for r in block])
        while len(rows) < 32:
            rows.append(queue.pop(0))
        dt, pending = td, [r for r in rows if r["pre"] > 0]
        for r in rows:
            if r["pre"] == 0 and r["out"] > 0:
                r["out"] -= 1
                tokens += 1
        if pending:
            p = pending[0]
            p["pre"] -= min(chunk, p["pre"])
            dt += tc
            chunks += 1
            if p["pre"] == 0:           # the first token
                p["out"] -= 1
                tokens += 1
        rows = [r for r in rows if r["pre"] > 0 or r["out"] > 0]
        t += dt
    return tokens / t, chunks


def spread(values):
    q = statistics.quantiles(values, n=4)
    return 100 * (q[2] - q[0]) / statistics.median(values)


if __name__ == "__main__":
    for seed, measured in MEASURED.items():
        rate, chunks = sim(seed)
        print(f"seed {seed}: measured {measured:.1f}, replayed {rate:.1f} "
              f"tokens/s, {chunks} chunks")
    seeds = [int(x) for x in np.random.default_rng(1).integers(1, 2**31, 24)]
    for tc in (0.0222, 0.0163, 0.011, 0.005):
        v = [sim(s, tc=tc)[0] for s in seeds]
        print(f"a chunk adds {1e3 * tc:.1f} ms: mean {np.mean(v):.0f} tokens/s,"
              f" sd {100 * np.std(v) / np.mean(v):.2f}%, sets of six spread "
              + ", ".join(f"{spread(v[i:i + 6]):.2f}%" for i in range(0, 24, 6)))
    for group in (4, 8):
        v = [sim(s, group=group)[0] for s in seeds]
        print(f"lengths balanced in groups of {group}: sd "
              f"{100 * np.std(v) / np.mean(v):.2f}%, sets of six spread "
              + ", ".join(f"{spread(v[i:i + 6]):.2f}%" for i in range(0, 24, 6)))
