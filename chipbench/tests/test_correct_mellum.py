"""The new architecture's cell through the comparison that decides
``correct``, as ``test_correct.py`` has the others: the rehearsal's tiny
widths on the CPU (window 16 under contexts of 24-96, so pages are released,
the ring wraps and the grouped matmul runs), kernels interpreted. A sound run
comes out correct; the control (the program's own w8/kv8 path) and a token
altered where it is produced come out not correct."""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from chipbench import run as bench_run

CELL = "mellum2-12b-a2.5b.repo-context-overload"


def line(capsys, plant=None, seed=3000000033, trace=0):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "5",
            "--trace", str(trace), "--rehearsal", "1"]
    if plant:
        argv += ["--plant", plant]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert "compared" == list(last)[-1]
    return last


def test_a_sound_run_is_correct_and_reports_the_new_counters(capsys):
    last = line(capsys, trace=1)
    assert last["rehearsal_correct"] is True, last["compared"]
    assert last["attempted"] > 0 and last["failed"] == 0
    m = last["metrics"]
    assert 0 < m["moe.experts_hit_share.repo"]["value"] <= 100
    assert m["moe.max_expert_load_ratio.repo"]["value"] >= 1
    assert m["cache.window_pages_released_per_step.repo"]["value"] > 0
    assert m["cache.full_pool_used_peak_share.repo"]["value"] > 0
    assert "engine.step_ms_p50.repo" in m and "sched.batch_occupancy.repo" in m
    # no share of a peak is reported from a CPU
    assert not any("roofline" in k or "mfu" in k for k in m)
    assert last["device"]["busy_s"] > 0


@pytest.mark.parametrize("plant", ["control", "token_altered"])
def test_the_control_and_a_fault_are_not_correct(capsys, plant):
    last = line(capsys, plant)
    assert last["planted"] == plant
    assert last["rehearsal_correct"] is False, last["compared"]
    assert any(c["value"] > c["limit"] for c in last["compared"])


def test_every_seed_offers_the_lengths_in_one_order():
    """``lengths_seed``: two seeds give the same prompt and answer lengths
    in the same places, block after block and in the staggered start, and
    other token ids; a mix without the key is the generator's own stream."""
    import itertools

    import numpy as np

    from chipbench import harness
    from chipbench.drivers import serve_arch
    from chipbench.traffic import gen
    mix = harness.Cell(CELL).mix
    assert "lengths_seed" in mix
    a, b = (list(itertools.islice(serve_arch.stream(mix, s, 98304), 3))
            for s in (2150000011, 2250000017))
    for ba, bb in zip(a, b):
        assert [(r.prompt_tokens, r.output_tokens, r.index) for r in ba] == \
               [(r.prompt_tokens, r.output_tokens, r.index) for r in bb]
        assert all(r.prompt.size == r.prompt_tokens for r in ba)
        assert not np.array_equal(ba[0].prompt, bb[0].prompt)
    sa, sb = (gen.stagger(x[0], s, 98304)
              for x, s in ((a, 2150000011), (b, 2250000017)))
    assert [(r.prompt.size, r.max_new) for r in sa] == \
           [(r.prompt.size, r.max_new) for r in sb]
    plain = {k: v for k, v in mix.items() if k != "lengths_seed"}
    mine = next(serve_arch.stream(plain, 7, 98304))
    theirs = next(gen.stream(plain, 7, 98304))
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(mine, theirs))
