"""The gated-attention, windowed MoE architecture's cell through the
comparison that decides ``correct``, as ``test_correct_kimi_k2.py`` has
Kimi's: the rehearsal's tiny widths on the CPU (chunks of 16 over pages of
8, a window of 16, 8 of 32 experts held, kernels interpreted; the rehearsal
runs at a routed scale of 0.5 for Kimi's reason: at a width of 128 bfloat16
flips a chosen expert often and at the published 2.448 those flips, which
the control shares, hide it). A sound run comes out correct and reports the
new counters; the control (the program's own w8/kv8 path) and a token
altered where it is produced come out not correct."""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from chipbench import run as bench_run

CELL = "trinity-large-preview.longctx-overload"


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
    assert 0 < m["moe.experts_hit_share.longctx"]["value"] <= 100
    # 8 of 32 experts held: a quarter of the items, or near it
    assert 15 < m["moe.items_held_share.longctx"]["value"] < 35
    assert 0 < m["cache.full_pool_used_peak_share.longctx"]["value"] <= 100
    assert m["cache.window_pages_released_per_step.longctx"]["value"] > 0
    assert m["engine.pipelined_launch_share.longctx"]["value"] > 90
    for name in ("engine.step_ms_p50", "sched.batch_occupancy",
                 "engine.device_wait_ms_per_step",
                 "engine.host_dispatch_ms_per_step",
                 "engine.host_commit_ms_per_step",
                 "sched.host_plan_ms_per_step", "sched.step_self_ms_per_step",
                 "engine.programs_first_met_in_window",
                 "engine.chunk_step_share", "engine.chunk_step_extra_ms",
                 "engine.host_dispatch_chunk_ms_per_step",
                 "host.stall_ms_in_window", "host.stall_off_cpu_share"):
        assert name + ".longctx" in m
    # no share of a peak is reported from a CPU
    assert not any("roofline" in k or "mfu" in k for k in m)
    assert last["device"]["busy_s"] > 0


@pytest.mark.parametrize("plant", ["control", "token_altered"])
def test_the_control_and_a_fault_are_not_correct(capsys, plant):
    last = line(capsys, plant)
    assert last["planted"] == plant
    assert last["rehearsal_correct"] is False, last["compared"]
    assert any(c["value"] > c["limit"] for c in last["compared"])
