"""The Jamba architecture's cell through the comparison that decides
``correct``, as ``test_correct_nemotron_h.py`` has Nemotron's: the
rehearsal's tiny widths on the CPU (two periods of a Mamba-1 and an
attention layer, each with its MLP; chunks of 16 over pages of 8; kernels
interpreted). A sound run comes out correct and reports the new counters;
the control (the program's own w8/kv8 path) and a token altered where it is
produced come out not correct."""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from chipbench import run as bench_run

CELL = "ai21-jamba2-3b.longthink-overload"


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
    # 2 Mamba-1 layers a decode row, up to 4 rows
    rows = m["engine.mamba1_row_layers_per_decode.longthink"]["value"]
    assert 2 <= rows <= 8
    for name in ("engine.step_ms_p50", "engine.chunk_step_share",
                 "engine.chunk_step_extra_ms"):
        assert name + ".longthink" in m
    # no share of a peak is reported from a CPU
    assert not any("roofline" in k or "mfu" in k for k in m)
    assert last["device"]["busy_s"] > 0


@pytest.mark.parametrize("plant", ["control", "token_altered"])
def test_the_control_and_a_fault_are_not_correct(capsys, plant):
    last = line(capsys, plant)
    assert last["planted"] == plant
    assert last["rehearsal_correct"] is False, last["compared"]
    assert any(c["value"] > c["limit"] for c in last["compared"])
