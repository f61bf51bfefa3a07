"""The comparison that decides ``correct``, shown to fail: each run skips
the look for a chip (the rehearsal's tiny sizes on the CPU, kernels
interpreted), drives the rest of a run, and reads the result line. Sound
runs come out correct; the control (the nearest precision below bfloat16)
and each planted fault come out not correct."""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import pytest

from chipbench import harness
from chipbench import run as bench_run

CHAT = "internlm2-1.8b.chat-shared"
OVER = "internlm2-1.8b.longgen-overload"
TRAIN = "ernie45-0.3b.train-4k"
HYBRID = "internlm2-1.8b.train-4k-fsdp2tp2"


FOUR_CHIP = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "four_chip")
HYBRID_ENTRY = {"name": HYBRID, "config": "internlm2-1.8b",
                "traffic": "train-4k-fsdp2tp2", "chips": 4, "why": "see PERF.md"}


@pytest.fixture(autouse=True)
def with_the_four_chip_cell(monkeypatch):
    """The four-chip cell is not in BENCHMARK.json yet (PERF.md, Open
    questions), but the driver's mesh path and the sharded reference are
    there: the tests add the cell's entry, and its job and rehearsal files
    from tests/data/four_chip, to what the harness reads."""
    load = harness.load_json
    monkeypatch.setattr(harness, "DATA_DIRS", harness.DATA_DIRS + [FOUR_CHIP])

    def patched(path):
        d = load(path)
        if os.path.basename(path) == "BENCHMARK.json":
            d["workloads"] = d["workloads"] + [HYBRID_ENTRY]
        return d
    monkeypatch.setattr(harness, "load_json", patched)


def line(capsys, workload, plant=None, seed=3000000033, seconds=5, trace=0):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearsal", "1"]
    if plant:
        argv += ["--plant", plant]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    # a rehearsal names the CPU and is never correct as a chip run
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert "compared" == list(last)[-1]
    for c in last["compared"]:
        assert f"compared {c['name']}=" in out.err
    return last


@pytest.mark.parametrize("workload", [CHAT, OVER, TRAIN, HYBRID])
def test_a_sound_run_is_correct(capsys, workload):
    last = line(capsys, workload)
    assert last["rehearsal_correct"] is True, last["compared"]
    assert last["attempted"] > 0 and last["failed"] == 0
    if workload != HYBRID:
        assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2


@pytest.mark.parametrize("workload,plant", [
    (CHAT, "token_altered"), (OVER, "token_altered"),
    (CHAT, "control"), (OVER, "control"),
    (TRAIN, "state_unchanged"), (TRAIN, "half_batch"), (TRAIN, "control"),
    # at fsdp=2 an exchange between chips left out is each shard stepping on
    # its own half of the batch: it is planted as half_batch
    (HYBRID, "half_batch"), (HYBRID, "state_unchanged"),
])
def test_the_control_and_each_fault_are_not_correct(capsys, workload, plant):
    last = line(capsys, workload, plant)
    assert last["planted"] == plant
    assert last["rehearsal_correct"] is False, last["compared"]
    assert any(c["value"] > c["limit"] for c in last["compared"])


def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(capsys):
    last = line(capsys, OVER, trace=1)
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    assert last["breakdown"]["device_ops"] and len(last["breakdown"]["device_ops"]) <= 10
    assert "engine.programs_first_met_in_window.overload" in last["metrics"]
    # no share of a peak is reported from a CPU
    assert not any("roofline" in k or "mfu" in k for k in last["metrics"])
