"""The readers over the program's chunk-step counters and stall records
(``chipbench/readers/steps.py``): each on a hand-made record gives the
number worked out by hand, 0 where nothing happened, None where the
closing snapshot lacks the key (the parent of PR 36), and the traced
rehearsal of one expert cell and of ``chat-shared`` prints every one."""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from chipbench import harness
from chipbench import run as bench_run
from chipbench.readers import program, steps

MS = 1_000_000
STEMS = ("engine.chunk_step_share", "engine.chunk_step_extra_ms",
         "host.stall_ms_in_window", "host.stall_off_cpu_share")


def spec(name):
    return harness.load_json(harness.find("metrics", name + ".json"))


def stall(step, wall_ms, cpu_ms, span="engine.wait", kind="decode"):
    return {"span": span, "kind": kind, "step": step, "start_ns": 0,
            "wall_ns": wall_ms * MS, "cpu_ns": cpu_ms * MS,
            "sampled_ns": (wall_ms + 20) * MS, "voluntary_switches": 1,
            "involuntary_switches": 0}


def snapshot(steps_, step_ms, chunk_steps, chunk_ms, dispatch_chunk_ms,
             stall_ms, stalls):
    return {"sched_steps": steps_,
            "steps_committing_chunk_total": chunk_steps,
            "steps_committing_chunk_ns_total": chunk_ms * MS,
            "stalls_total": len(stalls), "stall_ns_total": stall_ms * MS,
            "stalls": stalls,
            "spans": {"sched.step": {"count": steps_, "ns": step_ms * MS,
                                     "max_ns": 0},
                      "engine.dispatch/chunk": {
                          "count": chunk_steps, "ns": dispatch_chunk_ms * MS,
                          "max_ns": 0}}}


@pytest.fixture
def record():
    """A hundred steps between the snapshots in 2,600 ms; forty of them
    committed a chunk and took 1,600 ms (40 ms each against 1,000 / 60);
    one stall of 1,500 ms before the opening snapshot's step, two after it
    of 2,000 ms (100 on the core) and 400 ms (all of it on the core)."""
    early = stall(90, 1500, 3)
    return {
        "stats_open": snapshot(100, 9000, 30, 1000, 60, 1500, [early]),
        "stats_close": snapshot(
            200, 11600, 70, 2600, 180, 3900,
            [early, stall(120, 2000, 100),
             stall(150, 400, 400, span="between_steps", kind=None)])}


def test_the_step_readers_give_the_numbers_worked_out_by_hand(record):
    assert steps.chunk_step_share(record, {}) == pytest.approx(40.0)
    assert steps.chunk_step_extra_ms(record, {}) == pytest.approx(
        1600 / 40 - 1000 / 60)
    assert steps.stall_ms(record, {}) == pytest.approx(2400.0)
    # what the harness did between set-up's last step and the window's first
    # comes after the opening snapshot under a step before it: left out too
    late = stall(99, 300, 300, span="between_steps", kind=None)
    late["start_ns"] = 5
    record["stats_close"]["stalls"].append(late)
    record["stats_close"]["stall_ns_total"] += 300 * MS
    assert steps.stall_ms(record, {}) == pytest.approx(2400.0)
    # the stall before the opening snapshot's step is left out
    assert steps.stall_off_cpu_share(record, {}) == pytest.approx(
        100 * (2400 - 100 - 400) / 2400)
    s = spec("engine.host_dispatch_chunk_ms_per_step.repo")
    assert s["reader"] == "program:span_ms_per_step"
    assert program.span_ms_per_step(record, s) == pytest.approx(120 / 100)


def test_a_window_where_nothing_happened_reads_zero(record):
    close = record["stats_close"]
    close.update(stall_ns_total=1500 * MS, stalls=close["stalls"][:1],
                 steps_committing_chunk_total=30,
                 steps_committing_chunk_ns_total=1000 * MS)
    assert steps.stall_ms(record, {}) == 0.0
    assert steps.stall_off_cpu_share(record, {}) == 0.0
    assert steps.chunk_step_share(record, {}) == 0.0
    assert steps.chunk_step_extra_ms(record, {}) == 0.0
    # every step committed a chunk: nothing to set them against
    close.update(steps_committing_chunk_total=130)
    assert steps.chunk_step_extra_ms(record, {}) == 0.0
    # a record's CPU is an upper bound and may pass its wall: never negative
    close["stalls"] = [stall(150, 200, 230)]
    assert steps.stall_off_cpu_share(record, {}) == 0.0


@pytest.mark.parametrize("reader,key", [
    ("chunk_step_share", "steps_committing_chunk_total"),
    ("chunk_step_extra_ms", "steps_committing_chunk_ns_total"),
    ("stall_ms", "stall_ns_total"), ("stall_ms", "stalls"),
    ("stall_off_cpu_share", "stalls")])
def test_a_program_from_before_these_totals_gives_none(record, reader, key):
    del record["stats_close"][key]
    assert getattr(steps, reader)(record, {}) is None
    old = {"stats_open": {"allocs_total": 1}, "stats_close": {"allocs_total": 9}}
    assert getattr(steps, reader)(old, {}) is None


def test_every_new_metric_names_its_reader_and_its_cell():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    mine = [m for m in bench["per_layer"]
            if m["name"].rsplit(".", 1)[0] in STEMS]
    assert len(mine) == 4 + 4 + 5 + 5
    for m in mine:
        s = spec(m["name"])
        mod, fn = s["reader"].split(":")
        assert mod == "steps" and callable(getattr(steps, fn))
        assert m["moves"] == ("itl_p95_ms" if m["name"].endswith(".chat")
                              else "serve_tokens_per_s")
    # appended: nothing that was there moved
    assert [m["name"] for m in bench["per_layer"][79:]] == [
        m["name"] for m in bench["per_layer"]
        if m["name"].rsplit(".", 1)[0] in STEMS + (
            "engine.host_dispatch_chunk_ms_per_step",)]


@pytest.mark.parametrize("workload,suffix", [
    ("internlm2-1.8b.chat-shared", "chat"),
    ("mellum2-12b-a2.5b.repo-context-overload", "repo")])
def test_the_traced_rehearsal_prints_every_new_metric(capsys, workload,
                                                      suffix):
    assert bench_run.main(["--workload", workload, "--seed", "3600000036",
                           "--seconds", "5", "--trace", "1",
                           "--rehearsal", "1"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = last["metrics"]
    want = [f"{stem}.{suffix}" for stem in STEMS]
    if suffix != "chat":
        want.append(f"engine.host_dispatch_chunk_ms_per_step.{suffix}")
    assert set(want) <= set(got), sorted(set(want) - set(got))
    share = got[f"engine.chunk_step_share.{suffix}"]["value"]
    assert 0 < share < 100          # both kinds of step in the window
    assert got[f"host.stall_ms_in_window.{suffix}"]["value"] >= 0
    assert 0 <= got[f"host.stall_off_cpu_share.{suffix}"]["value"] <= 100
    if suffix != "chat":
        chunk = got[f"engine.host_dispatch_chunk_ms_per_step.{suffix}"]
        assert 0 < chunk["value"] < got[
            f"engine.host_dispatch_ms_per_step.{suffix}"]["value"]
