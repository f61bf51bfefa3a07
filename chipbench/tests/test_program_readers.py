"""The readers over the program's span totals and counters
(``chipbench/readers/program.py``): each on a hand-made record gives the
number worked out by hand, None where a total is missing, and the rehearsal
of each serving cell prints every one of its new metrics."""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from chipbench import harness
from chipbench import run as bench_run
from chipbench.readers import program

CHAT = "internlm2-1.8b.chat-shared"
OVER = "internlm2-1.8b.longgen-overload"
MS = 1_000_000


def snapshot(steps, admit, plan, dispatch, wait, commit, step, **counters):
    spans = {"sched.step": {"count": steps, "ns": step * MS},
             "sched.admit": {"count": steps, "ns": admit * MS},
             "sched.plan": {"count": steps, "ns": plan * MS},
             "engine.dispatch": {"count": 2 * steps, "ns": dispatch * MS},
             "engine.wait": {"count": 2 * steps, "ns": wait * MS},
             "engine.commit": {"count": 2 * steps, "ns": commit * MS}}
    return {"allocs_total": 7, "spans": spans, **counters}


@pytest.fixture
def record():
    """Ten steps between the snapshots: 2 ms admitting, 3 planning, 20
    dispatching, 1,300 waiting, 10 committing, 1,340 in all."""
    return {
        "stats_open": snapshot(
            100, 10, 20, 300, 9000, 50, 9400, queue_wait_ns_total=40 * MS,
            admissions_total=4, prompt_tokens_total=4000,
            prefix_hit_tokens_total=3000),
        "stats_close": snapshot(
            110, 12, 23, 320, 10300, 60, 10740, queue_wait_ns_total=100 * MS,
            admissions_total=16, prompt_tokens_total=16000,
            prefix_hit_tokens_total=13200)}


def spec(name):
    return harness.load_json(harness.find("metrics", name + ".json"))


@pytest.mark.parametrize("metric,by_hand", [
    ("sched.host_plan_ms_per_step", (2 + 3) / 10),
    ("engine.host_dispatch_ms_per_step", 20 / 10),
    ("engine.device_wait_ms_per_step", 1300 / 10),
    ("engine.host_commit_ms_per_step", 10 / 10),
    ("sched.step_self_ms_per_step", (1340 - 2 - 3 - 20 - 1300 - 10) / 10),
])
@pytest.mark.parametrize("cell", ["chat", "overload"])
def test_a_span_reader_gives_the_number_worked_out_by_hand(record, metric,
                                                           cell, by_hand):
    s = spec(f"{metric}.{cell}")
    assert s["reader"] == "program:span_ms_per_step"
    assert program.span_ms_per_step(record, s) == pytest.approx(by_hand)


def test_the_counter_readers_give_the_numbers_worked_out_by_hand(record):
    s = spec("sched.queue_wait_mean_ms.chat")
    assert program.counter_ratio(record, s) == pytest.approx(60 / 12)
    s = spec("cache.prefix_hit_tokens_share.chat")
    assert program.counter_ratio(record, s) == pytest.approx(
        100 * 10200 / 12000)


def test_a_total_that_is_missing_gives_none(record):
    self_time = spec("sched.step_self_ms_per_step.chat")
    wait = spec("sched.queue_wait_mean_ms.chat")
    # a program from before the spans: no totals at all
    old = {"stats_open": {"allocs_total": 1}, "stats_close": {"allocs_total": 9}}
    assert program.span_ms_per_step(old, self_time) is None
    assert program.counter_ratio(old, wait) is None
    # one child of the step gone, one counter gone
    del record["stats_close"]["spans"]["engine.commit"]
    del record["stats_close"]["admissions_total"]
    assert program.span_ms_per_step(record, self_time) is None
    assert program.counter_ratio(record, wait) is None
    assert program.span_ms_per_step(
        record, spec("engine.device_wait_ms_per_step.chat")) == pytest.approx(130)


def test_no_step_or_no_admission_between_the_snapshots_gives_none(record):
    record["stats_close"] = record["stats_open"]
    assert program.span_ms_per_step(
        record, spec("engine.device_wait_ms_per_step.overload")) is None
    assert program.counter_ratio(
        record, spec("sched.queue_wait_mean_ms.chat")) is None


def test_a_total_first_seen_after_the_opening_snapshot_counts_from_zero(record):
    del record["stats_open"]["spans"]["engine.wait"]
    del record["stats_open"]["prefix_hit_tokens_total"]
    assert program.span_ms_per_step(
        record, spec("engine.device_wait_ms_per_step.chat")) == pytest.approx(1030)
    assert program.counter_ratio(
        record, spec("cache.prefix_hit_tokens_share.chat")) == pytest.approx(110)


@pytest.mark.parametrize("workload,suffix,count", [(CHAT, "chat", 7),
                                                   (OVER, "overload", 5)])
def test_the_traced_rehearsal_prints_every_new_metric(capsys, workload,
                                                      suffix, count):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    new = [m["name"] for m in bench["per_layer"]
           if workload in m["workloads"]
           and spec(m["name"])["reader"].startswith("program:")]
    assert len(new) == count and all(n.endswith("." + suffix) for n in new)
    assert bench_run.main(["--workload", workload, "--seed", "3000000057",
                           "--seconds", "5", "--trace", "1",
                           "--rehearsal", "1"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = last["metrics"]
    assert set(new) <= set(got), sorted(set(new) - set(got))
    phases = [got[f"{p}.{suffix}"]["value"] for p in (
        "sched.host_plan_ms_per_step", "engine.host_dispatch_ms_per_step",
        "engine.device_wait_ms_per_step", "engine.host_commit_ms_per_step",
        "sched.step_self_ms_per_step")]
    assert all(v >= 0 for v in phases)
    # the spans cover the step: what no child holds is a small part of it
    assert phases[-1] < 0.2 * sum(phases)
    if suffix == "chat":
        inside = got["cache.prefix_hit_tokens_share.chat"]["value"]
        outside = got["cache.prefix_hit_share.chat"]["value"]
        assert inside == pytest.approx(outside, abs=1.0)
        assert got["sched.queue_wait_mean_ms.chat"]["value"] >= 0
