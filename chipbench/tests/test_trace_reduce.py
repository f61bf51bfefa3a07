"""The trace reduction against a small trace recorded on a v5e
(``data/small.xplane.pb``, 1.5 MB: two train steps of a 2-layer model through
the flash kernels, four scheduler steps of a 4-slot engine through the paged
kernel, each inside the harness's annotation; ``tools/probe_trace.py`` wrote
it), and its pieces against hand-made intervals."""
import json
import os
import warnings

import numpy as np
import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")


@pytest.fixture(scope="module")
def reduced():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return tr.reduce_file(os.path.join(HERE, "data", "small.xplane.pb"), 1)


def pattern(metric):
    path = os.path.join(METRICS, metric + ".json")
    if not os.path.exists(path):      # the four-chip cell's, not a cell yet
        path = os.path.join(HERE, "data", "four_chip", "metrics", metric + ".json")
    with open(path) as f:
        return json.load(f)["op_pattern"]


def test_union_and_self_times_on_hand_made_intervals():
    sec, merged = tr.union_seconds([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert merged == [(0, 20), (30, 45)] and sec == pytest.approx(35e-9)
    # a while of 100 ns holding two bodies of 30 and 20 keeps 50 for itself
    ops = [(0, 100, "%while.1"), (10, 40, "%a"), (50, 70, "%b"), (120, 130, "%c")]
    own = {n: t for _, _, n, t in tr.self_times(ops)}
    assert own == {"%while.1": 50, "%a": 30, "%b": 20, "%c": 10}


def test_idle_gaps_are_charged_to_the_span_that_covers_them():
    busy = [(0, 10), (30, 40), (90, 100), (300, 310)]
    spans = [(5, 50, "chipbench.a"), (60, 120, "chipbench.b")]
    got = tr.charge_gaps(busy, spans)
    assert got == {"chipbench.a": pytest.approx(30e-9),      # 10..30 and 40..50
                   "chipbench.b": pytest.approx(50e-9),      # of 40..90 b covers 30, of 100..300 20
                   "_none_": pytest.approx(190e-9)}          # 50..60 and 120..300


def test_busy_and_window_of_the_recorded_trace(reduced):
    # busy again by brute force: a grid of nanoseconds
    ops = reduced["per_chip"][0]
    lo = min(s for s, _, _ in ops)
    grid = np.zeros(int(max(e for _, e, _ in ops) - lo) + 2, bool)
    for s, e, _ in ops:
        grid[int(round(s - lo)):int(round(e - lo))] = True
    assert reduced["busy_s"] == pytest.approx(grid.sum() / 1e9, rel=2e-3)
    assert reduced["busy_s"] == pytest.approx(0.000756644, rel=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["window_s"] == pytest.approx(0.0193, rel=0.01)
    idle = sum(s for _, s in reduced["idle_gaps"])
    first_op_to_last = (max(e for _, e, _ in ops) - lo) / 1e9
    assert idle == pytest.approx(first_op_to_last - reduced["busy_s"], rel=1e-6)


def test_spans_of_the_recorded_trace(reduced):
    names = [n for n, _ in reduced["spans"]]
    assert names.count("chipbench.train_step") == 2
    assert names.count("chipbench.sched_step") == 4
    assert all(0.001 < s < 0.005 for _, s in reduced["spans"])
    assert [n for n, _ in reduced["idle_gaps"]][:2] == \
        ["chipbench.sched_step", "chipbench.train_step"]


def test_kernels_of_the_recorded_trace_are_found_by_the_metrics_patterns(reduced):
    # 4 scheduler steps x 2 layers of paged attention; 2 train steps x 2
    # layers x (forward + its recomputation); 2 x 2 backward passes of two
    # kernels each. No collective on one chip.
    sec, n = tr.op_seconds(reduced, pattern("kernel.paged_attention_roofline.overload"))
    assert n == 8 and sec == pytest.approx(4.9165e-05, rel=1e-6)
    sec, n = tr.op_seconds(reduced, pattern("kernel.flash_fwd_roofline"))
    assert n == 8 and sec == pytest.approx(7.4603e-05, rel=1e-6)
    sec, n = tr.op_seconds(reduced, pattern("kernel.flash_bwd_roofline"))
    assert n == 8 and sec == pytest.approx(4.7017e-05, rel=1e-6)
    assert tr.op_seconds(reduced, pattern("hybrid.collective_exposed_share")) == (0, 0)
    assert tr.exposed_seconds(reduced, pattern("hybrid.collective_exposed_share")) == 0


def test_per_operation_time_is_self_time_and_sums_to_busy(reduced):
    # the line's operations do not overlap except by nesting, so self times
    # add up to the busy time
    assert sum(reduced["ops"].values()) == pytest.approx(reduced["busy_s"], rel=1e-3)
    top = dict((n, s) for n, s in reduced["device_ops"][:10])
    assert "%closed_call.11 pallas bf16[4,2,128]" in top
    assert all(len(n) <= 120 for n in top)


def test_exposed_seconds_on_hand_made_operations():
    red = {"per_chip": [[(0, 10, "%all-reduce.1"), (5, 20, "%fusion.1"),
                         (30, 40, "%all-gather-done.2"), (40, 50, "%fusion.2")]]}
    # all-reduce alone for 0..5, all-gather-done alone for 30..40
    assert tr.exposed_seconds(red, pattern("hybrid.collective_exposed_share")) \
        == pytest.approx(15e-9)
