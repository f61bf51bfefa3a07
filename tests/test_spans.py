"""The serving step's spans and counters (observability/spans.py) and the
names on the programs and the Pallas kernels: totals that nest, spans on the
profiler's clock read back from a CPU trace, counters worked out by hand,
and the names as they lower for the TPU."""
import glob
import inspect
import logging
import re
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.inference.predictor import ContinuousBatchingEngine
from paddle_tpu.models import llama, train
from paddle_tpu.observability import spans as spans_mod
from paddle_tpu.observability.spans import SpanTotals
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import ServingScheduler

CHILDREN = ("sched.admit", "sched.plan", "engine.dispatch", "engine.wait",
            "engine.commit")
PAGE = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def scheduler(tiny, **kw):
    cfg, params = tiny
    eng = ContinuousBatchingEngine(params, cfg, max_batch=4, page_size=PAGE,
                                   max_len=64, prefill_chunk=16)
    return ServingScheduler(eng, **kw)


def prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        3, cfg.vocab_size, (n,)).astype(np.int32)


def span_ns(stats, name):
    return stats["spans"][name]["ns"]


def test_totals_are_monotonic_and_a_parents_self_time_is_what_its_children_leave():
    t = SpanTotals()
    seen = []
    for _ in range(3):
        with t.span("parent", step=1):
            with t.span("child", kind="a"):
                sum(range(2000))
            with t.span("child", kind="b"):
                pass
            sum(range(2000))
        seen.append(t.snapshot())
    for a, b in zip(seen, seen[1:]):
        for name in ("parent", "child"):
            assert b["spans"][name]["ns"] > a["spans"][name]["ns"]
            assert b["spans"][name]["count"] > a["spans"][name]["count"]
    last = seen[-1]["spans"]
    assert last["parent"]["count"] == 3 and last["child"]["count"] == 6
    own = last["parent"]["ns"] - last["child"]["ns"]
    assert 0 < own < last["parent"]["ns"]
    assert t.ns("parent") == last["parent"]["ns"] and t.ns("never") == 0


def test_counts_add_up_and_a_snapshot_is_a_copy():
    t = SpanTotals()
    t.count("tokens_total", 5)
    snap = t.snapshot()
    t.count("tokens_total", 7)
    with t.span("s"):
        pass
    assert snap["tokens_total"] == 5 and snap["spans"] == {}
    assert t.snapshot()["tokens_total"] == 12
    assert t.snapshot()["spans"]["s"]["count"] == 1


def test_a_span_that_raises_still_counts():
    t = SpanTotals()
    with pytest.raises(KeyError):
        with t.span("s"):
            raise KeyError("x")
    assert t.snapshot()["spans"]["s"]["count"] == 1


def test_a_names_kinds_sum_to_the_name_exactly_and_keep_their_longest():
    t = SpanTotals()
    for kind, n in (("chunk", 3), ("decode", 5), ("swap", 1)):
        for i in range(n):
            with t.span("engine.wait", kind=kind):
                sum(range(100 * (i + 1)))
    with t.span("engine.commit", rows=2):
        pass
    spans = t.snapshot()["spans"]
    kinds = {k: v for k, v in spans.items() if k.startswith("engine.wait/")}
    assert sorted(kinds) == ["engine.wait/chunk", "engine.wait/decode",
                             "engine.wait/swap"]
    assert kinds["engine.wait/decode"]["count"] == 5
    for field in ("count", "ns"):
        assert sum(v[field] for v in kinds.values()) \
            == spans["engine.wait"][field]
    assert spans["engine.wait"]["max_ns"] \
        == max(v["max_ns"] for v in kinds.values())
    assert [k for k in spans if k.startswith("engine.commit")] \
        == ["engine.commit"]                    # no kind, no second cell
    for v in spans.values():
        assert v["max_ns"] >= v["ns"] / v["count"] > 0
    assert t.calls("engine.wait/chunk") == 3 and t.calls("never") == 0


def _spin_until_cpu(ns):
    t0 = time.thread_time_ns()
    while time.thread_time_ns() - t0 < ns:
        sum(range(1000))


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_a_stall_record_tells_a_thread_off_its_core_from_one_computing(
        how, caplog):
    t = SpanTotals()
    t.step_begins(7)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving"):
        with t.span("sched.step", step=7):
            with t.span("engine.wait", kind="decode"):
                if how == "sleeps":
                    time.sleep(0.15)
                else:
                    _spin_until_cpu(150_000_000)
            with t.span("engine.commit", rows=1):
                pass
    st = t.snapshot()
    rec, = st["stalls"]             # the inner span's, and only that one
    assert (rec["span"], rec["kind"], rec["step"]) == ("engine.wait",
                                                       "decode", 7)
    assert rec["wall_ns"] >= 150_000_000
    assert st["stalls_total"] == 1 and st["stall_ns_total"] == rec["wall_ns"]
    assert rec["wall_ns"] == st["spans"]["engine.wait"]["max_ns"]
    if how == "sleeps":
        assert rec["cpu_ns"] < rec["wall_ns"] / 10
        assert rec["voluntary_switches"] >= 1
    else:       # an absolute reading: other processes share the machine
        assert rec["cpu_ns"] >= 150_000_000
    assert rec["involuntary_switches"] >= 0
    line, = [r.getMessage() for r in caplog.records]
    assert "engine.wait (decode)" in line and "step 7" in line


def test_nothing_is_logged_or_recorded_where_nothing_stalls(caplog):
    t = SpanTotals()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving"):
        for step in range(3):
            t.step_begins(step)
            with t.span("sched.step", step=step):
                with t.span("engine.wait", kind="decode"):
                    pass
            t.step_ends(True)
    st = t.snapshot()
    assert st["stalls"] == [] and st["stalls_total"] == 0
    assert st["stall_ns_total"] == 0 and not caplog.records


def test_a_long_span_around_a_stall_adds_only_its_own_long_time():
    t = SpanTotals()
    with t.span("engine.wait", kind="chunk"):
        time.sleep(0.12)        # outside a scheduler's step: the engine's
    assert t.snapshot()["stalls"] == []     # own calls queue and wait
    t.step_begins(0)
    with t.span("outer"):
        with t.span("inner"):
            time.sleep(0.12)
    assert [r["span"] for r in t.snapshot()["stalls"]] == ["inner"]
    with t.span("outer"):
        with t.span("inner"):
            time.sleep(0.12)
        time.sleep(0.12)
    recs = t.snapshot()["stalls"]
    assert [r["span"] for r in recs] == ["inner", "inner", "outer"]
    # the outer record holds its own time, not the inner stall's again
    assert 120_000_000 <= recs[2]["wall_ns"] < 200_000_000
    assert t.snapshot()["stall_ns_total"] == sum(r["wall_ns"] for r in recs)


def test_a_build_is_no_stall_and_is_taken_out_of_the_spans_around_it():
    t = SpanTotals()
    t.step_begins(0)
    with t.span("sched.step", step=0):
        with t.span("engine.dispatch", kind="chunk"):
            with t.span("engine.build_program", kind="chunk", ctx_cap=8,
                        width=16):
                time.sleep(0.12)
            with t.span("engine.build_program", kind="decode"):
                pass                # a short one still counts for nothing
    st = t.snapshot()
    assert st["stalls"] == [] and st["stalls_total"] == 0
    assert st["spans"]["engine.build_program/chunk"]["count"] == 1
    assert st["spans"]["sched.step"]["max_ns"] >= 120_000_000


def test_the_stall_list_is_bounded_and_the_totals_keep_counting(monkeypatch):
    monkeypatch.setattr(spans_mod, "STALL_NS", 200_000)
    t = SpanTotals()
    for step in range(40):
        t.step_begins(step)
        with t.span("engine.wait", kind="decode"):
            time.sleep(0.0005)
    st = t.snapshot()
    assert st["stalls_total"] == 40
    assert len(st["stalls"]) == spans_mod.STALLS_KEPT == 32
    assert [r["step"] for r in st["stalls"]] == list(range(8, 40))
    assert st["stall_ns_total"] >= sum(r["wall_ns"] for r in st["stalls"])


def test_the_threads_clock_is_sampled_once_in_a_while_not_every_step(
        monkeypatch):
    reads = []
    sample = spans_mod._thread_sample
    monkeypatch.setattr(spans_mod, "_thread_sample",
                        lambda: reads.append(1) or sample())
    t = SpanTotals()
    assert len(reads) == 1                      # at construction
    for step in range(50):
        t.step_begins(step)
        with t.span("sched.step", step=step):
            pass
        t.step_ends(True)
    assert len(reads) == 1                      # 50 steps in under 50 ms
    monkeypatch.setattr(spans_mod, "SAMPLE_NS", 0)
    t.step_begins(50)
    t.step_begins(51)
    assert len(reads) == 3
    monkeypatch.setattr(spans_mod, "STALL_NS", 100_000)
    with t.span("engine.wait", kind="decode"):  # inside step 51
        time.sleep(0.001)
    rec, = t.snapshot()["stalls"]               # a stall's exit samples too
    assert len(reads) == 4
    # what the CPU reading covers: from step 51's sample to the stall's exit
    assert rec["wall_ns"] <= rec["sampled_ns"] < rec["wall_ns"] + 50_000_000


@pytest.mark.parametrize("more", [True, False])
def test_a_pause_between_two_steps_is_a_record_while_work_remained(more):
    t = SpanTotals()
    t.step_begins(4)
    with t.span("sched.step", step=4):
        pass
    t.step_ends(more)
    time.sleep(0.12)
    t.step_begins(5)
    recs = t.snapshot()["stalls"]
    if not more:
        assert recs == []
        return
    rec, = recs
    assert (rec["span"], rec["kind"], rec["step"]) == ("between_steps",
                                                       None, 4)
    assert rec["wall_ns"] >= 120_000_000 > 10 * rec["cpu_ns"]
    # the record lies between the two steps on the spans' clock
    assert rec["start_ns"] + rec["wall_ns"] <= time.perf_counter_ns()


@pytest.mark.parametrize("overlap", [False, True])
def test_a_step_that_commits_a_chunk_is_counted_with_its_wall(tiny, overlap):
    """A prompt of 37 tokens is three chunks of 16; each is committed by
    one step, the decode steps after them by none."""
    s = scheduler(tiny, overlap=overlap)
    s.submit(prompt(tiny[0], 37, 8), max_new_tokens=6)
    seen = []
    more = True
    while more:
        a = s.stats()
        more = s.step()
        b = s.stats()
        grew = {k: b[k] - a[k] for k in ("steps_committing_chunk_total",
                                         "steps_committing_chunk_ns_total")}
        chunk = (b["spans"].get("engine.wait/chunk", {"count": 0})["count"]
                 - a["spans"].get("engine.wait/chunk", {"count": 0})["count"])
        wall = span_ns(b, "sched.step") - a["spans"].get(
            "sched.step", {"ns": 0})["ns"]
        seen.append(bool(chunk))
        if chunk:
            assert grew == {"steps_committing_chunk_total": 1,
                            "steps_committing_chunk_ns_total": wall}
        else:
            assert not any(grew.values())
    st = s.stats()
    assert st["steps_committing_chunk_total"] == sum(seen) == 3
    assert len(seen) - sum(seen) >= 5           # the decode-only steps
    assert st["steps_committing_chunk_ns_total"] < span_ns(st, "sched.step")
    for name in ("engine.dispatch", "engine.wait", "engine.build_program"):
        kinds = [v for k, v in st["spans"].items()
                 if k.startswith(name + "/")]
        assert len(kinds) == 2                  # chunk and decode
        for field in ("count", "ns"):
            assert sum(v[field] for v in kinds) == st["spans"][name][field]
    assert st["stalls_total"] == len(st["stalls"])


def test_the_steps_spans_cover_the_step(tiny):
    s = scheduler(tiny)
    s.submit(prompt(tiny[0], 21, 1), max_new_tokens=6)
    while s.step():
        pass
    st = s.stats()
    spans = st["spans"]
    assert spans["sched.step"]["count"] == st["sched_steps"]
    assert spans["sched.admit"]["count"] == spans["sched.plan"]["count"] \
        == st["sched_steps"]
    # a prefill of two chunks and five decode steps: one dispatch, one wait
    # and one commit a program
    assert spans["engine.dispatch"]["count"] == 7
    assert spans["engine.wait"]["count"] == spans["engine.commit"]["count"] == 7
    # one chunk program of each width and the decode program were built
    # once, inside a dispatch
    assert spans["engine.build_program"]["count"] == 3
    assert spans["engine.build_program"]["ns"] < spans["engine.dispatch"]["ns"]
    own = span_ns(st, "sched.step") - sum(span_ns(st, c) for c in CHILDREN)
    assert 0 <= own < 0.2 * span_ns(st, "sched.step")


@pytest.mark.parametrize("overlap", [False, None])
def test_host_overhead_fraction_comes_from_the_span_totals(tiny, overlap):
    """Exposed host time is the step less the device wait and, where a
    program was in flight throughout (the pipelined step), less what ran
    under it."""
    s = scheduler(tiny, overlap=overlap)
    s.submit(prompt(tiny[0], 12, 2), max_new_tokens=8)
    for _ in range(4):
        s.step()
    before = s.stats()
    s.step()
    after = s.stats()

    def grew(*names):
        return sum(span_ns(after, n) - span_ns(before, n) for n in names)
    wall, wait = grew("sched.step"), grew("engine.wait")
    hidden = 0 if overlap is False else grew(
        "sched.admit", "sched.plan", "engine.dispatch", "engine.commit")
    assert s.last_host_frac == pytest.approx((wall - wait - hidden) / wall)
    assert 0.0 < s.last_host_frac <= 1.0
    assert "host_overhead_fraction" in after
    # no second set of stamps
    assert "perf_counter_ns" not in inspect.getsource(ServingScheduler.step)
    assert not hasattr(s.engine, "take_fence_ns")
    assert not hasattr(s.engine, "_fence_ns")


@pytest.mark.parametrize("overlap", [False, True])
def test_spans_a_step_do_not_follow_the_rows(tiny, overlap):
    """A decode step opens as many spans with four rows as with one."""
    per_step = []
    for rows in (1, 4):
        s = scheduler(tiny, overlap=overlap)
        for i in range(rows):
            s.submit(prompt(tiny[0], 6, 10 + i), max_new_tokens=8)
        s.step()
        while s.engine.pending_prefills():      # one chunk a step
            s.step()
        for _ in range(3):          # pipelined: until a decode step is read
            s.step()                # every row prefilled and decoding
        assert len(s.last_plan.decode_slots) == rows
        assert not s.last_plan.prefills
        a = s.stats()["spans"]
        s.step()
        b = s.stats()["spans"]
        per_step.append({n: b[n]["count"] - a[n]["count"] for n in b})
    assert per_step[0] == per_step[1]
    assert per_step[0]["sched.step"] == 1
    assert per_step[0]["engine.dispatch"] == per_step[0]["engine.wait"] \
        == per_step[0]["engine.commit"] == 1


def test_admission_and_prefix_counters_by_hand(tiny):
    """A 16-token system prompt (two pages) with tails of 5, 7 and 3: the
    first request fills the cache, the other two find both pages."""
    cfg = tiny[0]
    ticks = iter(range(1, 10_000))
    s = scheduler(tiny, clock=lambda: float(next(ticks)))
    system = prompt(cfg, 2 * PAGE, 3)

    def ask(tail, seed):
        return s.submit(np.concatenate([system, prompt(cfg, tail, seed)]),
                        max_new_tokens=3)
    first = ask(5, 4)               # enqueued at tick 1, admitted at tick 2
    while s.step():
        pass
    assert first.done
    alone = s.stats()
    assert alone["admissions_total"] == 1
    assert alone["queue_wait_ns_total"] == 1_000_000_000
    assert alone["prompt_tokens_total"] == 21
    assert alone["prefix_hit_tokens_total"] == 0
    ask(7, 5)                       # tick k
    ask(3, 6)                       # tick k + 1; both admitted at tick k + 2
    while s.step():
        pass
    st = s.stats()
    assert st["admissions_total"] == 3
    assert st["queue_wait_ns_total"] == (1 + 2 + 1) * 1_000_000_000
    assert st["prompt_tokens_total"] == 21 + 23 + 19
    assert st["prefix_hit_tokens_total"] == 2 * (2 * PAGE)
    # the allocator's count keeps its meaning: it counts the trie's own
    # references too, so it is not the same number
    assert st["shares_total"] >= 4


def test_spans_sit_on_the_profilers_clock_nested_on_one_line(tiny, tmp_path):
    s = scheduler(tiny)
    s.submit(prompt(tiny[0], 6, 7), max_new_tokens=8)
    for _ in range(3):              # programs built: the trace sees none,
        s.step()                    # and a decode step is there to be read
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            s.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host, = [p for p in data.planes if p.name == "/host:CPU"]
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for e in line.events if e.name.startswith("paddle_tpu.")]
             for line in host.lines]
    line, = [evs for evs in lines if evs]       # one thread, one line
    steps = [e for e in line if e[0] == "paddle_tpu.sched.step"]
    assert len(steps) == 3
    assert [e[3]["step"] for e in steps] == [3, 4, 5]
    for _, s0, s1, _ in steps:
        inside = [e for e in line if s0 <= e[1] and e[2] <= s1
                  and e[0] != "paddle_tpu.sched.step"]
        # the pipelined step: read the step before last, then admit,
        # plan and launch behind the one still running
        assert [e[0].split(".", 1)[1] for e in inside] == [
            "engine.wait", "engine.commit", "sched.admit", "sched.plan",
            "engine.dispatch"]
        for a, b in zip(inside, inside[1:]):    # siblings, in order
            assert a[2] <= b[1]
        kinds = {e[0]: e[3].get("kind") for e in inside}
        assert kinds["paddle_tpu.engine.dispatch"] == "decode"
        assert kinds["paddle_tpu.engine.wait"] == "decode"
        assert inside[1][3]["rows"] == 1
    assert not [e for e in line if e[0] == "paddle_tpu.engine.build_program"]


def _tpu_text(fn, *shapes):
    with fa.force_compiled_lowering():
        return jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()


def _kernels(text):
    """name -> metadata of every Pallas call in a module's text."""
    found = {}
    for m in re.finditer(r'kernel_name = "(\w+)"', text):
        found[m.group(1)] = None
    for m in re.finditer(r'kernel_metadata = "([^\n]*?)"\}', text):
        meta = m.group(1).replace("\\0A", "").replace("\\22", '"')
        name = re.search(r'"kernel":"(\w+)"', meta).group(1)
        found[name] = meta
    return found


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkv",
                                    "flash_bwd_dq", "paged_attention"])
def test_each_kernel_carries_its_name_when_lowered_for_tpu(kernel):
    bf16, i32 = jnp.bfloat16, jnp.int32
    if kernel == "paged_attention":
        pool = jax.ShapeDtypeStruct((65, 16, 2, 128), bf16)
        text = _tpu_text(
            lambda q, k, v, bt, ln: pa.paged_attention(q, k, v, bt, ln),
            jax.ShapeDtypeStruct((8, 4, 128), bf16), pool, pool,
            jax.ShapeDtypeStruct((8, 8), i32), jax.ShapeDtypeStruct((8,), i32))
    else:
        q = jax.ShapeDtypeStruct((2, 1024, 4, 128), bf16)
        k = jax.ShapeDtypeStruct((2, 1024, 2, 128), bf16)

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()
        text = _tpu_text(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)
    found = _kernels(text)
    assert kernel in found, sorted(found)
    assert found[kernel] == '{"kernel":"%s"}' % kernel


def _module_name(jitted, *args):
    return re.search(r"module @(\w+)", jitted.trace(*args).lower().as_text()
                     ).group(1)


def test_the_engines_programs_lower_under_their_names(tiny):
    eng = scheduler(tiny).engine
    cache = eng.cache
    B = eng.max_batch
    decode = (eng.params, jnp.zeros((B,), jnp.int32), cache.pool,
              jnp.asarray(cache.block_tables), jnp.asarray(cache.lengths),
              jnp.ones((B,), bool), jax.random.PRNGKey(0),
              jnp.zeros((B,), jnp.int32))
    assert _module_name(eng._decode(), *decode) == "jit_paged_decode"
    chunk = (eng.params, jnp.zeros((1, 16), jnp.int32), cache.pool,
             jnp.asarray(cache.block_tables[0]), jnp.int32(8), jnp.int32(16))
    assert _module_name(eng._chunk_fn(8, 16), *chunk) \
        == "jit_prefill_chunk_c8_w16"


def test_the_train_step_lowers_as_jit_train_step(tiny):
    cfg = tiny[0]
    state = jax.eval_shape(lambda k: train.init_train_state(k, cfg),
                           jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    assert _module_name(train.make_train_step(cfg), state, tokens) \
        == "jit_train_step"
