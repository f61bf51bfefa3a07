"""Request-scoped distributed tracing + crash flight recorder
(ISSUE 16 acceptance gates).

The hard gates:

- **Zero cost when disabled**: with tracing off, no request ever grows
  a trace, the hook family reduces to one module-attr read, and a
  hot-loop of disabled hook calls stays cheap.
- **One stitched trace**: a request that prefills on one replica and
  decodes on another (prefill→decode handoff) carries ONE trace whose
  spans name both replicas, with the handoff export/import pair on the
  seam; preempt→swap-out→swap-in rides the same trace.
- **Determinism**: with an injected fake clock, two identical runs
  export byte-identical Chrome traces.
- **Flight recorder**: EngineDead and any exception escaping ``step()``
  leave a CRC-framed ``flight-<ts>.json`` next to the WAL; a tampered
  dump fails loudly; ``recover_from_disk`` surfaces the dead
  incarnation's dump; ring + trace memory stay bounded.
- **Tooling round-trip**: ``tools/trace_dump.py`` renders both artifact
  kinds from the bytes on disk.
"""
import json
import os

import numpy as np
import jax
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.observability import flight, hooks as _obs, tracing
from paddle_tpu.observability.timeline import chrome_trace
from paddle_tpu.serving import (EngineDead, EngineSupervisor,
                                FakeClock, FaultInjector, Priority,
                                ServingCluster, ServingScheduler,
                                run_trace, synth_trace)

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(0), _CFG)
_KW = dict(max_batch=2, page_size=8, max_len=32, prefill_chunk=8)
_SKW = dict(sleep=lambda s: None, backoff_s=0.0)
_PROTO = {}                     # shared-compile proto per config key


def _factory(**over):
    kw = dict(_KW, **over)
    key = tuple(sorted((k, str(v)) for k, v in kw.items()))

    def make():
        eng = ContinuousBatchingEngine(_PARAMS, _CFG, **kw)
        proto = _PROTO.get(key)
        if proto is None:
            _PROTO[key] = eng
        else:
            eng._chunk_fns = proto._chunk_fns
            eng._spec_fns = proto._spec_fns
            eng.cache._cow_fn = proto.cache._cow_fn
            if proto._decode_fn is not None:
                eng._decode_fn = proto._decode_fn
        return eng
    return make


def _fake_ns():
    """A deterministic monotonic-ns clock: 1ms per call."""
    t = [0]

    def clk():
        t[0] += 1_000_000
        return t[0]
    return clk


def _prompt(n, seed=3):
    rs = np.random.RandomState(seed)
    return rs.randint(3, _CFG.vocab_size, (n,)).astype(np.int32)


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled (the module
    default) — a leaked enable would silently change other suites."""
    tracing.disable()
    yield
    tracing.disable()


class TestDisabledZeroCost:
    def test_disabled_run_leaves_no_trace(self):
        """ACCEPTANCE: with tracing off, a full serve leaves NO trace
        object on any handle and the registry untouched."""
        assert not tracing.tracing_enabled()
        assert _obs.serving_trace_now() == 0
        # the registry is the process's: a test that traced earlier in
        # this worker has left its count there
        before = tracing.TRACER.stats()["spans_total"]
        sched = ServingScheduler(_factory()(), token_budget=32)
        reqs = [sched.submit(_prompt(6, seed=i), max_new_tokens=3)
                for i in range(3)]
        for _ in range(200):
            if not sched.step():
                break
        for r in reqs:
            assert r.done
            assert getattr(r, "trace", None) is None
        assert tracing.TRACER.stats()["spans_total"] == before

    def test_disabled_hooks_are_cheap(self):
        """The off switch is one module-attr read: a hot loop of
        disabled hook calls must not cost microseconds each."""
        import time
        req = object()
        t0 = time.perf_counter()
        for _ in range(100_000):
            _obs.serving_trace_span(req, "decode_step", 0)
            _obs.serving_trace_now()
        dt = time.perf_counter() - t0
        assert dt < 0.5, f"disabled trace hooks too slow: {dt:.3f}s"


class TestLifecycle:
    def test_single_engine_spans_and_breakdown(self):
        """Submit→queue→admit→prefill chunks→decode→finish all land in
        ONE trace, with a TTFT breakdown whose phases are non-negative
        and sum to at most the total."""
        tracing.enable(clock_ns=_fake_ns())
        sched = ServingScheduler(_factory()(), token_budget=32)
        r = sched.submit(_prompt(12), max_new_tokens=4)
        for _ in range(200):
            if not sched.step():
                break
        tr = r.trace
        assert tr is not None and tr.done and tr.reason in ("eos",
                                                            "max_len")
        names = [s.name for s in tr.spans]
        assert "queue_wait" in names
        assert names.count("prefill_chunk") >= 2      # 12 tok, 8-chunk
        assert "decode_step" in names
        assert names[-1] == "finish"
        bd = tr.ttft_breakdown()
        assert bd is not None
        assert all(v >= 0 for v in bd.values())
        parts = (bd["queue_ms"] + bd["prefill_ms"] + bd["handoff_ms"]
                 + bd["swap_ms"] + bd["sched_overhead_ms"])
        assert parts == pytest.approx(bd["ttft_ms"], abs=1e-6)

    def test_preempt_swap_resume_in_one_trace(self):
        """A preempted victim's swap-out, swap-in (or replay resume)
        and final finish all stitch into the SAME trace."""
        tracing.enable(clock_ns=_fake_ns())
        sched = ServingScheduler(_factory(host_tier=True)(),
                                 token_budget=32)
        lows = [sched.submit(_prompt(8, seed=i), max_new_tokens=6,
                             priority=Priority.LOW) for i in range(2)]
        for _ in range(4):
            sched.step()
        highs = [sched.submit(_prompt(4, seed=9 + i), max_new_tokens=2,
                              priority=Priority.HIGH) for i in range(2)]
        for _ in range(400):
            if not sched.step():
                break
        assert sched.preemptions_total >= 1
        victims = [r for r in lows
                   if any(s.name == "preempt" for s in r.trace.spans)]
        assert victims, "no LOW victim carries a preempt mark"
        v = victims[0]
        names = [s.name for s in v.trace.spans]
        assert "swap_out" in names
        # the resume is either a swap-in restore or the replay path
        assert ("swap_in" in names or "resume_replay" in names), names
        assert v.done and names[-1] == "finish"
        for h in highs:
            assert h.done and h.trace.done


class TestStitching:
    def test_handoff_stitches_one_trace_across_replicas(self):
        """ACCEPTANCE: prefill on replica 0, decode on replica 1 —
        ONE trace, both replicas listed, the export/import pair on the
        seam with the import naming its source."""
        tracing.enable(clock_ns=_fake_ns())
        cluster = ServingCluster(_factory(), replicas=2,
                                 prefill_replicas=1,
                                 supervisor_kw=dict(_SKW))
        r = cluster.submit(_prompt(12), max_new_tokens=5)
        cluster.run()
        assert r.done and cluster.handoffs_total >= 1
        tr = r.trace
        assert tr is not None and len(tr.replicas) == 2
        by_name = {s.name: s for s in tr.spans}
        assert "handoff_export" in by_name
        assert "handoff_import" in by_name
        exp, imp = by_name["handoff_export"], by_name["handoff_import"]
        assert exp.replica != imp.replica
        assert imp.meta["src"] == exp.replica
        # decode continued on the import side
        decodes = [s for s in tr.spans if s.name == "decode_step"]
        assert decodes and all(s.replica == imp.replica
                               for s in decodes)


class TestDeterminism:
    def test_fake_clock_chrome_export_byte_identical(self):
        """ACCEPTANCE: two identical runs under injected clocks export
        byte-identical Chrome traces."""
        def one_run():
            tracing.enable(clock_ns=_fake_ns())
            sched = ServingScheduler(_factory()(), token_budget=32)
            reqs = [sched.submit(_prompt(6 + i, seed=i),
                                 max_new_tokens=3) for i in range(3)]
            for _ in range(200):
                if not sched.step():
                    break
            assert all(r.done for r in reqs)
            doc = tracing.TRACER.chrome()
            tracing.disable()
            return json.dumps(doc, sort_keys=True,
                              separators=(",", ":"))
        assert one_run() == one_run()


class TestChromeGolden:
    _ROWS = [
        {"name": "decode_step", "cat": "decode", "start_ns": 3_000_000,
         "dur_ns": 1_000_000, "pid": 2, "tid": 1, "args": {"rid": 7}},
        {"name": "prefill_chunk", "cat": "prefill",
         "start_ns": 1_000_000, "dur_ns": 2_000_000, "pid": 1,
         "tid": 2, "args": {"rid": 7}},
        {"name": "queue_wait", "cat": "queue", "start_ns": 0,
         "dur_ns": 1_000_000, "pid": 1, "tid": 1, "args": {"rid": 7}},
    ]

    def test_sort_stable_and_lane_rows(self):
        """Permuted input rows encode to IDENTICAL bytes, with one
        process row per replica and thread rows per slot lane."""
        names = {1: "router", 2: "replica 1"}
        a = chrome_trace(list(self._ROWS), pid_names=names)
        b = chrome_trace(list(reversed(self._ROWS)), pid_names=names)
        ja = json.dumps(a, sort_keys=True, separators=(",", ":"))
        jb = json.dumps(b, sort_keys=True, separators=(",", ":"))
        assert ja == jb
        evs = a["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert {(e["name"], e["pid"]) for e in meta} >= {
            ("process_name", 1), ("process_name", 2)}
        xs = [e for e in evs if e["ph"] == "X"]
        # metadata first, then (pid, tid, ts) order; ns -> us
        assert evs[:len(meta)] == meta
        assert [(e["pid"], e["tid"], e["ts"]) for e in xs] == sorted(
            (e["pid"], e["tid"], e["ts"]) for e in xs)
        assert xs[0]["ts"] == 0 and xs[0]["dur"] == 1000

    def test_tracer_chrome_lanes(self):
        """The tracer's export gives every replica its own pid row
        ('router' for the unplaced lane) and every slot a tid."""
        tracing.enable(clock_ns=_fake_ns())
        tr = tracing.TRACER
        class R:                        # minimal handle
            rid = 5
        r = R()
        tr.attach(r)
        tr.record(r, "decode_step", tr.now(), replica=1, slot=0)
        doc = tr.chrome()
        names = {(e["pid"], (e.get("args") or {}).get("name"))
                 for e in doc["traceEvents"] if e["ph"] == "M"
                 and e["name"] == "process_name"}
        assert (0, "router") in names       # submit mark, replica -1
        assert (2, "replica 1") in names


class TestFlightRecorder:
    def test_ring_and_dump_roundtrip(self, tmp_path):
        rec = flight.FlightRecorder(max_ticks=4, meta={"replica": 0})
        for i in range(10):
            rec.record_tick(step=i, committed=i % 3)
        assert rec.ticks_total == 10
        assert [t["step"] for t in rec.last_ticks()] == [6, 7, 8, 9]
        path = rec.dump(str(tmp_path), "manual", extra={"note": "x"})
        payload = flight.load(path)
        assert payload["reason"] == "manual"
        assert payload["ticks_total"] == 10
        assert [t["step"] for t in payload["ticks"]] == [6, 7, 8, 9]
        assert payload["extra"]["note"] == "x"
        assert flight.find_dumps(str(tmp_path)) == [path]

    def test_tampered_dump_fails_loudly(self, tmp_path):
        rec = flight.FlightRecorder(max_ticks=4)
        rec.record_tick(step=1)
        path = rec.dump(str(tmp_path), "manual")
        doc = json.loads(open(path, "rb").read())
        doc["payload"]["ticks"][0]["step"] = 999     # bit-flip
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(ValueError, match="CRC"):
            flight.load(path)

    def test_engine_dead_leaves_black_box(self, tmp_path):
        """ACCEPTANCE: the circuit opening dumps the flight ring +
        trace tails next to the WAL, CRC-clean, with the error and
        the fault tick recorded."""
        tracing.enable(clock_ns=_fake_ns())
        wd = str(tmp_path / "wal")
        sup = EngineSupervisor(_factory(), wal_dir=wd,
                               circuit_threshold=2, **_SKW)
        sup.replica_id = 3
        r = sup.submit(_prompt(6), max_new_tokens=3)
        inj = FaultInjector(seed=0, rate=1.0, sites=["sched_tick"])
        with inj:
            with pytest.raises(EngineDead):
                for _ in range(50):
                    sup.step()
        assert sup.last_flight_dump is not None
        payload = flight.load(sup.last_flight_dump)
        assert payload["reason"] == "EngineDead"
        assert payload["meta"]["replica"] == 3
        assert "circuit breaker open" in payload["extra"]["error"]
        assert any(t.get("fault") for t in payload["ticks"])
        # the trace tails rode along (tracing was on)
        assert any(t["rid"] == r.rid for t in payload["traces"])

    def test_step_exception_dumps_and_recovery_surfaces(self, tmp_path):
        """An exception ESCAPING step() (the chaos harness's simulated
        kill -9) leaves a dump, and recover_from_disk points at it."""
        wd = str(tmp_path / "wal")
        sup = EngineSupervisor(_factory(), wal_dir=wd,
                               circuit_threshold=50, **_SKW)

        class Died(RuntimeError):
            pass

        def die(err):
            raise Died(str(err))
        sup._on_failure = die
        sup.submit(_prompt(6), max_new_tokens=3)
        inj = FaultInjector(seed=0)
        inj.arm("decode_step", "raise", nth=1)
        with inj:
            with pytest.raises(Died):
                for _ in range(50):
                    sup.step()
        dumps = flight.find_dumps(wd)
        assert len(dumps) == 1
        assert flight.load(dumps[0])["reason"] == "Died"
        sup2 = EngineSupervisor.recover_from_disk(_factory(), wd,
                                                  **_SKW)
        assert sup2.last_flight_dump == dumps[0]
        # recovered sessions finish; the wal_replay span is recorded
        # when tracing is on (see test_wal for the identity gates)
        while sup2.step():
            pass

    def test_manual_dump_and_tick_fields(self, tmp_path):
        """dump_flight() on demand: plan summary, budget, WAL lsn and
        degraded rung all present on the recorded ticks."""
        wd = str(tmp_path / "wal")
        sup = EngineSupervisor(_factory(), wal_dir=wd, **_SKW)
        sup.submit(_prompt(6), max_new_tokens=3)
        for _ in range(4):
            sup.step()
        path = sup.dump_flight()
        payload = flight.load(path)
        assert payload["reason"] == "manual"
        t = payload["ticks"][-1]
        for k in ("step", "committed", "planned_tokens", "budget",
                  "queued", "degraded", "failures", "wal_lsn"):
            assert k in t, k
        assert t["wal_lsn"] >= 1
        assert payload["extra"]["health"] == "healthy"


class TestBoundedMemory:
    def test_tracer_lru_and_span_ring(self):
        """ACCEPTANCE: the registry never exceeds max_traces and a
        trace never exceeds max_spans — evictions/drops are counted,
        the tails survive."""
        tracing.enable(clock_ns=_fake_ns(), max_traces=2, max_spans=6)
        sched = ServingScheduler(_factory()(), token_budget=32)
        reqs = [sched.submit(_prompt(12, seed=i), max_new_tokens=6)
                for i in range(5)]
        for _ in range(400):
            if not sched.step():
                break
        st = tracing.TRACER.stats()
        assert st["traces"] <= 2
        assert st["evicted"] >= 3
        long = reqs[-1].trace
        assert len(long.spans) <= 6
        assert long.dropped > 0
        assert long.recorded == len(long.spans) + long.dropped
        # the breakdown survives span drops (kept outside the ring)
        assert long.ttft_breakdown() is not None


class TestTraceDumpTool:
    def _tool(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_dump", os.path.join(os.path.dirname(__file__),
                                       "..", "tools", "trace_dump.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_flight_dump_roundtrip(self, tmp_path):
        """ACCEPTANCE: the CLI renders a real flight dump from its
        bytes on disk — tick table + span waterfall."""
        tracing.enable(clock_ns=_fake_ns())
        wd = str(tmp_path / "wal")
        sup = EngineSupervisor(_factory(), wal_dir=wd, **_SKW)
        r = sup.submit(_prompt(10), max_new_tokens=3)
        while sup.step():
            pass
        path = sup.dump_flight()
        out = "\n".join(self._tool().render_path(path))
        assert "flight dump: reason=manual" in out
        assert "lsn" in out             # tick-table column rendered
        assert f"rid={r.rid}" in out
        assert "prefill_chunk" in out and "queue_wait" in out
        assert "ttft:" in out
        # --ticks clamps the table
        short = self._tool().render_path(path, last_ticks=2)
        assert len(short) < len(self._tool().render_path(path))

    def test_chrome_export_roundtrip(self, tmp_path):
        tracing.enable(clock_ns=_fake_ns())
        sched = ServingScheduler(_factory()(), token_budget=32)
        r = sched.submit(_prompt(6), max_new_tokens=3)
        for _ in range(200):
            if not sched.step():
                break
        path = str(tmp_path / "trace.json")
        tracing.TRACER.export_chrome(path)
        lines = self._tool().render_path(path, rid=r.rid)
        out = "\n".join(lines)
        assert f"rid={r.rid}" in out
        assert "decode_step" in out
        assert "router" in out          # bare engine: unplaced lane

    def test_rejects_foreign_json(self, tmp_path):
        p = str(tmp_path / "x.json")
        with open(p, "w") as f:
            json.dump({"hello": 1}, f)
        with pytest.raises(ValueError, match="neither"):
            self._tool().render_path(p)


class TestSLOBreakdown:
    def test_report_carries_ttft_breakdown(self):
        """ACCEPTANCE: with tracing on, run_trace aggregates each
        completed request's phase attribution into p50/p99 columns on
        the SLOReport (and its dict form)."""
        tracing.enable()
        trace = synth_trace(seed=7, duration_s=1.0, base_rps=6,
                            tenants=2, page_size=8,
                            vocab=_CFG.vocab_size, deadline_frac=0.0)
        clock = FakeClock()
        cluster = ServingCluster(_factory(), replicas=2, clock=clock,
                                 supervisor_kw=dict(_SKW))
        report = run_trace(cluster, trace, clock, step_dt=0.05)
        assert report.completed > 0
        bd = report.ttft_breakdown
        assert bd is not None
        for ph in ("queue_ms", "prefill_ms", "handoff_ms", "swap_ms",
                   "sched_overhead_ms", "ttft_ms"):
            assert set(bd[ph]) == {"p50_ms", "p99_ms"}
            assert bd[ph]["p99_ms"] >= bd[ph]["p50_ms"] >= 0
        d = report.as_dict()["ttft_breakdown"]
        assert d["ttft_ms"]["p50_ms"] == round(bd["ttft_ms"]["p50_ms"],
                                               3)

    def test_report_breakdown_none_when_disabled(self):
        trace = synth_trace(seed=7, duration_s=0.5, base_rps=4,
                            tenants=1, page_size=8,
                            vocab=_CFG.vocab_size, deadline_frac=0.0)
        clock = FakeClock()
        cluster = ServingCluster(_factory(), replicas=1, clock=clock,
                                 supervisor_kw=dict(_SKW))
        report = run_trace(cluster, trace, clock, step_dt=0.05)
        assert report.ttft_breakdown is None
        assert report.as_dict()["ttft_breakdown"] is None
