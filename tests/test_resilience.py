"""Fault-tolerant serving tests (ISSUE 8 acceptance gates).

The hard gates:

- **Recovery**: kill the engine via an injected fault at EACH hot-path
  site — including during a speculative-verify step and under tp
  sharding on the 8-device host mesh — then restore from the
  supervisor's write-ahead journal; the final token streams must be
  BIT-IDENTICAL to uninterrupted decode at fp and int8-KV.
- **Chaos soak**: a seeded mixed workload with >= 50 injected faults
  across all sites drains with zero lost/duplicated requests, a
  balanced allocator, and every fault visible in the
  ``serving_fault_*`` metrics (tools/chaos_soak.py; the tier-1 variant
  here runs the same invariants on a smaller request mix).
- **Drain/restore**: drain checkpoints in-flight sessions + the prefix
  trie; a fresh engine restores them, finishes the sessions
  token-identically, and serves the same system prompt with a prefix
  HIT (not a miss) — fp and int8-KV — while ``serving_drain_*``
  metrics record checkpoint/restore sizes and latency.
"""
import importlib.util
import os

import numpy as np
import jax
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.distributed.mesh import serving_mesh
from paddle_tpu.serving import (BlockAllocator, CorruptionDetected,
                                EngineDead, EngineSupervisor,
                                FaultInjector, InjectedFault,
                                PrefixCache, Priority)
from paddle_tpu.serving.resilience import (DEGRADED_MODES,
                                           ENGINE_SITES, SITES)

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(0), _CFG)
_REF = {}                       # kv -> uninterrupted reference outputs


def _load_chaos_soak():
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "chaos_soak.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the tool under test doubles as the shared deterministic-speculator
#: source (_speculator: always-draft repeat-last — verify runs every
#: step, greedy output stays bit-identical); one implementation keeps
#: the soak and these unit tests from silently diverging
_SOAK = _load_chaos_soak()
_repeat_last = _SOAK._speculator


def _prompts():
    rs = np.random.RandomState(3)
    plain = rs.randint(3, _CFG.vocab_size, (6,)).astype(np.int32)
    long = rs.randint(3, _CFG.vocab_size, (20,)).astype(np.int32)
    motif = rs.randint(3, _CFG.vocab_size, (4,)).astype(np.int32)
    rep = np.tile(motif, 4).astype(np.int32)[:14]
    return [plain, long, rep]


_KW = dict(max_batch=2, page_size=8, max_len=32, prefill_chunk=8)

#: first engine built per config — later engines (and tests) adopt its
#: compiled step programs, exactly as the supervisor does across
#: rebuilds (pure functions of their array arguments), so the 7-site x
#: 2-kv parity sweep compiles each program once, not once per test
_PROTO = {}


def _factory(kv=None, spec=False, mesh=None):
    key = (kv, spec, None if mesh is None else tuple(mesh.shape.items()))

    def make():
        kw = dict(_KW, kv_cache_dtype=kv, mesh=mesh)
        if spec:
            kw.update(spec_k=2, speculator=_repeat_last(2))
        eng = ContinuousBatchingEngine(_PARAMS, _CFG, **kw)
        proto = _PROTO.get(key)
        if proto is None:
            _PROTO[key] = eng
        else:
            # shared dicts: programs either engine compiles land in
            # the common cache
            eng._chunk_fns = proto._chunk_fns
            eng._spec_fns = proto._spec_fns
            eng.cache._cow_fn = proto.cache._cow_fn
            if proto._decode_fn is not None:
                eng._decode_fn = proto._decode_fn
        return eng
    return make


def _refs(kv):
    """Uninterrupted single-chip plain-engine outputs (spec decode and
    tp sharding are token-identical by the PR 5/7 gates, so one
    reference serves every flavor)."""
    if kv not in _REF:
        eng = _factory(kv)()        # seeds the shared-compile proto
        _REF[kv] = [np.asarray(o) for o in
                    eng.generate(_prompts(), max_new_tokens=6)]
    return _REF[kv]


def _supervised_run(factory, inj, **kw):
    kw.setdefault("backoff_s", 0.0)
    kw.setdefault("sleep", lambda s: None)
    sup = EngineSupervisor(factory, **kw)
    with inj:
        reqs = [sup.submit(p, max_new_tokens=6) for p in _prompts()]
        sup.run()
    return sup, reqs


class TestFaultInjector:
    def test_deterministic_given_seed(self):
        def drive(inj):
            log = []
            for site in ("alloc", "decode_step", "transfer") * 40:
                try:
                    inj.fire(site)
                except InjectedFault as e:
                    log.append((e.site, e.mode))
            return log

        a = drive(FaultInjector(seed=7, rate=0.2,
                                modes=("raise", "corrupt")))
        b = drive(FaultInjector(seed=7, rate=0.2,
                                modes=("raise", "corrupt")))
        assert a and a == b
        c = drive(FaultInjector(seed=8, rate=0.2,
                                modes=("raise", "corrupt")))
        assert a != c

    def test_armed_fires_on_nth_call(self):
        inj = FaultInjector()
        inj.arm("free", "raise", nth=3)
        inj.fire("free")
        inj.fire("free")
        with pytest.raises(InjectedFault, match="site 'free'"):
            inj.fire("free")
        inj.fire("free")                     # armed shot is spent
        assert inj.fired["free"] == 1 and inj.calls["free"] == 4

    def test_validates_sites_and_modes(self):
        with pytest.raises(ValueError, match="unknown site"):
            FaultInjector(sites=["nope"])
        with pytest.raises(ValueError, match="unknown mode"):
            FaultInjector(modes=("explode",))
        with pytest.raises(ValueError, match="unknown site"):
            FaultInjector().arm("nope")

    def test_max_faults_bounds_rate_mode(self):
        inj = FaultInjector(seed=0, rate=1.0, max_faults=2)
        fired = 0
        for _ in range(10):
            try:
                inj.fire("alloc")
            except InjectedFault:
                fired += 1
        assert fired == 2 == inj.fired_total

    def test_uninstalled_fault_point_is_free(self):
        from paddle_tpu.serving.resilience import fault_point
        fault_point("alloc")                 # no injector: no-op


#: a fault site's n-th firing that lands mid-run for the standard
#: 3-request workload (admissions, retirements and steps interleave)
_SITE_NTH = {"alloc": 2, "free": 1, "decode_step": 2,
             "prefill_chunk": 2, "verify_step": 2, "transfer": 3,
             "sched_tick": 4,
             # ISSUE 12 dispatch/commit seams: visited on every decode
             # (the sync path composes dispatch+commit), so mid-run
             # firings mirror decode_step/transfer
             "dispatch": 2, "commit": 3}


class TestRecoveryParity:
    """ACCEPTANCE: recovery from a fault at EVERY site is bit-identical
    to uninterrupted decode, fp and int8-KV."""

    @pytest.mark.parametrize("kv", [None, "int8"])
    @pytest.mark.parametrize("site", SITES)
    def test_each_site(self, site, kv):
        if site in ("swap_out", "swap_in"):
            pytest.skip(
                "host-tier sites only run on the preemption path — "
                "their recovery-parity gates live in "
                "tests/test_host_tier.py::TestResilience (and the "
                "chaos soak fires them)")
        if site in ("dispatch", "commit"):
            pytest.skip(
                "the ISSUE 12 dispatch/commit seams are gated in "
                "tests/test_overlap.py::TestOverlapRecovery on the "
                "OVERLAPPED pipeline (a step genuinely in flight when "
                "the fault strikes — the case these sites exist for); "
                "the chaos soak fires them in both modes")
        if site in ("handoff_export", "handoff_import",
                    "autoscale_tick"):
            pytest.skip(
                "cluster-plane sites (ISSUE 13) only execute inside a "
                "ServingCluster — gated in tests/test_traffic.py and "
                "fired by the traffic soak "
                "(tools/chaos_soak.py --traffic)")
        if site in ("adapter_load", "adapter_promote"):
            pytest.skip(
                "adapter sites (ISSUE 14) only run on admissions that "
                "reference a LoRA variant — recovery-parity gates live "
                "in tests/test_adapters.py::TestAdapterLifecycle (and "
                "the chaos soak fires them with adapter traffic)")
        if site in ("rpc_send", "rpc_recv", "fabric_put", "fabric_get"):
            pytest.skip(
                "multi-process sites (ISSUE 19) only execute on the "
                "RPC transport / fabric client — gated in "
                "tests/test_multiproc.py and fired by the multiproc "
                "soak (tools/chaos_soak.py --multiproc)")
        if site in ("wal_append", "wal_fsync", "checkpoint_write"):
            pytest.skip(
                "durable-journal sites (ISSUE 15) only execute on a "
                "WAL-backed supervisor — their recovery gates are the "
                "crash-point sweep in tests/test_wal.py (process death "
                "after each site + recover_from_disk), and the chaos "
                "soak fires them with the WAL attached")
        if site in ("draft_propose", "tree_verify"):
            pytest.skip(
                "draft-model / tree sites (ISSUE 20) only execute on a "
                "draft-model tree-speculation engine — gated in "
                "tests/test_tree_spec.py::TestTreeRecovery and, fp and "
                "int8, by tests/test_wal.py's crash-point sweep "
                "(test_every_engine_site[*-draft_propose|tree_verify])")
        refs = _refs(kv)
        # the verify site only exists on the speculative path; every
        # other site uses the plain engine (where decode_step always
        # runs)
        factory = _factory(kv, spec=(site == "verify_step"))
        inj = FaultInjector(seed=0)
        inj.arm(site, "raise", nth=_SITE_NTH[site])
        sup, reqs = _supervised_run(factory, inj)
        assert inj.fired[site] == 1, f"site {site} never fired"
        assert sup.recoveries >= 1
        assert sup.health != "dead"
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)
            assert r.finish_reason in ("eos", "max_len")

    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_under_tp_during_spec_verify(self, kv):
        """The 8-device host mesh (tp=2: head-sharded KV pools): a
        fault during a spec-verify step kills the sharded engine; the
        journal restores it bit-identically."""
        refs = _refs(kv)
        mesh = serving_mesh(2)
        inj = FaultInjector(seed=0)
        inj.arm("verify_step", "raise", nth=2)
        sup, reqs = _supervised_run(
            _factory(kv, spec=True, mesh=mesh), inj)
        assert inj.fired["verify_step"] == 1 and sup.recoveries >= 1
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)

    def test_under_tp4_replicated_kv(self):
        """tp=4 takes the GQA KV-replication path (nkv=2 < tp); a
        mid-decode fault recovers bit-identically there too."""
        refs = _refs(None)
        mesh = serving_mesh(4)
        inj = FaultInjector(seed=0)
        inj.arm("decode_step", "raise", nth=3)
        sup, reqs = _supervised_run(_factory(None, mesh=mesh), inj)
        assert sup.recoveries >= 1
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)

    def test_corrupt_and_detect_on_transfer(self):
        """The corrupt mode models a checksum catching a bad
        device->host payload: detection precedes commit, so recovery
        is exactly the raise path — bit-identical."""
        refs = _refs(None)
        inj = FaultInjector(seed=0)
        inj.arm("transfer", "corrupt", nth=3)
        sup, reqs = _supervised_run(_factory(None), inj)
        assert sup.recoveries == 1 and sup.injected_faults == 1
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)

    def test_watchdog_stall_recovery(self):
        """A step stalled past the watchdog deadline is abandoned with
        the poisoned engine and the journal restores the sessions —
        bit-identical (the injected stall raises on wake, so the
        abandoned thread never commits)."""
        refs = _refs(None)
        inj = FaultInjector(seed=0, stall_s=3.0)
        inj.arm("transfer", "stall", nth=4)
        sup, reqs = _supervised_run(_factory(None), inj,
                                    watchdog_s=2.5)
        assert sup.recoveries == 1
        # the watchdog only sees a StepStalled, but the supervisor
        # asks the installed injector whether the stall was its own —
        # chaos runs must never inflate the REAL-failure counter
        assert sup.injected_faults == 1 and sup.real_faults == 0
        assert inj.fired["transfer"] == 1 and not inj.pending_stalls
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)

    def test_self_raised_stall_retires_its_pending_entry(self):
        """A stall that wakes BEFORE the watchdog raises itself: its
        pending-stall entry must retire with it, or a later REAL
        watchdog stall would be misattributed as injected."""
        refs = _refs(None)
        inj = FaultInjector(seed=0, stall_s=0.01)   # wakes instantly
        inj.arm("decode_step", "stall", nth=2)
        sup, reqs = _supervised_run(_factory(None), inj,
                                    watchdog_s=30.0)
        assert sup.injected_faults == 1 and sup.real_faults == 0
        assert inj.pending_stalls == []             # retired, not stale
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)

    def test_multiple_faults_one_run(self):
        """Several faults across different sites in one run: each
        recovery replays from the journal; the streams still match."""
        refs = _refs(None)
        inj = FaultInjector(seed=0)
        inj.arm("alloc", "raise", nth=2)
        inj.arm("decode_step", "raise", nth=4)
        inj.arm("sched_tick", "corrupt", nth=9)
        sup, reqs = _supervised_run(_factory(None), inj)
        assert sup.recoveries == 3
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)


class TestJournal:
    def test_write_ahead_then_sync_then_prune(self):
        sup = EngineSupervisor(_factory(None))
        p = _prompts()[0]
        req = sup.submit(p, max_new_tokens=4)
        # write-ahead: journaled at submit, before any step ran
        assert sup.journal.size == 1
        e = sup.journal.live_entries()[0]
        np.testing.assert_array_equal(e.prompt, p)
        assert e.tokens == [] and not e.admitted
        while not req.done:
            sup.step()
        # finished entries leave the journal (results live on the
        # caller's handle)
        assert sup.journal.size == 0
        assert sup.journal.finished_total == 1

    def test_rid_monotonic_across_rebuilds(self):
        inj = FaultInjector(seed=0)
        inj.arm("decode_step", "raise", nth=2)
        sup, reqs = _supervised_run(_factory(None), inj)
        assert sup.recoveries >= 1
        late = sup.submit(_prompts()[0], max_new_tokens=2)
        assert late.rid > max(r.rid for r in reqs)
        sup.run()
        assert late.done


class TestDegradedLadder:
    def test_escalate_shed_then_recover(self):
        """The pressure ladder: recovery 1 disables spec decode,
        recovery 2 shrinks the prefill chunk to one page, recovery 3
        sheds LOW admissions with the structured ``rejected_overload``
        reason; sustained healthy steps climb back down and restore
        the shelved configuration."""
        from paddle_tpu import observability as obs
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            def factory():
                return ContinuousBatchingEngine(
                    _PARAMS, _CFG, max_batch=2, page_size=8,
                    max_len=32, prefill_chunk=16, spec_k=2,
                    speculator=_repeat_last(2))
            sup = EngineSupervisor(factory, backoff_s=0.0,
                                   sleep=lambda s: None,
                                   recover_after=3,
                                   circuit_threshold=20)
            orig_chunk = sup.engine.prefill_chunk
            assert orig_chunk == 16 and sup.engine.spec is not None
            req = sup.submit(_prompts()[1], max_new_tokens=6)
            # drive three failures straight into the failure handler
            # (the per-site recovery tests cover the step()-side path)
            sup._on_failure(InjectedFault("sched_tick"))
            assert sup.degraded_level == 1
            assert sup.engine.spec is None              # rung 1
            sup._on_failure(InjectedFault("sched_tick"))
            assert sup.degraded_level == 2
            assert (sup.engine.prefill_chunk
                    == sup.engine.cache.page_size)      # rung 2
            sup._on_failure(InjectedFault("sched_tick"))
            assert sup.degraded_level == 3
            assert sup.degraded_mode == "shed_low" \
                == DEGRADED_MODES[3]
            shed = sup.submit(_prompts()[0], max_new_tokens=4,
                              priority=Priority.LOW)
            assert shed.done and shed.tokens == []
            assert shed.finish_reason == "rejected_overload"
            ok = sup.submit(_prompts()[0], max_new_tokens=4,
                            priority=Priority.NORMAL)
            assert not ok.done
            sup.run()                        # healthy steps: descend
            assert ok.done and req.done
            assert sup.degraded_level < 3
            # keep stepping an idle engine? no — drive fresh traffic
            # until fully healthy again
            while sup.degraded_level > 0:
                r = sup.submit(_prompts()[0], max_new_tokens=2)
                sup.run()
            assert sup.engine.spec is not None           # un-shelved
            assert sup.engine.prefill_chunk == orig_chunk
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert snap["serving_cancellations_total"]["values"][
            "reason=rejected_overload"] == 1
        assert snap["serving_degraded_mode"]["values"][""] == 0
        assert sup.shed_total == 1 and sup.stats()["shed_total"] == 1

    def test_circuit_breaker_opens_and_reports(self):
        inj = FaultInjector(seed=0, rate=1.0, sites=["sched_tick"])
        sup = EngineSupervisor(_factory(None), backoff_s=0.0,
                               sleep=lambda s: None,
                               circuit_threshold=3)
        with inj:
            req = sup.submit(_prompts()[0], max_new_tokens=4)
            with pytest.raises(EngineDead, match="circuit breaker"):
                sup.run()
        assert sup.health == "dead"
        assert req.done and req.finish_reason == "engine_dead"
        with pytest.raises(EngineDead):
            sup.step()
        with pytest.raises(EngineDead):
            sup.submit(_prompts()[0], max_new_tokens=2)

    def test_fault_metrics_split_injected_vs_real(self):
        from paddle_tpu import observability as obs
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            inj = FaultInjector(seed=0)
            inj.arm("decode_step", "raise", nth=2)
            sup, _ = _supervised_run(_factory(None), inj)
            # one REAL failure on top (a non-injected exception)
            sup._on_failure(RuntimeError("connection reset"))
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        inj_vals = snap["serving_fault_injected_total"]["values"]
        assert inj_vals["site=decode_step,kind=raise"] == 1
        real = snap["serving_fault_failures_total"]["values"]
        assert real["site=step,kind=RuntimeError"] == 1
        assert snap["serving_fault_recoveries_total"]["values"][""] == 2
        assert snap["serving_fault_recovery_ms"]["values"][""]["count"] \
            == 2
        assert "serving_fault_journal_entries" in snap


class TestChaosSoak:
    def test_short_seeded_soak(self):
        """Tier-1 variant of tools/chaos_soak.py: >= 50 injected faults
        across every site, zero lost/duplicated requests, balanced
        allocator, all faults visible in serving_fault_* (run_soak
        raises SoakError on any violation)."""
        report = _SOAK.run_soak(seed=0, faults=50, requests=12,
                               stall_faults=1)
        assert report["faults_fired"] >= 50
        # the single-engine soak covers the per-engine sites; the
        # cluster-plane sites (ISSUE 13) are the traffic soak's job
        assert set(report["faults_by_site"]) == set(ENGINE_SITES)
        assert report["recoveries"] >= 1
        assert report["allocator"]["num_used"] == 0
        assert (report["allocator"]["allocs_total"]
                == report["allocator"]["frees_total"])


class TestDrainRestore:
    # int8 is the slowest single parity sweep in the file (ISSUE 13
    # watchdog-headroom satellite): the fp case stays the tier-1
    # representative, the int8 variant runs outside `-m 'not slow'`
    @pytest.mark.parametrize("kv", [
        None, pytest.param("int8", marks=pytest.mark.slow)])
    def test_roundtrip_prefix_hits_and_parity(self, kv, tmp_path):
        """ACCEPTANCE: drain with a warm prefix trie + an in-flight
        session; restore into a fresh engine; the session finishes
        BIT-IDENTICALLY and the same system prompt admits with a trie
        HIT (not a miss). serving_drain_* metrics record both sides."""
        from paddle_tpu import observability as obs
        rs = np.random.RandomState(11)
        sys_p = rs.randint(3, _CFG.vocab_size, (16,)).astype(np.int32)
        t1 = rs.randint(3, _CFG.vocab_size, (4,)).astype(np.int32)
        t2 = rs.randint(3, _CFG.vocab_size, (5,)).astype(np.int32)
        p1 = np.concatenate([sys_p, t1])
        p2 = np.concatenate([sys_p, t2])
        kw = dict(_KW, max_len=48)

        def factory():
            return ContinuousBatchingEngine(_PARAMS, _CFG,
                                            kv_cache_dtype=kv, **kw)
        refs = ContinuousBatchingEngine(
            _PARAMS, _CFG, kv_cache_dtype=kv, **kw).generate(
                [p1, p2], max_new_tokens=6)
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            sup = EngineSupervisor(factory)
            a = sup.submit(p1, max_new_tokens=6)
            while not a.done:
                sup.step()                  # warm trie: p1 registered
            b = sup.submit(p2, max_new_tokens=6)
            for _ in range(4):
                sup.step()                  # b mid-flight
            assert not b.done and len(b.tokens) > 0
            path = str(tmp_path / "drain.npz")
            info = sup.drain(path)
            assert info["sessions"] == 1 and info["trie_pages"] > 0
            assert info["bytes"] == os.path.getsize(path) > 0
            with pytest.raises(RuntimeError, match="drained"):
                sup.step()
            with pytest.raises(RuntimeError, match="drained"):
                sup.submit(p1, max_new_tokens=2)

            sup2 = EngineSupervisor.restore(factory, path)
            b2 = sup2.restored[b.rid]
            assert b2.tokens == b.tokens    # journal state carried
            sup2.run()
            np.testing.assert_array_equal(b2.output,
                                          np.asarray(refs[1]))
            # the restored trie must HIT for the same system prompt
            before = obs.REGISTRY.to_json()[
                "serving_prefix_hit_tokens_total"]["values"][""]
            c = sup2.submit(p1, max_new_tokens=6)
            sup2.run()
            np.testing.assert_array_equal(c.output,
                                          np.asarray(refs[0]))
            snap = obs.REGISTRY.to_json()
            hits = snap["serving_prefix_hit_tokens_total"]["values"][""]
            assert hits > before >= 0
            assert hits >= len(sys_p) - 1   # the shared span hit
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert snap["serving_drain_checkpoint_bytes"]["values"][""] > 0
        assert snap["serving_drain_restore_bytes"]["values"][""] > 0
        assert snap["serving_drain_checkpoint_ms"]["values"][""][
            "count"] == 1
        assert snap["serving_drain_restore_ms"]["values"][""][
            "count"] == 1
        assert snap["serving_drain_sessions_total"]["values"][""] == 1
        assert snap["serving_drain_restored_sessions_total"][
            "values"][""] == 1

    def test_failed_drain_does_not_brick_the_supervisor(self, tmp_path):
        """A drain whose checkpoint write fails (bad path, disk full)
        must leave the supervisor SERVING: freezing admissions with
        nothing saved would strand every in-flight session."""
        sup = EngineSupervisor(_factory(None))
        req = sup.submit(_prompts()[0], max_new_tokens=4)
        with pytest.raises(OSError):
            sup.drain(str(tmp_path / "no" / "such" / "dir" / "c.npz"))
        sup.run()                           # still alive and serving
        assert req.done and req.finish_reason in ("eos", "max_len")
        ok = sup.drain(str(tmp_path / "ok.npz"))   # and still drainable
        assert ok["bytes"] > 0

    def test_restore_reanchors_deadlines_on_the_new_clock(self,
                                                          tmp_path):
        """Deadlines checkpoint as REMAINING seconds and re-anchor on
        the restoring process's clock — monotonic stamps from the
        drained host would freeze or instantly expire the SLO across
        a reboot/host change."""
        t1 = [1000.0]                       # drained host: high uptime
        sup = EngineSupervisor(_factory(None), clock=lambda: t1[0],
                               scheduler_kw={})
        sup.submit(_prompts()[0], max_new_tokens=4, deadline_s=30.0)
        path = str(tmp_path / "d.npz")
        sup.drain(path)

        t2 = [5.0]                          # restored host: fresh boot
        sup2 = EngineSupervisor.restore(_factory(None), path,
                                        clock=lambda: t2[0])
        (req,) = sup2.restored.values()
        assert req.deadline_at == pytest.approx(35.0)   # 5 + 30 left
        t2[0] = 20.0                        # well within the SLO
        sup2.run()
        assert req.done and req.finish_reason in ("eos", "max_len")

    def test_restore_validates_geometry(self, tmp_path):
        sup = EngineSupervisor(_factory(None))
        sup.submit(_prompts()[0], max_new_tokens=4)
        path = str(tmp_path / "ckpt.npz")
        sup.drain(path)

        def other():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=2, page_size=16, max_len=32)
        with pytest.raises(ValueError, match="page_size"):
            EngineSupervisor.restore(other, path)

        def other_kv():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, kv_cache_dtype="int8", **_KW)
        with pytest.raises(ValueError, match="kv_dtype"):
            EngineSupervisor.restore(other_kv, path)


class TestTrieSerialization:
    def test_records_roundtrip_with_remap(self):
        """PrefixCache.to_records/restore_records: structure (chains +
        tails) survives a page-id remap; the restored trie matches the
        same prompts and the allocator ends with one trie reference
        per restored page."""
        page = 4
        rs = np.random.RandomState(5)
        p_a = rs.randint(0, 100, (11,)).astype(np.int32)   # 2 full + tail
        p_b = np.concatenate([p_a[:8],
                              rs.randint(0, 100, (4,)).astype(np.int32)])
        src_alloc = BlockAllocator(16)
        trie = PrefixCache(page)
        pages_a = src_alloc.alloc(3)
        trie.register(p_a, pages_a, src_alloc)
        pages_b = src_alloc.alloc(3)
        trie.register(p_b, pages_b, src_alloc)
        rec = trie.to_records()

        dst_alloc = BlockAllocator(32)
        boot = dst_alloc.alloc(len(set(trie.pages())))
        page_map = dict(zip(sorted(set(trie.pages())), boot))
        trie2 = PrefixCache(page)
        trie2.restore_records(rec, page_map, dst_alloc)
        dst_alloc.free(boot)               # trie owns the pages now

        m_a, tail_a = trie2.match(p_a)
        assert m_a == [page_map[p] for p in pages_a[:2]]
        assert tail_a is not None and tail_a[0] == page_map[pages_a[2]]
        m_b, _ = trie2.match(p_b)
        assert m_b[:1] == [page_map[pages_a[0]]]   # shared first page
        # one live reference per restored page, none dangling
        for old, new in page_map.items():
            assert dst_alloc.refcount(new) >= 1
        trie2.drop_all(dst_alloc)
        assert dst_alloc.num_used == 0
        assert dst_alloc.allocs_total == dst_alloc.frees_total

    def test_restore_requires_empty_trie(self):
        trie = PrefixCache(4)
        alloc = BlockAllocator(8)
        pages = alloc.alloc(1)
        trie.register(np.arange(4, dtype=np.int32), pages, alloc)
        with pytest.raises(ValueError, match="not empty"):
            trie.restore_records({"nodes": [], "tails": []}, {}, alloc)
