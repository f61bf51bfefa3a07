"""Disaggregated serving cluster tests (ISSUE 9 acceptance gates).

The hard gates:

- **Routed identity**: a 2-replica cluster serving a mixed multi-tenant
  request set produces token streams EXACTLY equal to one engine
  serving the same set, at fp and int8-KV (and with tp-sharded
  replicas) — routing must never change what a request decodes.
- **Handoff bit-identity**: a prefill→decode page handoff leaves the
  decode replica's pages BYTE-identical to prefilling in place (raw
  export bytes compared), and the decoded continuation matches the
  single-engine reference, at fp and int8-KV.
- **Affinity**: same-tenant requests route to the replica whose prefix
  trie holds their system prompt and actually produce prefix HITs —
  gated on the serving_prefix hit-token counter, not on routing alone.
- **Fairness / limits**: the fair-share dispatch order bounds a light
  tenant's starvation behind a heavy tenant; over-quota submissions
  reject with ``rejected_ratelimit`` before touching any replica.
- **Rolling upgrade & failover**: ``retire_replica`` mid-decode drains
  through the PR 8 path, the sessions finish token-identically on
  survivors, and the restored trie keeps serving prefix hits; the
  cluster chaos soak (tools/chaos_soak.py --cluster) kills a replica
  mid-traffic with zero lost/duplicated requests.
"""
import importlib.util
import os

import numpy as np
import jax
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.distributed.mesh import serving_mesh
from paddle_tpu import observability as obs
from paddle_tpu.serving import (FinishReason, Priority, ServingCluster,
                                ServingScheduler, TenantQuota)

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(0), _CFG)
_KW = dict(max_batch=2, page_size=8, max_len=32, prefill_chunk=8)
#: supervisor knobs for every test cluster: no real sleeping
_SKW = dict(sleep=lambda s: None, backoff_s=0.0)
_REF = {}                       # kv -> single-engine reference outputs

#: first engine built per config — later engines (replicas, rebuilt
#: replicas, reference engines) adopt its compiled step programs, the
#: same shared-compile contract the supervisor uses across rebuilds,
#: so the replica fan-out compiles each program once per config
_PROTO = {}


def _factory(kv=None, mesh=None):
    key = (kv, None if mesh is None else tuple(mesh.shape.items()))

    def make():
        eng = ContinuousBatchingEngine(_PARAMS, _CFG,
                                       kv_cache_dtype=kv, mesh=mesh,
                                       **_KW)
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


def _prompts(seed=3, lens=(6, 12, 9, 5, 14, 7)):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, _CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _refs(kv):
    if kv not in _REF:
        eng = _factory(kv)()        # seeds the shared-compile proto
        _REF[kv] = [np.asarray(eng.generate([p], max_new_tokens=5)[0])
                    for p in _prompts()]
    return _REF[kv]


def _cluster(kv=None, mesh=None, **ckw):
    ckw.setdefault("supervisor_kw", dict(_SKW))
    return ServingCluster(_factory(kv, mesh), **ckw)


def _metrics():
    """Enable the registry for one test; caller restores via the
    returned callable."""
    was = obs.metrics_enabled()
    obs.REGISTRY.clear()
    obs.enable()

    def restore():
        obs.REGISTRY.clear()
        if not was:
            obs.disable()
    return restore


def _counter_sum(snap, name):
    return sum(snap.get(name, {}).get("values", {}).values())


class TestRoutedIdentity:
    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_routed_equals_single_engine(self, kv):
        """ACCEPTANCE: routed cluster output is token-identical to a
        single engine serving the same request set (fp + int8-KV), and
        the router actually spread the work over both replicas."""
        refs = _refs(kv)
        cluster = _cluster(kv, replicas=2)
        reqs = [cluster.submit(p, max_new_tokens=5,
                               tenant=f"t{i % 3}")
                for i, p in enumerate(_prompts())]
        cluster.run()
        for r, ref in zip(reqs, refs):
            assert r.done and r.finish_reason in ("eos", "max_len")
            assert np.array_equal(r.output, ref)
        assert len(cluster.router.dispatch_by_replica) == 2
        assert cluster.router.dispatches_total == len(reqs)
        # router bookkeeping drains with the requests (no rid leak)
        assert not cluster._live and not cluster._owner

    def test_all_replicas_dead_raises(self):
        from paddle_tpu.serving import EngineDead
        cluster = _cluster(replicas=1)
        cluster.replicas[0]._dead = True
        with pytest.raises(EngineDead):
            cluster.submit(_prompts()[0])


class TestHandoff:
    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_handoff_bit_identity(self, kv):
        """ACCEPTANCE: the prefill→decode handoff's pages are
        BYTE-identical to prefilling in place (raw export payloads
        compared right after prefill completes, before any decode),
        and the disaggregated cluster's final output matches the
        single-engine reference."""
        prompt = _prompts()[1]                      # 12 tokens, 2 chunks
        # in-place: engine primitives, prefill to completion, export
        eng = _factory(kv)()
        ra = eng.create_request(prompt, max_new_tokens=5)
        eng.admit_request(ra)
        while eng._pending:
            eng.prefill_step()
        ref_payload = eng.cache.export_request(ra.slot)
        # disaggregated: 1 prefill + 1 decode replica; export the
        # decode side right after the handoff lands (one token, no
        # decode on the imported pages yet)
        cluster = _cluster(kv, replicas=2, prefill_replicas=1)
        rb = cluster.submit(prompt, max_new_tokens=5)
        while cluster.handoffs_total == 0:
            assert cluster.step() or cluster.handoffs_total
        own = cluster.replicas[cluster._owner[rb.rid]]
        got = own.engine.cache.export_request(rb.slot)
        assert got["length"] == ref_payload["length"]
        assert got["num_pages"] == ref_payload["num_pages"]
        for name in ref_payload["arrays"]:
            assert np.array_equal(got["arrays"][name],
                                  ref_payload["arrays"][name]), name
        # the decode replica journals the adopted session
        assert rb.rid in {e.rid for e in own.journal.live_entries()}
        cluster.run()
        assert np.array_equal(rb.output, _refs(kv)[1])

    def test_disaggregated_parity_and_fallback(self):
        """Every request finishes token-identically even when the
        decode replica cannot absorb them all (max_batch=2, six
        requests): unplaced ones keep decoding on the prefill replica
        — disaggregation degrades to colocation, never stalls."""
        refs = _refs(None)
        cluster = _cluster(replicas=2, prefill_replicas=1)
        reqs = [cluster.submit(p, max_new_tokens=5) for p in _prompts()]
        cluster.run()
        for r, ref in zip(reqs, refs):
            assert np.array_equal(r.output, ref)
        assert cluster.handoffs_total >= 1

    def test_import_validation(self):
        """Geometry/dtype mismatches between replicas fail LOUDLY at
        import, before any allocation."""
        eng = _factory()()
        req = eng.create_request(_prompts()[0], max_new_tokens=4)
        eng.admit_request(req)
        while eng._pending:
            eng.prefill_step()
        payload = eng.cache.export_request(req.slot)
        other = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=2, page_size=16, max_len=32)
        with pytest.raises(ValueError, match="page_size"):
            other.cache.import_request(0, payload, 16)
        other8 = _factory("int8")()
        with pytest.raises(ValueError, match="tiers"):
            other8.cache.import_request(0, payload, 16)
        with pytest.raises(ValueError, match="inactive"):
            eng.cache.export_request(1 - req.slot)


class TestAffinity:
    def test_affinity_prefix_hits_counter_gated(self):
        """ACCEPTANCE: a tenant's second request follows its affinity
        binding to the same replica and actually admits with a prefix
        HIT — gated on the serving_prefix hit-token counter AND the
        router's affinity counters."""
        restore = _metrics()
        try:
            rs = np.random.RandomState(17)
            sysp = rs.randint(3, _CFG.vocab_size, (16,)).astype(np.int32)
            mk = lambda n: np.concatenate(  # noqa: E731
                [sysp, rs.randint(3, _CFG.vocab_size, (n,)).astype(
                    np.int32)])
            cluster = _cluster(replicas=2)
            r1 = cluster.submit(mk(3), max_new_tokens=4, tenant="a")
            cluster.run()
            r2 = cluster.submit(mk(4), max_new_tokens=4, tenant="a")
            cluster.run()
            # both dispatches landed on ONE replica (the binding held)
            assert len(cluster.router.dispatch_by_replica) == 1
            assert cluster.router.affinity_hits >= 1
            snap = obs.REGISTRY.to_json()
            assert _counter_sum(snap,
                                "serving_prefix_hit_tokens_total") >= 16
            aff = snap["serving_router_affinity_total"]["values"]
            assert aff.get("outcome=hit", 0) >= 1
        finally:
            restore()

    def test_short_prompt_has_no_affinity_key(self):
        cluster = _cluster(replicas=2)
        assert cluster.router.affinity_key(
            np.arange(5, dtype=np.int32)) is None
        key = cluster.router.affinity_key(
            np.arange(20, dtype=np.int32))
        assert key == np.arange(16, dtype=np.int32).tobytes()


class TestFairShareAndLimits:
    def test_fair_share_starvation_bound(self):
        """A light tenant submitting AFTER eight heavy-tenant requests
        dispatches among the first two — ascending-account order means
        no tenant waits behind another tenant's backlog."""
        cluster = _cluster(replicas=2)
        heavy = [cluster.submit(p, max_new_tokens=4, tenant="heavy")
                 for p in (_prompts() + _prompts(seed=5))[:8]]
        light = cluster.submit(_prompts()[0], max_new_tokens=4,
                               tenant="light")
        cluster.step()          # one dispatch pass drains the queue
        order = list(cluster._owner)        # dict preserves dispatch order
        assert order.index(light.rid) <= 1, order
        cluster.run()
        assert light.done and all(r.done for r in heavy)
        acc = cluster.router.stats()["tenant_accounts"]
        assert acc["heavy"] > acc["light"]

    def test_rate_limit_rejection(self):
        """Over-quota submissions finish ``rejected_ratelimit`` with
        zero tokens and never reach a replica; the window rolls with
        the injected clock."""
        restore = _metrics()
        try:
            now = [0.0]
            cluster = ServingCluster(
                _factory(), replicas=2, clock=lambda: now[0],
                quotas={"t": TenantQuota(20, window_s=10.0)},
                supervisor_kw=dict(_SKW))
            a = cluster.submit(_prompts()[0], max_new_tokens=5,
                               tenant="t")          # cost 11
            b = cluster.submit(_prompts()[1], max_new_tokens=5,
                               tenant="t")          # cost 17 > remaining
            assert not a.done
            assert b.done and b.finish_reason == "rejected_ratelimit"
            assert b.rid not in cluster._owner
            now[0] = 11.0                           # window rolls
            c = cluster.submit(_prompts()[1], max_new_tokens=5,
                               tenant="t")
            assert not c.done
            cluster.run()
            assert a.done and c.done and not b.tokens
            snap = obs.REGISTRY.to_json()
            assert _counter_sum(
                snap, "serving_router_ratelimited_total") == 1
        finally:
            restore()


class TestDegradedRouting:
    def test_router_retries_shed_work(self):
        """ACCEPTANCE (satellite): a LOW request shed by its
        affinity-bound degraded replica is re-dispatched once to the
        healthiest replica and finishes there; counted under
        serving_router_retries_total."""
        restore = _metrics()
        try:
            rs = np.random.RandomState(23)
            sysp = rs.randint(3, _CFG.vocab_size, (8,)).astype(np.int32)
            p1 = np.concatenate([sysp, rs.randint(
                3, _CFG.vocab_size, (3,)).astype(np.int32)])
            cluster = _cluster(replicas=2)
            r0 = cluster.submit(p1, max_new_tokens=4, tenant="a")
            cluster.run()
            bound = cluster.router._affinity[
                cluster.router.affinity_key(p1)]
            sup = cluster.replicas[bound]
            for _ in range(3):
                sup._escalate()         # shed_low: rejects fresh LOW
            before = dict(cluster.router.dispatch_by_replica)
            r1 = cluster.submit(p1, max_new_tokens=4, tenant="a",
                                priority=Priority.LOW)
            cluster.run()
            assert r1.done and r1.finish_reason in ("eos", "max_len")
            # one dispatch to the (shedding) bound replica + one retry
            # dispatch to the other
            after = cluster.router.dispatch_by_replica
            assert after[bound] == before.get(bound, 0) + 1
            assert after[1 - bound] == before.get(1 - bound, 0) + 1
            assert cluster.router.retries_total == 1
            snap = obs.REGISTRY.to_json()
            assert _counter_sum(snap,
                                "serving_router_retries_total") == 1
        finally:
            restore()

    def test_whole_cluster_shedding_surfaces_rejection(self):
        cluster = _cluster(replicas=2)
        for sup in cluster.replicas:
            for _ in range(3):
                sup._escalate()
        r = cluster.submit(_prompts()[0], max_new_tokens=4,
                           priority=Priority.LOW)
        cluster.step()
        assert r.done and r.finish_reason == "rejected_overload"
        assert not r.tokens


class TestLoadStats:
    def test_scheduler_load_stats_snapshot(self):
        """The satellite API: one structured snapshot with per-class
        queue depths, deadline slack, pool occupancy — pure host
        reads."""
        now = [100.0]
        eng = _factory()()
        sched = ServingScheduler(eng, clock=lambda: now[0])
        sched.submit(_prompts()[0], max_new_tokens=4,
                     priority=Priority.HIGH, deadline_s=5.0)
        sched.submit(_prompts()[1], max_new_tokens=4,
                     priority=Priority.LOW, deadline_s=9.0)
        s = sched.load_stats()
        assert s["queue_depths"] == {0: 1, 2: 1}
        assert s["queued_total"] == 2
        assert s["running"] == 0 and s["free_slots"] == 2
        assert abs(s["oldest_deadline_slack_s"] - 5.0) < 1e-9
        assert s["pool_occupancy"] == 0.0
        assert s["degraded_level"] == 0
        assert s["degraded_mode"] == "healthy"
        sched.run()

    def test_degraded_mode_visible_without_registry(self):
        """The latent-issue fix: the degraded rung reaches
        load_stats() through the scheduler mirror — no metrics
        registry required."""
        assert not obs.metrics_enabled()
        from paddle_tpu.serving import EngineSupervisor
        sup = EngineSupervisor(_factory(), **_SKW)
        sup._escalate()
        assert sup.scheduler.load_stats()["degraded_level"] == 1
        assert sup.scheduler.load_stats()["degraded_mode"] == "no_spec"
        assert sup.load_stats()["health"] == "degraded"
        assert sup.load_stats()["draining"] is False


class TestRetireReplica:
    def test_retire_mid_decode_parity_and_trie_survival(self):
        """ACCEPTANCE: retire_replica mid-decode — sessions requeue
        elsewhere and finish token-identically; the replacement
        replica inherits the drained prefix trie, so the tenant's next
        prompt still prefix-HITs (counter-gated)."""
        restore = _metrics()
        try:
            rs = np.random.RandomState(29)
            sysp = rs.randint(3, _CFG.vocab_size, (16,)).astype(np.int32)
            mk = lambda n: np.concatenate(  # noqa: E731
                [sysp, rs.randint(3, _CFG.vocab_size, (n,)).astype(
                    np.int32)])
            p1, p2 = mk(3), mk(4)
            eng = _factory()()
            ref1 = np.asarray(eng.generate([p1], max_new_tokens=6)[0])
            ref2 = np.asarray(eng.generate([p2], max_new_tokens=6)[0])
            cluster = _cluster(replicas=2)
            r1 = cluster.submit(p1, max_new_tokens=6, tenant="a")
            while len(r1.tokens) < 2:
                cluster.step()          # mid-decode, steps in flight
            assert r1.tokens and not r1.done
            idx = cluster._owner[r1.rid]
            summary = cluster.retire_replica(idx)
            assert summary["rehomed"] == 1
            assert cluster.retirements_total == 1
            cluster.run()
            assert np.array_equal(r1.output, ref1)
            # the rebuilt replica holds the drained trie: the binding
            # is still valid and the next same-prefix prompt HITs
            key = cluster.router.affinity_key(p2)
            assert cluster.router._affinity[key] == idx
            hit0 = _counter_sum(obs.REGISTRY.to_json(),
                                "serving_prefix_hit_tokens_total")
            r2 = cluster.submit(p2, max_new_tokens=6, tenant="a")
            cluster.run()
            assert np.array_equal(r2.output, ref2)
            hit1 = _counter_sum(obs.REGISTRY.to_json(),
                                "serving_prefix_hit_tokens_total")
            assert hit1 >= hit0 + 16
        finally:
            restore()

    def test_retire_without_replace_needs_survivor(self):
        cluster = _cluster(replicas=1)
        with pytest.raises(ValueError, match="serviceable"):
            cluster.retire_replica(0, replace=False)
        # the guard counts SERVICEABLE survivors, not list length:
        # after one non-replace retirement of a 2-replica cluster, the
        # drained husk must not satisfy the next retirement's guard
        c2 = _cluster(replicas=2)
        c2.retire_replica(0, replace=False)
        with pytest.raises(ValueError, match="serviceable"):
            c2.retire_replica(1, replace=False)


class TestClusterChaosSoak:
    def test_cluster_soak_replica_kill(self):
        """Tier-1 variant of ``tools/chaos_soak.py --cluster``: a
        replica is killed mid-traffic via the FaultInjector (circuit
        opens), the cluster fails over with ZERO lost/duplicated
        requests, and prefix-affinity hit rate recovers after the
        replica rebuilds (run_cluster_soak raises SoakError on any
        violation)."""
        spec = importlib.util.spec_from_file_location(
            "chaos_soak", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "tools", "chaos_soak.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        report = mod.run_cluster_soak(seed=0, requests=12, replicas=3)
        assert report["failovers"] >= 1
        assert report["rehomed_sessions"] >= 1
        assert report["affinity_hit_rate"] > 0
        assert report["prefix_hit_tokens"] > 0


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="tp cluster needs >= 2 devices")
class TestTpCluster:
    def test_tp2_routed_handoff_identity(self):
        """ACCEPTANCE: routed + disaggregated serving over tp=2
        SHARDED replicas stays token-identical to the single-chip
        reference (the handoff scatter preserves the kv-head
        sharding)."""
        refs = _refs(None)
        cluster = _cluster(mesh=serving_mesh(2), replicas=2,
                           prefill_replicas=1)
        reqs = [cluster.submit(p, max_new_tokens=5)
                for p in _prompts()[:3]]
        cluster.run()
        for r, ref in zip(reqs, refs[:3]):
            assert np.array_equal(r.output, ref)
        assert cluster.handoffs_total >= 1
