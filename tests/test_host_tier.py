"""Hierarchical KV tier tests (ISSUE 10 acceptance gates).

The host-RAM page tier under the paged allocator
(paddle_tpu/serving/host_tier.py). The hard gates:

- **Swap parity**: preempt → SWAP-OUT → swap-in → finish decode is
  BIT-IDENTICAL to uninterrupted decode at fp and int8-KV, including
  tp=2-sharded pools (the per-shard kv-head byte layout round-trips
  exactly through the raw-uint8 host payloads).
- **Standing store**: a RESTARTED engine — a fresh process sharing only
  the on-disk prefix store directory — serves a persisted system
  prompt as a prefix HIT (promote counters + hit-token counters gate
  it), not a re-prefill.
- **Recovery swaps in**: a supervisor recovery finds swapped-out
  sessions' payloads carried across the engine rebuild and swaps them
  in instead of charging the replay prefill — still token-identical,
  and faults injected AT the swap_out/swap_in sites recover cleanly.

This module runs BEFORE the persistent-compilation-cache boundary
(tests/conftest.py orders it with tests/test_offload.py) and disables
the cache for itself — the known XLA:CPU segfault when host-memory
programs meet the compilation-cache machinery must never take tier-1's
watchdog down with it.
"""
import os
import tempfile
import types

import numpy as np
import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _no_compilation_cache():
    """Same guard as tests/test_offload.py: the host-tier programs move
    KV through host memory; in a process where the persistent XLA
    compilation cache has been active, XLA:CPU's host-memory-space
    handling is known to segfault. conftest orders this module before
    the cache boundary; this fixture additionally guards direct
    invocations where the cache was enabled externally."""
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.distributed.mesh import serving_mesh
from paddle_tpu.serving import (EngineSupervisor, FaultInjector,
                                HostPageStore, PreemptionPolicy,
                                Priority, ServingCluster,
                                ServingScheduler, TieredKVCache,
                                TokenBudgetPlanner)

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(1), _CFG)

#: first engine built per (kv, mesh-key) — later engines adopt its
#: compiled step programs (pure functions of their array arguments,
#: the same carry the supervisor does across rebuilds) so the parity
#: sweep compiles each program once, not once per test
_PROTO = {}


def _engine(kv=None, mesh=None, host=True, **kw):
    key = (kv, None if mesh is None else tuple(mesh.shape.items()))
    eng_kw = dict(max_batch=1, page_size=8, max_len=32,
                  kv_cache_dtype=kv, mesh=mesh, host_tier=host)
    eng_kw.update(kw)
    eng = ContinuousBatchingEngine(_PARAMS, _CFG, **eng_kw)
    proto = _PROTO.get(key)
    if proto is None:
        _PROTO[key] = eng
    else:
        eng._chunk_fns = proto._chunk_fns
        eng.cache._cow_fn = proto.cache._cow_fn
        eng.cache._scatter_fn = proto.cache._scatter_fn
        if proto._decode_fn is not None:
            eng._decode_fn = proto._decode_fn
        if host and getattr(proto.cache, "_gather_fn", None) is not None:
            eng.cache._gather_fn = proto.cache._gather_fn
    return eng


def _prompt(n, seed=0):
    rs = np.random.RandomState(seed)
    return rs.randint(3, _CFG.vocab_size, (n,)).astype(np.int32)


def _swap_preempt_run(kv=None, mesh=None, **host_kw):
    """Shared scenario: a LOW request decodes, a HIGH burst preempts it
    (swap-out), HIGH finishes, LOW swaps back in and finishes. Returns
    (victim request, engine, scheduler)."""
    eng = _engine(kv=kv, mesh=mesh,
                  host_tier_kw=host_kw if host_kw else None)
    sched = ServingScheduler(eng)
    a = sched.submit(_prompt(6, seed=2), max_new_tokens=8,
                     priority=Priority.LOW)
    while len(a.tokens) < 3:
        sched.step()
    sched.submit(_prompt(4, seed=3), max_new_tokens=2,
                 priority=Priority.HIGH)
    sched.step()
    assert a.preemptions == 1 and a.slot is None
    sched.run()
    return a, eng, sched


class TestHostPageStore:
    def test_roundtrip_raw_bytes_and_accounting(self):
        import ml_dtypes
        store = HostPageStore(page_size=8)
        arrays = {
            "k": np.arange(2 * 3 * 8 * 4, dtype=np.float32).reshape(
                2, 3, 8, 4).astype(ml_dtypes.bfloat16),
            "ks": np.ones((2, 3, 8), np.int8),
        }
        entry = store.put(("swap", 7), arrays, extra={"length": 20})
        assert store.pages_resident == 3
        assert store.bytes_resident == entry["bytes"] > 0
        got = HostPageStore.decode(store.get(("swap", 7)))
        assert str(got["k"].dtype) == "bfloat16"       # raw-byte roundtrip
        np.testing.assert_array_equal(
            got["k"].view(np.uint8), arrays["k"].view(np.uint8))
        np.testing.assert_array_equal(got["ks"], arrays["ks"])
        assert store.pop(("swap", 7))["extra"]["length"] == 20
        assert store.pages_resident == 0 and store.bytes_resident == 0
        assert store.get(("swap", 7), touch=False) is None

    def test_capacity_drops_lru_first(self):
        store = HostPageStore(page_size=8, capacity_pages=4)
        one_page = {"k": np.zeros((1, 1, 8), np.int8)}
        for i in range(4):
            store.put(("swap", i), one_page)
        store.get(("swap", 0))              # 0 becomes most-recent
        store.put(("swap", 9), one_page)    # over capacity: drop LRU (1)
        assert store.get(("swap", 1), touch=False) is None
        assert store.get(("swap", 0), touch=False) is not None
        assert store.capacity_drops_total == 1
        assert store.pages_resident == 4

    def test_standing_disk_tier_survives_new_store(self):
        d = tempfile.mkdtemp()
        key = np.arange(8, dtype=np.int32).tobytes()
        a = HostPageStore(page_size=8, path=d)
        a.put(key, {"k": np.full((1, 1, 8), 3, np.int8)},
              extra={"tokens": list(range(8))}, persist=True)
        assert len(os.listdir(d)) == 1
        b = HostPageStore(page_size=8, path=d)      # fresh process's view
        entry = b.get(key)                          # RAM miss -> disk hit
        assert entry is not None and entry["extra"]["tokens"] == \
            list(range(8))
        np.testing.assert_array_equal(
            HostPageStore.decode(entry)["k"], np.full((1, 1, 8), 3,
                                                      np.int8))
        with pytest.raises(ValueError, match="bytes keys"):
            a.put(("swap", 1), {"k": np.zeros((1, 1, 8), np.int8)},
                  persist=True)

    def test_disk_promotion_respects_capacity(self):
        """A RAM miss promoted from the standing disk tier obeys the
        same capacity bound a put() does — read-mostly restarted
        engines must not grow host RAM past the cap."""
        d = tempfile.mkdtemp()
        writer = HostPageStore(page_size=8, path=d)
        keys = [np.arange(8 * (i + 1), dtype=np.int32).tobytes()
                for i in range(2)]
        for k in keys:
            writer.put(k, {"k": np.zeros((1, 1, 8), np.int8)},
                       persist=True)
        reader = HostPageStore(page_size=8, capacity_pages=1, path=d)
        assert reader.get(keys[0]) is not None      # disk -> RAM
        assert reader.get(keys[1]) is not None      # disk -> RAM, evicts
        assert reader.pages_resident <= 1
        assert reader.capacity_drops_total >= 1
        # the dropped entry is still a (disk) hit, not a loss
        assert reader.get(keys[0]) is not None

    def test_torn_disk_file_reads_as_miss(self):
        d = tempfile.mkdtemp()
        key = b"\x01\x02\x03\x04"
        from paddle_tpu.serving.host_tier import _key_name
        with open(os.path.join(d, _key_name(key)), "wb") as f:
            f.write(b"not an npz")
        store = HostPageStore(page_size=8, path=d)
        assert store.get(key) is None


class TestPolicy:
    def test_planner_reserves_swap_charge(self):
        planner = TokenBudgetPlanner(16, 8)
        decode = [(Priority.LOW, i, i) for i in range(4)]
        pending = [(Priority.HIGH, 9, 9, 32)]
        plan = planner.plan(decode, pending, chunk_cap=16,
                            reserved_tokens=8)
        # one 8-token page of budget is already spent on the swap-in:
        # only one page of prefill fits, decodes take the tail
        assert plan.reserved_tokens == 8
        assert plan.scheduled_tokens + plan.reserved_tokens <= 16
        assert plan.prefills == [(9, 8)]
        # a reserve covering the whole budget defers everything
        plan = planner.plan(decode, pending, chunk_cap=16,
                            reserved_tokens=16)
        assert plan.scheduled_tokens == 0
        assert plan.deferred_decodes == 4

    def test_preemption_policy_prefers_swappable(self):
        def req(prio, ntok, rid):
            return types.SimpleNamespace(priority=int(prio),
                                         tokens=[0] * ntok, rid=rid)
        pol = PreemptionPolicy()
        running = [req(Priority.LOW, 9, 1), req(Priority.LOW, 2, 2)]
        # without the predicate: fewest tokens wins (rid 2)
        assert pol.pick_victim(running, Priority.HIGH).rid == 2
        # with it: the swappable victim wins even with more tokens —
        # its resume is one page copy, the other's is a replay
        assert pol.pick_victim(
            running, Priority.HIGH,
            swappable=lambda r: r.rid == 1).rid == 1
        # class still dominates swappability
        running.append(req(Priority.NORMAL, 0, 3))
        assert pol.pick_victim(
            running, Priority.HIGH,
            swappable=lambda r: r.rid == 3).rid in (1, 2)


class TestSwapResume:
    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_swap_resume_token_parity(self, kv):
        """ACCEPTANCE: preempt→swap-out→swap-in→finish is BIT-IDENTICAL
        to uninterrupted decode, fp and int8-KV — and the resume really
        was a swap (no replay prefill ran for the victim)."""
        ref = _engine(kv=kv, host=False).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]
        a, eng, sched = _swap_preempt_run(kv=kv)
        assert eng.cache.swap_outs_total == 1
        assert eng.cache.swap_ins_total == 1
        assert eng.cache.swap_replay_fallbacks == 0
        assert sched.resumes_total == 1
        assert a.done and a.finish_reason == "max_len"
        np.testing.assert_array_equal(a.output, ref)
        # swap cycle kept the allocator balanced
        if eng.cache.prefix is not None:
            eng.cache.prefix.drop_all(eng.cache.allocator)
        st = eng.cache.allocator.stats()
        assert st["num_used"] == 0
        assert st["allocs_total"] == st["frees_total"]

    def test_swap_resume_parity_tp2_sharded_pool(self):
        """ACCEPTANCE: the same gate on a tp=2 kv-head-sharded pool —
        the per-shard byte layout round-trips exactly through the host
        payload (raw global bytes; the scatter re-installs the
        sharding)."""
        ref = _engine(host=False).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]
        a, eng, _ = _swap_preempt_run(mesh=serving_mesh(2))
        assert eng.cache.swap_outs_total == 1
        assert eng.cache.swap_ins_total == 1
        np.testing.assert_array_equal(a.output, ref)

    @pytest.mark.parametrize("wb,kv", [(4, None), (8, "int8")])
    def test_swap_resume_parity_lowbit_tiers(self, wb, kv):
        """ISSUE 11: preempt→swap-out→swap-in→finish on the LOW-BIT
        weight tiers (per-group int4; w8/kv8) — the swap path moves KV
        bytes and is weight-dtype-agnostic, and decode after the
        swap-in stays token-identical to uninterrupted low-bit
        decode."""
        ref = _engine(kv=kv, host=False, weight_bits=wb).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]
        eng = _engine(kv=kv, weight_bits=wb)
        sched = ServingScheduler(eng)
        a = sched.submit(_prompt(6, seed=2), max_new_tokens=8,
                         priority=Priority.LOW)
        while len(a.tokens) < 3:
            sched.step()
        sched.submit(_prompt(4, seed=3), max_new_tokens=2,
                     priority=Priority.HIGH)
        sched.step()
        assert a.preemptions == 1
        sched.run()
        assert eng.cache.swap_outs_total == 1
        assert eng.cache.swap_ins_total == 1
        np.testing.assert_array_equal(a.output, ref)

    def test_swap_fallback_to_replay_when_dropped(self):
        """A payload LRU-dropped from a tiny host pool falls back to
        the replay-prefill resume — slower, still bit-identical."""
        ref = _engine(host=False).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]
        eng = _engine(host_tier_kw={"host_capacity_pages": 1,
                                    "persist_prefix": False})
        sched = ServingScheduler(eng)
        a = sched.submit(_prompt(6, seed=2), max_new_tokens=8,
                         priority=Priority.LOW)
        while len(a.tokens) < 3:
            sched.step()
        sched.submit(_prompt(4, seed=3), max_new_tokens=2,
                     priority=Priority.HIGH)
        sched.step()                        # swap-out (2 pages > capacity
        assert a.preemptions == 1           # -> entry immediately shed)
        eng.cache.host.put(("pad", 0),      # ...and definitely gone now
                           {"k": np.zeros((1, 1, 8), np.int8)})
        sched.run()
        assert eng.cache.swap_replay_fallbacks >= 1
        np.testing.assert_array_equal(a.output, ref)

    def test_scheduler_charges_swap_in_against_budget(self):
        """The step that admits a swap-in reserves its pages' tokens
        out of the budget, amortizing a swap bigger than one step's
        budget across later steps — (planned + reserved) <= budget on
        EVERY step, observably."""
        eng = _engine()
        budget = 10
        sched = ServingScheduler(eng, token_budget=budget)
        a = sched.submit(_prompt(6, seed=2), max_new_tokens=8,
                         priority=Priority.LOW)
        while len(a.tokens) < 3:
            sched.step()
        sched.submit(_prompt(4, seed=3), max_new_tokens=2,
                     priority=Priority.HIGH)
        # drive to completion; the swap-in resume step must show the
        # reserve and never exceed the ceiling
        saw_reserve = False
        guard = 0
        while sched.step():
            plan = sched.last_plan
            assert plan.scheduled_tokens + plan.reserved_tokens \
                <= budget
            saw_reserve = saw_reserve or plan.reserved_tokens > 0
            guard += 1
            assert guard < 200
        assert eng.cache.swap_ins_total == 1
        assert saw_reserve

    def test_mid_prefill_victim_still_replays(self):
        """A victim preempted before any token committed has no KV
        worth swapping: the plain evict/replay path serves it, and the
        host tier never sees it — still bit-identical."""
        kw = dict(max_batch=1, page_size=8, max_len=32, prefill_chunk=8,
                  enable_prefix_cache=False)
        p = _prompt(20, seed=17)
        ref = ContinuousBatchingEngine(_PARAMS, _CFG, **kw).generate(
            [p], max_new_tokens=5)[0]
        eng = ContinuousBatchingEngine(_PARAMS, _CFG, **kw,
                                       host_tier=True)
        sched = ServingScheduler(eng)
        a = sched.submit(p, max_new_tokens=5, priority=Priority.LOW)
        sched.step()                        # first chunk only
        assert a.slot is not None and len(a.tokens) == 0
        sched.submit(_prompt(4, seed=18), max_new_tokens=2,
                     priority=Priority.HIGH)
        sched.step()
        assert a.preemptions == 1
        assert eng.cache.swap_outs_total == 0
        sched.run()
        np.testing.assert_array_equal(a.output, ref)


class TestPrefixTier:
    def test_demote_then_promote_hit(self):
        """A chain evicted under PoolExhausted demotes to host and the
        next same-prefix admission promotes it back — prefix HIT, not
        re-prefill, and output parity holds."""
        sys_prompt = _prompt(16, seed=5)
        p1 = np.concatenate([sys_prompt, _prompt(3, seed=6)])
        p2 = np.concatenate([sys_prompt, _prompt(3, seed=7)])
        ref = _engine(host=False).generate([p2], max_new_tokens=4)[0]
        eng = _engine(num_pages=6,
                      host_tier_kw={"persist_prefix": False})
        eng.generate([p1], max_new_tokens=4)
        # a request too big for the trie-laden pool forces demotion
        eng.generate([_prompt(30, seed=8)], max_new_tokens=2)
        assert eng.cache.demotions_total >= 1
        assert len(eng.cache.host) >= 1
        o2 = eng.generate([p2], max_new_tokens=4)[0]
        assert eng.cache.promote_hits_total >= 1
        np.testing.assert_array_equal(o2, ref)

    def test_restarted_engine_prefix_hits_from_standing_store(self):
        """ACCEPTANCE: a fresh engine sharing only the standing store
        DIRECTORY serves the persisted system prompt as a prefix HIT
        (hit-token + promote counters both gate it) and decodes
        token-identically."""
        from paddle_tpu import observability as obs
        d = tempfile.mkdtemp()
        sys_prompt = _prompt(16, seed=9)            # two full 8-token pages
        p1 = np.concatenate([sys_prompt, _prompt(4, seed=10)])
        p2 = np.concatenate([sys_prompt, _prompt(4, seed=11)])
        ref = _engine(host=False).generate([p2], max_new_tokens=4)[0]
        host_kw = {"prefix_store_dir": d}
        eng = _engine(host_tier_kw=host_kw)
        eng.generate([p1], max_new_tokens=4)
        assert len(os.listdir(d)) == 2              # chains on disk
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng2 = _engine(host_tier_kw=host_kw)    # "restarted" engine
            o2 = eng2.generate([p2], max_new_tokens=4)[0]
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert eng2.cache.promote_hits_total == 2
        hit = sum(snap["serving_prefix_hit_tokens_total"]
                  ["values"].values())
        promoted = sum(snap["serving_prefix_promoted_pages_total"]
                       ["values"].values())
        assert hit >= 16 and promoted == 2
        np.testing.assert_array_equal(o2, ref)

    def test_promotion_under_pressure_never_aliases_trie_pages(self):
        """Promotion pins the matched trie span before allocating (the
        admit_prompt guard): when its own allocation must evict under a
        FULL pool, a matched page can never be recycled into the fresh
        set and re-registered — no two trie nodes may ever share a
        physical page, and the worst case is honest back-pressure
        (PoolExhausted), never silent prefix corruption."""
        from paddle_tpu.serving import PoolExhausted
        cache = TieredKVCache(_CFG, 2, 32, page_size=8, num_pages=6,
                              persist_prefix=False)
        p24 = _prompt(24, seed=20)
        cache.admit(0, 24)
        cache.lengths[0] = 24
        cache.register_prefix(0, p24)               # 3-page chain
        cache.release(0)
        cache._evict_prefix(1)                      # chain-3 -> host
        assert cache.demotions_total == 1
        cache.admit(1, 24)                          # pool now 100% full
        assert cache.allocator.num_free == 0
        p25 = np.concatenate(
            [p24, _prompt(1, seed=21)]).astype(np.int32)
        # the promotion itself: its alloc must evict, and the eviction
        # must NOT recycle a matched page into the fresh set (the
        # unpinned code registered chain-3's bytes onto chain-2's
        # recycled page id — two trie nodes aliasing one physical page)
        promoted = cache._promote_prefix(p25)

        def trie_pages():
            out, stack = [], [cache.prefix.root]
            while stack:
                node = stack.pop()
                if node.page is not None:
                    out.append(node.page)
                    assert cache.allocator.refcount(node.page) >= 1
                stack.extend(node.children.values())
            return out
        pages = trie_pages()
        assert len(pages) == len(set(pages)), \
            f"trie nodes alias physical pages: {sorted(pages)}"
        # pinned promotion under a full pool aborts cleanly instead
        assert promoted == 0
        # ...and the full admission path stays corruption-free too
        # (honest back-pressure is an acceptable outcome here)
        try:
            cache.admit_prompt(0, p25, 25)
        except PoolExhausted:
            pass
        pages = trie_pages()
        assert len(pages) == len(set(pages))

    def _corrupted_store_roundtrip(self, damage, seed0):
        """Shared scaffold (ISSUE 13 satellite): write a standing
        store, DAMAGE one chain file on disk, then restart the engine
        against the same directory — the admission must fall back to a
        prefix MISS + replay (no crash, no corrupt KV served), with
        the quarantine counters emitted and the bad file removed so it
        can never be re-read."""
        from paddle_tpu import observability as obs
        d = tempfile.mkdtemp()
        sys_prompt = _prompt(16, seed=seed0)
        p1 = np.concatenate([sys_prompt, _prompt(4, seed=seed0 + 1)])
        p2 = np.concatenate([sys_prompt, _prompt(4, seed=seed0 + 2)])
        ref = _engine(host=False).generate([p2], max_new_tokens=4)[0]
        host_kw = {"prefix_store_dir": d}
        _engine(host_tier_kw=host_kw).generate([p1], max_new_tokens=4)
        files = sorted(os.listdir(d))
        assert len(files) == 2
        damage(os.path.join(d, files[0]))
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng2 = _engine(host_tier_kw=host_kw)
            o2 = eng2.generate([p2], max_new_tokens=4)[0]
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        np.testing.assert_array_equal(o2, ref)
        assert eng2.cache.host.quarantined_total >= 1
        # the damaged chain never promoted (its pages replayed); only
        # the intact sibling may have
        assert eng2.cache.promote_hits_total < 2
        # the quarantined file was removed, then the replayed chain's
        # write-through re-created it with FRESH bytes — a brand-new
        # store must read every surviving file cleanly
        from paddle_tpu.serving import HostPageStore
        probe = HostPageStore(8, path=d)
        for f in list(os.listdir(d)):
            with np.load(os.path.join(d, f)) as data:
                raw_key = bytes(np.asarray(data["key"]))
            assert probe.get(raw_key) is not None, \
                f"store file {f} unreadable after recovery"
        assert probe.quarantined_total == 0
        q = sum(v for k, v in snap.get(
            "serving_integrity_events_total", {})
            .get("values", {}).items()
            if "quarantined" in k)
        assert q >= 1

    def test_torn_standing_store_file_replays(self):
        """SATELLITE: a TRUNCATED (torn-write) standing-store ``.npz``
        is a quarantined miss on restart, never a crash or corrupt
        KV."""
        def truncate(fn):
            n = os.path.getsize(fn)
            with open(fn, "rb") as f:
                half = f.read(n // 2)
            with open(fn, "wb") as f:
                f.write(half)
        self._corrupted_store_roundtrip(truncate, seed0=40)

    def test_bitflipped_standing_store_file_replays(self):
        """SATELLITE: a BIT-FLIPPED standing-store ``.npz`` (payload
        damage a torn-write check can't see) is detected before any
        scatter — quarantined miss + replay, token-identically."""
        def bitflip(fn):
            with open(fn, "rb") as f:
                raw = bytearray(f.read())
            raw[len(raw) // 2] ^= 0xFF
            with open(fn, "wb") as f:
                f.write(bytes(raw))
        self._corrupted_store_roundtrip(bitflip, seed0=44)

    def test_stale_store_geometry_reads_as_miss(self):
        """A standing store written by a DIFFERENT kv tier must not
        corrupt the pool: promotion drops the bad chain and the
        admission proceeds as a plain miss."""
        d = tempfile.mkdtemp()
        sys_prompt = _prompt(16, seed=12)
        p = np.concatenate([sys_prompt, _prompt(4, seed=13)])
        host_kw = {"prefix_store_dir": d}
        _engine(host_tier_kw=host_kw).generate([p], max_new_tokens=2)
        ref = _engine(kv="int8", host=False).generate(
            [p], max_new_tokens=4)[0]
        eng = _engine(kv="int8", host_tier_kw=host_kw)
        out = eng.generate([p], max_new_tokens=4)[0]
        assert eng.cache.promote_hits_total == 0
        np.testing.assert_array_equal(out, ref)


class TestResilience:
    def test_recovery_swaps_in_instead_of_replaying(self):
        """ACCEPTANCE: a swapped-out session's payload survives the
        engine teardown (host state carries across rebuilds), the
        journal marks it host-resident, and the recovered session
        swaps in — token-identical, no replay for it."""
        ref = _engine(host=False).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]

        def factory():
            return _engine()
        inj = FaultInjector(seed=0)
        sup = EngineSupervisor(factory, backoff_s=0.0,
                               sleep=lambda s: None)
        with inj:
            a = sup.submit(_prompt(6, seed=2), max_new_tokens=8,
                           priority=Priority.LOW)
            while len(a.tokens) < 3:
                sup.step()
            sup.submit(_prompt(4, seed=3), max_new_tokens=2,
                       priority=Priority.HIGH)
            sup.step()                       # preempts a: swap-out
            assert sup.engine.cache.swap_outs_total == 1
            sup._sync_journal()
            entry = [e for e in sup.journal.live_entries()
                     if e.rid == a.rid]
            assert entry and entry[0].swapped
            inj.arm("decode_step", "raise", nth=1)
            sup.run()                        # fault -> rebuild -> swap in
        assert sup.recoveries == 1
        assert sup.engine.cache.swap_ins_total == 1
        assert sup.engine.cache.swap_replay_fallbacks == 0
        np.testing.assert_array_equal(a.output, ref)

    def test_fault_at_swap_in_absorbed_by_bounded_retry(self):
        """ISSUE 13: a transient fault AT swap_in retries in place
        (bounded exponential backoff, idempotent — the failed attempt
        committed nothing) instead of costing a full engine recovery;
        the payload survives and the retried scatter installs it
        bit-identically."""
        ref = _engine(host=False).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]

        def factory():
            return _engine()
        inj = FaultInjector(seed=0)
        sup = EngineSupervisor(factory, backoff_s=0.0,
                               sleep=lambda s: None)
        with inj:
            a = sup.submit(_prompt(6, seed=2), max_new_tokens=8,
                           priority=Priority.LOW)
            while len(a.tokens) < 3:
                sup.step()
            sup.submit(_prompt(4, seed=3), max_new_tokens=2,
                       priority=Priority.HIGH)
            sup.step()                       # swap-out succeeds
            inj.arm("swap_in", "raise", nth=1)
            sup.run()
        assert inj.fired["swap_in"] == 1
        assert sup.recoveries == 0           # absorbed, no teardown
        assert sup.engine.cache.swap_in_retries_total == 1
        assert sup.engine.cache.swap_ins_total == 1
        np.testing.assert_array_equal(a.output, ref)

    def test_swap_in_retry_exhaustion_recovers_token_identically(self):
        """Past the retry budget the fault escalates to the supervisor
        (the pre-ISSUE-13 path): the payload still committed nothing,
        survives the teardown, and the recovered resume swaps it in —
        bit-identical either way."""
        ref = _engine(host=False).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]

        def factory():
            return _engine()
        inj = FaultInjector(seed=0)
        sup = EngineSupervisor(factory, backoff_s=0.0,
                               sleep=lambda s: None)
        with inj:
            a = sup.submit(_prompt(6, seed=2), max_new_tokens=8,
                           priority=Priority.LOW)
            while len(a.tokens) < 3:
                sup.step()
            sup.submit(_prompt(4, seed=3), max_new_tokens=2,
                       priority=Priority.HIGH)
            sup.step()                       # swap-out succeeds
            # one more fault than the budget (default 2 retries = 3
            # attempts): every in-place attempt fails, the supervisor
            # pays one recovery, and the post-recovery admission swaps
            # the surviving payload in
            for _ in range(3):
                inj.arm("swap_in", "raise", nth=1)
            sup.run()
        assert inj.fired["swap_in"] == 3
        assert sup.recoveries == 1
        assert sup.engine.cache.swap_in_retries_total == 2
        assert sup.engine.cache.swap_ins_total == 1
        np.testing.assert_array_equal(a.output, ref)

    def test_fault_at_swap_out_falls_back_cleanly(self):
        """A fault AT swap_out fires before the gather: no payload
        exists, the recovered victim replays — still bit-identical."""
        ref = _engine(host=False).generate(
            [_prompt(6, seed=2)], max_new_tokens=8)[0]

        def factory():
            return _engine()
        inj = FaultInjector(seed=0)
        sup = EngineSupervisor(factory, backoff_s=0.0,
                               sleep=lambda s: None)
        with inj:
            a = sup.submit(_prompt(6, seed=2), max_new_tokens=8,
                           priority=Priority.LOW)
            while len(a.tokens) < 3:
                sup.step()
            inj.arm("swap_out", "raise", nth=1)
            sup.submit(_prompt(4, seed=3), max_new_tokens=2,
                       priority=Priority.HIGH)
            sup.run()
        assert inj.fired["swap_out"] == 1
        assert sup.engine.cache.swap_ins_total == 0
        np.testing.assert_array_equal(a.output, ref)


class TestCluster:
    def test_failover_rehomed_session_swaps_in_on_survivor(self):
        """The cluster shares ONE host store across replicas: a
        session swapped out on a replica that then DIES swaps in on
        whichever replica it rehomes to — no replay, token-identical
        cluster-wide."""
        def factory():
            return _engine(max_batch=2)
        refs = [
            _engine(host=False).generate([_prompt(6, seed=2)],
                                         max_new_tokens=8)[0],
            _engine(host=False).generate([_prompt(5, seed=4)],
                                         max_new_tokens=4)[0],
        ]
        cluster = ServingCluster(
            factory, replicas=2,
            supervisor_kw=dict(backoff_s=0.0, sleep=lambda s: None,
                               circuit_threshold=2, recover_after=4))
        store = cluster._host_store
        assert store is not None
        assert all(sup.engine.cache.host is store
                   for sup in cluster.replicas)
        inj = FaultInjector(seed=0)
        with inj:
            a = cluster.submit(_prompt(6, seed=2), max_new_tokens=8,
                               tenant="t0", priority=Priority.LOW)
            b = cluster.submit(_prompt(5, seed=4), max_new_tokens=4,
                               tenant="t1", priority=Priority.LOW)
            while len(a.tokens) < 3 or len(b.tokens) < 2:
                cluster.step()
            # swap a out on its owner, then blow that replica's circuit
            owner = cluster.replicas[cluster._owner[a.rid]]
            owner.engine.fence()        # by hand, so read first by hand
            owner.engine.cache.swap_out(a.slot, a.rid)
            owner.engine._slots[a.slot] = None
            a.slot = None
            a.preemptions += 1
            a.swapped = True
            a.finish_reason = "preempted"
            owner.scheduler.requeue(a, front=True)
            for _ in range(2):
                inj.arm("sched_tick", "raise", nth=1)
            before = cluster.failovers_total
            while cluster.step():
                pass
        assert cluster.failovers_total >= before  # survived either way
        swap_ins = sum(s.engine.cache.swap_ins_total
                       for s in cluster.replicas)
        assert swap_ins >= 1
        np.testing.assert_array_equal(a.output, refs[0])
        np.testing.assert_array_equal(b.output, refs[1])


class TestLowering:
    def test_swap_gather_scatter_export_to_tpu(self):
        """The swap-out gather + swap-in scatter AOT-export to the TPU
        platform (the tools/aot_validate.py --config serving-host gate,
        smoke-tested here at the fp layout)."""
        import jax.export
        import jax.numpy as jnp
        from paddle_tpu.models import generate as gen
        from paddle_tpu.serving.host_tier import _pool_gather
        from paddle_tpu.serving.paged_cache import _pool_scatter
        pool = gen.init_paged_cache(_CFG, num_pages=9, page_size=8)
        ids = jnp.asarray(np.asarray([1, 3], np.int32))
        jax.export.export(jax.jit(_pool_gather),
                          platforms=["tpu"])(pool, ids)
        vals = {n: np.zeros((a.shape[0], 2) + a.shape[2:], a.dtype)
                for n, a in pool.items()}
        jax.export.export(jax.jit(_pool_scatter, donate_argnums=(0,)),
                          platforms=["tpu"])(pool, vals, ids)
