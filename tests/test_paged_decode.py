"""Paged KV cache + continuous-batching decode engine tests.

The serving acceptance gate: paged decode must be TOKEN-IDENTICAL to
the dense-cache decode (fp and int8 KV tiers), the allocator must
survive alloc/free/OOM cycles, and the engine must admit new prompts
into free slots mid-decode without disturbing live rows.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import llama, generate
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.serving import (BlockAllocator, PagedKVCache,
                                PoolExhausted, TRASH_PAGE)
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import flash_attention as fa


def _setup(seed=0, **kw):
    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64, **kw)
    params = llama.init_params(jax.random.key(seed), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _dense_ref(params, prompt, cfg, new, ext, kv=None):
    """Single-request dense-cache greedy reference, cache sized to the
    engine's per-slot extent so attention reductions match bit-for-bit."""
    return np.asarray(generate.generate(
        params, jnp.asarray(prompt[None]), cfg, max_new_tokens=new,
        temperature=0.0, max_len=ext, kv_cache_dtype=kv))[0]


class TestPagedDenseParity:
    """Acceptance gate: paged decode == dense decode, token for token."""

    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_mixed_length_batch_matches_dense(self, kv):
        cfg, params = _setup()
        prompts = _prompts(cfg, [4, 7], seed=1)
        new = 6
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=16,
            kv_cache_dtype=kv)
        outs = eng.generate(prompts, max_new_tokens=new)
        ext = eng.cache.max_len
        for out, p in zip(outs, prompts):
            np.testing.assert_array_equal(
                out, _dense_ref(params, p, cfg, new, ext, kv=kv))
        # chunked-prefill programs are bucketed by PAGE multiple, not
        # prompt length: both prompts (4 and 7 tokens) share the
        # (ctx=0, width=8) program
        assert list(eng._chunk_fns) == [(0, 8)]

    def test_prefill_insert_scatters_dense_rows(self):
        """Pages gathered back in block-table order hold exactly the
        dense prefill's cache rows (the storage is paged, the content
        is not)."""
        cfg, params = _setup(seed=2)
        page = 8
        paged = generate.init_paged_cache(cfg, num_pages=5, page_size=page)
        table = jnp.asarray([2, 4], jnp.int32)       # 2 pages = 16 slots
        prompt = jnp.asarray(_prompts(cfg, [6], seed=3)[0][None])
        logits_p, paged = generate.paged_prefill_insert(
            params, prompt, paged, table, cfg)
        dense = generate.init_cache(cfg, 1, 16)
        logits_d, dense = generate._forward_cached(
            params, prompt, dense, 0, cfg, 16)
        # two programs: equal to the bound the paged path states against
        # the dense one (PERF.md section 6, PR 27); the cached rows agree exactly
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(logits_d),
                                   rtol=2e-5, atol=2e-5)
        for name in ("k", "v"):
            got = pa.gather_pages(paged[name][0], table[None])[0]
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(dense[name][0, 0]))


class TestPagedAttentionOp:
    def _pages(self, rs, P, page, HK, D, dtype=jnp.float32):
        return (jnp.asarray(rs.randn(P, page, HK, D), dtype),
                jnp.asarray(rs.randn(P, page, HK, D), dtype))

    def test_kernel_matches_reference_fp(self):
        rs = np.random.RandomState(0)
        P, page, HK, D, B, pp = 8, 8, 2, 16, 3, 2
        kp, vp = self._pages(rs, P, page, HK, D)
        q = jnp.asarray(rs.randn(B, 4, D), jnp.float32)
        bt = jnp.asarray(np.stack(
            [rs.choice(np.arange(1, P), pp, replace=False)
             for _ in range(B)]).astype(np.int32))
        lens = jnp.asarray([5, 9, 16], jnp.int32)
        ref = pa.paged_attention_reference(q, kp, vp, bt, lens)
        fa.set_interpret(True)
        try:
            ker = pa.paged_attention_kernel(q, kp, vp, bt, lens)
        finally:
            fa.set_interpret(False)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_kernel_matches_reference_int8_rows(self):
        """Per-row dequant scales (the cachekv-int8 tier) agree between
        the in-VMEM kernel dequant and the reference's jnp dequant."""
        rs = np.random.RandomState(1)
        P, page, HK, D, B, pp = 8, 8, 2, 16, 2, 2
        k8 = jnp.asarray(rs.randint(-127, 128, (P, page, HK, D)), jnp.int8)
        v8 = jnp.asarray(rs.randint(-127, 128, (P, page, HK, D)), jnp.int8)
        ks = jnp.asarray(rs.rand(P, page, HK) * 0.05 + 0.01, jnp.float32)
        vs = jnp.asarray(rs.rand(P, page, HK) * 0.05 + 0.01, jnp.float32)
        q = jnp.asarray(rs.randn(B, 4, D), jnp.float32)
        bt = jnp.asarray(rs.randint(1, P, (B, pp)), jnp.int32)
        lens = jnp.asarray([7, 13], jnp.int32)
        ref = pa.paged_attention_reference(q, k8, v8, bt, lens,
                                           ks_pages=ks, vs_pages=vs)
        fa.set_interpret(True)
        try:
            ker = pa.paged_attention_kernel(q, k8, v8, bt, lens,
                                            ks_pages=ks, vs_pages=vs)
        finally:
            fa.set_interpret(False)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
    @pytest.mark.parametrize("HK,rep", [(1, 4), (2, 2), (2, 8), (8, 2),
                                        (4, 1)])
    def test_kernel_matches_reference_ragged(self, monkeypatch, HK, rep,
                                             pool):
        """The kernel body (interpret mode) against the reference over
        the head groupings the engine's configurations present, with
        groups of two pages so that rows end inside a page, on a page
        boundary, inside a group and on the slot extent."""
        page, D, pp, P = 8, 16, 5, 24
        itemsize = {"f32": 4, "bf16": 2, "int8": 1}[pool]
        monkeypatch.setattr(pa, "_KV_VMEM_BUDGET",
                            2 * 4 * page * HK * D * itemsize)
        assert pa._pages_per_group(pp, page, HK, D, itemsize) == 2
        rs = np.random.RandomState(HK * 16 + rep)
        # 1, one short of a page, a page, one over, a partial last group
        # (3 pages), the slot extent, an idle row on the trash page, and
        # a row whose table ends in -1
        lens = [1, page - 1, page, page + 1, 2 * page + 4, pp * page, 1,
                page]
        B = len(lens)
        bt = np.stack([rs.choice(np.arange(1, P), pp, replace=False)
                       for _ in range(B)]).astype(np.int32)
        bt[6] = 0
        bt[7, 1:] = -1
        dt = jnp.bfloat16 if pool == "bf16" else jnp.float32
        q = jnp.asarray(rs.randn(B, HK * rep, D), dt)
        kw = {}
        if pool == "int8":
            kp, vp = (jnp.asarray(rs.randint(-127, 128, (P, page, HK, D)),
                                  jnp.int8) for _ in range(2))
            kw = dict(
                ks_pages=jnp.asarray(rs.rand(P, page, HK) * 0.05 + 0.01,
                                     jnp.float32),
                vs_pages=jnp.asarray(rs.rand(P, page, HK) * 0.05 + 0.01,
                                     jnp.float32))
        else:
            kp, vp = self._pages(rs, P, page, HK, D, dt)
        bt, lens = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
        ref = pa.paged_attention_reference(q, kp, vp, bt, lens, **kw)
        fa.set_interpret(True)
        try:
            ker = pa.paged_attention_kernel(q, kp, vp, bt, lens, **kw)
        finally:
            fa.set_interpret(False)
        # bf16: p is rounded to bf16 against another running maximum
        tol = 2e-2 if pool == "bf16" else 2e-5
        np.testing.assert_allclose(np.asarray(ker, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_mismatched_scales_raise(self):
        rs = np.random.RandomState(2)
        kp, vp = self._pages(rs, 4, 8, 2, 16)
        q = jnp.asarray(rs.randn(1, 4, 16), jnp.float32)
        bt = jnp.zeros((1, 1), jnp.int32)
        with pytest.raises(ValueError, match="together"):
            pa.paged_attention_reference(
                q, kp, vp, bt, jnp.asarray([4]),
                ks_pages=jnp.zeros((4, 8, 2)))

    def test_kernels_lower_for_tpu(self):
        """AOT Mosaic lowering guard (the round-2/3 interpret-green /
        silicon-red bug class): both paged kernels must export for the
        TPU platform with a tpu_custom_call present."""
        import jax.export
        rs = np.random.RandomState(0)
        P, page, HK, D, B, pp = 16, 64, 2, 128, 4, 4
        q = jnp.asarray(rs.randn(B, 4, D), jnp.bfloat16)
        kp = jnp.asarray(rs.randn(P, page, HK, D), jnp.bfloat16)
        vp = jnp.asarray(rs.randn(P, page, HK, D), jnp.bfloat16)
        bt = jnp.asarray(rs.randint(1, P, (B, pp)), jnp.int32)
        ln = jnp.asarray([64, 100, 256, 17], jnp.int32)
        with fa.force_compiled_lowering():
            exp = jax.export.export(
                jax.jit(lambda *a: pa.paged_attention_kernel(*a)),
                platforms=["tpu"])(q, kp, vp, bt, ln)
        assert "tpu_custom_call" in exp.mlir_module()
        k8 = jnp.asarray(rs.randint(-127, 128, (P, page, HK, D)), jnp.int8)
        ks = jnp.asarray(rs.rand(P, page, HK), jnp.float32)
        with fa.force_compiled_lowering():
            exp8 = jax.export.export(
                jax.jit(lambda q, kp, vp, bt, ln, ks, vs:
                        pa.paged_attention_kernel(
                            q, kp, vp, bt, ln, ks_pages=ks, vs_pages=vs)),
                platforms=["tpu"])(q, k8, k8, bt, ln, ks, ks)
        assert "tpu_custom_call" in exp8.mlir_module()


class TestBlockAllocator:
    def test_alloc_free_stats(self):
        a = BlockAllocator(6)                      # pages 1..5 usable
        p = a.alloc(3)
        assert p == [1, 2, 3]                      # deterministic order
        assert a.num_used == 3 and a.num_free == 2
        assert a.peak_in_use == 3
        a.free(p[:2])
        assert a.num_used == 1
        assert a.allocs_total == 3 and a.frees_total == 2
        assert 0 < a.utilization() < 1

    def test_oom_and_recovery(self):
        a = BlockAllocator(6)
        p1 = a.alloc(4)
        with pytest.raises(PoolExhausted):
            a.alloc(2)
        assert a.alloc_failures == 1
        assert a.num_used == 4                     # failed alloc leaks nothing
        a.free(p1)
        assert len(a.alloc(5)) == 5                # fully recovered

    def test_misuse_is_loud(self):
        a = BlockAllocator(4)
        p = a.alloc(1)
        with pytest.raises(ValueError, match="double free"):
            a.free(p + p)
        with pytest.raises(ValueError, match="out-of-range"):
            a.free([0])                            # trash page never freed

    def test_fragmentation_and_defrag(self):
        cfg, params = _setup()
        cache = PagedKVCache(cfg, max_batch=3, max_len=16, page_size=8)
        cache.admit(0, 16)
        cache.admit(1, 16)
        cache.admit(2, 9)
        # seed pool content so the defrag gather is observable
        rs = np.random.RandomState(0)
        cache.pool = {n: jnp.asarray(rs.randn(*v.shape), v.dtype)
                      for n, v in cache.pool.items()}
        before = {n: np.asarray(pa.gather_pages(
            v[0], jnp.asarray(cache.block_tables)))
            for n, v in cache.pool.items()}
        cache.release(0)                           # holes at the front
        assert cache.allocator.fragmentation() > 0
        tables_live = cache.block_tables[1:].copy()
        cache.defrag()
        assert cache.allocator.defrags_total == 1
        assert cache.allocator.fragmentation() == 0
        # live slots see EXACTLY the same bytes through their tables
        for n, v in cache.pool.items():
            after = np.asarray(pa.gather_pages(
                v[0], jnp.asarray(cache.block_tables)))
            np.testing.assert_array_equal(after[1:], before[n][1:])
        assert not np.array_equal(cache.block_tables[1:], tables_live)
        # compacted pages sit at the pool front; freed ones reallocate
        assert sorted(p for row in cache._slot_pages for p in row) == \
            list(range(1, 1 + cache.allocator.num_used))


class TestContinuousBatching:
    def test_admission_mid_decode_mixed_lengths(self):
        """3 requests, 2 slots: the third admits mid-decode into the
        slot a short request frees, live rows keep decoding untouched —
        every output still token-identical to its dense reference."""
        cfg, params = _setup(seed=1)
        prompts = _prompts(cfg, [3, 6, 5], seed=4)
        eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                       page_size=8, max_len=16)
        r1 = eng.submit(prompts[0], max_new_tokens=2)
        r2 = eng.submit(prompts[1], max_new_tokens=8)
        r3 = eng.submit(prompts[2], max_new_tokens=4)
        eng.step()
        assert r3.slot is None and len(eng._queue) == 1
        saw_mixed = False
        while eng.step():
            saw_mixed = saw_mixed or (r1.done and r3.slot is not None
                                      and not r2.done)
        assert saw_mixed, "r3 never ran concurrently with r2 mid-decode"
        assert r1.finish_reason == r2.finish_reason == "max_len"
        ext = eng.cache.max_len
        for r, p, new in ((r1, prompts[0], 2), (r2, prompts[1], 8),
                          (r3, prompts[2], 4)):
            np.testing.assert_array_equal(
                r.output, _dense_ref(params, p, cfg, new, ext))
        st = eng.stats()
        assert st["active_slots"] == 0
        # the prefix cache retains prompt pages past retirement (future
        # admissions share them); dropping its references empties the
        # pool and every reference taken was dropped exactly once
        assert st["num_used"] == len(eng.cache.prefix.pages())
        eng.cache.prefix.drop_all(eng.cache.allocator)
        assert eng.cache.allocator.num_used == 0
        assert eng.cache.allocator.frees_total == \
            eng.cache.allocator.allocs_total > 0

    def test_pool_backpressure_defers_admission(self):
        """A pool sized for one request at a time serializes admissions
        through PoolExhausted back-pressure instead of failing."""
        cfg, params = _setup(seed=2)
        prompts = _prompts(cfg, [6, 6], seed=5)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=16,
            num_pages=1 + 2)   # trash + one 2-page (10-token) request
        outs = eng.generate(prompts, max_new_tokens=4)
        assert eng.cache.allocator.alloc_failures > 0
        ext = eng.cache.max_len
        for out, p in zip(outs, prompts):
            np.testing.assert_array_equal(
                out, _dense_ref(params, p, cfg, 4, ext))

    def test_impossible_request_raises(self):
        cfg, params = _setup()
        eng = ContinuousBatchingEngine(params, cfg, max_batch=1,
                                       page_size=8, max_len=16)
        with pytest.raises(ValueError, match="exceeds max_len"):
            eng.submit(np.arange(1, 20, dtype=np.int32),
                       max_new_tokens=8)

    def test_eos_retires_early(self):
        cfg, params = _setup(seed=3)
        p = _prompts(cfg, [4], seed=6)[0]
        ext = 16
        ref = _dense_ref(params, p, cfg, 8, ext)
        gen = list(ref[len(p):])
        # the eos is the first generated token, after the first, that
        # no earlier step emitted: a hit at that step and not before
        hit = next(i for i in range(1, len(gen)) if gen[i] not in gen[:i])
        eos = int(gen[hit])
        eng = ContinuousBatchingEngine(params, cfg, max_batch=1,
                                       page_size=8, max_len=16,
                                       eos_token_id=eos)
        req = eng.submit(p, max_new_tokens=8)
        eng.run()
        assert req.finish_reason == "eos"
        assert req.tokens[-1] == eos and len(req.tokens) == hit + 1
        np.testing.assert_array_equal(req.output,
                                      ref[:len(p) + len(req.tokens)])

    def test_kernel_path_matches_reference_path(self):
        """use_kernel=True routes the engine's decode through the Pallas
        paged kernel (interpret mode on CPU) — greedy tokens must match
        the pure-lax reference path."""
        cfg, params = _setup(seed=4)
        prompts = _prompts(cfg, [4, 6], seed=7)
        ref_eng = ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=16,
            use_kernel=False)
        refs = ref_eng.generate(prompts, max_new_tokens=4)
        fa.set_interpret(True)
        try:
            ker_eng = ContinuousBatchingEngine(
                params, cfg, max_batch=2, page_size=8, max_len=16,
                use_kernel=True)
            kers = ker_eng.generate(prompts, max_new_tokens=4)
        finally:
            fa.set_interpret(False)
        for a, b in zip(refs, kers):
            np.testing.assert_array_equal(a, b)

    def test_serving_metrics_emitted(self):
        """The PR-1 observability hooks fire on the serving hot path:
        admission/eviction counters, occupancy histogram, block-pool
        utilization gauge."""
        from paddle_tpu import observability as obs
        cfg, params = _setup(seed=5)
        prompts = _prompts(cfg, [3, 5], seed=8)
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                           page_size=8, max_len=16)
            eng.generate(prompts, max_new_tokens=3)
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert snap["serving_admissions_total"]["values"][""] == 2
        assert snap["serving_evictions_total"]["values"][
            "reason=max_len"] == 2
        occ = snap["serving_batch_occupancy"]["values"][""]
        assert occ["count"] >= 1                   # one obs per step
        assert "serving_block_pool_utilization" in snap
        assert snap["serving_decode_steps_total"]["values"][""] >= 1

    def test_temperature_sampling_runs(self):
        cfg, params = _setup(seed=6)
        eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                       page_size=8, max_len=16,
                                       temperature=1.0,
                                       key=jax.random.key(3))
        outs = eng.generate(_prompts(cfg, [4, 4], seed=9),
                            max_new_tokens=5)
        assert all(o.shape == (9,) for o in outs)
        assert all(int(o.max()) < cfg.vocab_size for o in outs)

    def test_trash_page_isolation(self):
        """Retired slots' masked writes land on the reserved trash page
        — admitting into a recycled slot never clobbers live pages (the
        parity tests would catch corruption; this checks the invariant
        directly)."""
        cfg, params = _setup(seed=7)
        eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                       page_size=8, max_len=16)
        r1 = eng.submit(_prompts(cfg, [3], seed=10)[0], max_new_tokens=2)
        r2 = eng.submit(_prompts(cfg, [5], seed=11)[0], max_new_tokens=6)
        eng.run()
        assert r1.done and r2.done
        assert TRASH_PAGE not in [p for row in eng.cache._slot_pages
                                  for p in row]
        assert (eng.cache.block_tables == TRASH_PAGE).all()
