"""Low-bit decode tiers + fused Pallas serving kernels (ISSUE 11).

The acceptance gates:

- **Tier-vs-tier identity**: the FUSED serving path (in-VMEM q-RoPE +
  KV dequant decode kernel, flash chunk attention behind chunked
  prefill and spec verify, the fused page move) is TOKEN-IDENTICAL to
  the unfused path AT EVERY TIER — fused-fp vs unfused-fp, fused-int8
  vs unfused-int8, fused-int4 vs unfused-int4, fused-w8kv8 vs
  unfused-w8kv8 — single-chip and under ``shard_map`` on the tp mesh
  (tp=2 head-sharded KV, tp=4 GQA-replicated). Off-TPU the fused
  REFERENCE path is additionally BIT-identical by construction; the
  kernels themselves run in interpret mode here (the paged_attention
  fallback pattern), so the real kernel bodies are exercised under
  ``JAX_PLATFORMS=cpu``.
- **Low-bit end-to-end**: int4 weights and w8/kv8 run the whole paged
  tower — plain decode, chunked prefill, prefix-cache resume and
  speculative verify (the preempt→swap→resume leg lives in
  tests/test_host_tier.py with the compilation-cache ordering guard).
- **Partition rules**: int4 per-group quant scales shard under
  SERVING_TP_RULES exactly like the matrices they scale, including the
  GQA kv-replication expand.
- **Fused page move**: the one donated gather+scatter program is
  byte-identical to the host-staged export→import pair, and the
  in-place defrag built on it preserves every live page's bytes.

Runs on 8 virtual host-platform devices (conftest forces
``--xla_force_host_platform_device_count=8``).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import llama, generate
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.distributed.mesh import serving_mesh
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import serving_fused as sf

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(0), _CFG)
_TIERS = {          # tier name -> (weight_bits, kv_cache_dtype)
    "fp": (None, None),
    "int8kv": (None, "int8"),
    "int4": (4, None),
    "w8kv8": (8, "int8"),
}
_REF = {}           # (scenario, tier) -> cached unfused single-chip ref


def _prompts(lens, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, _CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _engine(tier, tp=None, **kw):
    wb, kv = _TIERS[tier]
    mesh = serving_mesh(tp) if tp else None
    eng_kw = dict(max_batch=2, page_size=8, max_len=32,
                  weight_bits=wb, kv_cache_dtype=kv, mesh=mesh)
    eng_kw.update(kw)
    return ContinuousBatchingEngine(_PARAMS, _CFG, **eng_kw)


def _run(tier, prompts, new=6, **kw):
    return [np.asarray(o) for o in _engine(tier, **kw).generate(
        prompts, max_new_tokens=new)]


def _ref(scenario, tier, make):
    key = (scenario, tier)
    if key not in _REF:
        _REF[key] = make()
    return _REF[key]


_MIX = _prompts([4, 7])


def _mix_ref(tier):
    return _ref("mix", tier, lambda: _run(tier, _MIX))


# ---------------- op-level kernel gates ----------------
class TestFusedDecodeOp:
    def _paged(self, quant, seed=0):
        rs = np.random.RandomState(seed)
        B, H, D, P, page, HK, pp = 3, 4, 16, 9, 8, 2, 4
        q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
        if quant:
            kp = jnp.asarray(rs.randint(-127, 128, (P, page, HK, D)),
                             jnp.int8)
            scl = jnp.asarray(rs.rand(P, page, HK), jnp.float32)
        else:
            kp = jnp.asarray(rs.randn(P, page, HK, D), jnp.float32)
            scl = None
        bt = jnp.asarray(rs.randint(1, P, (B, pp)), jnp.int32)
        ln = jnp.asarray([5, 17, 30], jnp.int32)
        cos, sin = llama.rope_tables(64, D, _CFG.rope_theta)
        rot = generate._rope_rows(q[:, None], cos, sin,
                                  (ln - 1)[:, None])[:, 0]
        return (q, rot, cos[ln - 1], sin[ln - 1], kp, bt, ln,
                dict(ks_pages=scl, vs_pages=scl) if quant else {})

    @pytest.mark.parametrize("quant", [False, True])
    def test_reference_bit_identical_to_unfused(self, quant):
        """The fused op's CPU reference — rotation + the unfused
        reference attention — is BIT-identical to rotating with
        ``_rope_rows`` and calling the unfused reference: the fused=on
        engine default off-TPU changes NOTHING."""
        q, rot, cr, sr, kp, bt, ln, kwq = self._paged(quant)
        a = pa.paged_attention_reference(rot, kp, kp, bt, ln, **kwq)
        b = sf.fused_paged_decode_reference(q, cr, sr, kp, kp, bt, ln,
                                            **kwq)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("quant", [False, True])
    def test_kernel_matches_unfused_kernel(self, quant):
        """The fused kernel (interpret mode — the real kernel body)
        reproduces the unfused paged kernel's output to the unfused
        kernel's stated bound (rtol = atol = 2e-5): the two block the
        keys differently (one page of one kv head against a group of
        pages with all heads), so their f32 sums run in another order,
        and the in-kernel rotation contracts to fma."""
        q, rot, cr, sr, kp, bt, ln, kwq = self._paged(quant)
        fa.set_interpret(True)
        try:
            a = pa.paged_attention_kernel(rot, kp, kp, bt, ln, **kwq)
            b = sf.fused_paged_decode_kernel(q, cr, sr, kp, kp, bt, ln,
                                             **kwq)
        finally:
            fa.set_interpret(False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


class TestFlashChunkOp:
    def _chunk(self, quant, B=3, T=4, W=24, seed=0):
        rs = np.random.RandomState(seed)
        H, D, HK = 4, 16, 2
        q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
        if quant:
            ck = jnp.asarray(rs.randint(-127, 128, (B, W, HK, D)),
                             jnp.int8)
            rows = jnp.asarray(rs.rand(B, W, HK), jnp.float32)
            kwq = dict(k_rows=rows, v_rows=rows)
        else:
            ck = jnp.asarray(rs.randn(B, W, HK, D), jnp.float32)
            kwq = {}
        kst = jnp.asarray(rs.randint(0, W - T, (B,)), jnp.int32)
        return q, ck, W, kst, kwq

    @pytest.mark.parametrize("quant", [False, True])
    def test_reference_bit_identical_to_attn_with_cache(self, quant):
        """The flash chunk reference is op-for-op the unfused
        ``_attn_with_cache`` composition — the CPU serving path with
        fused=True is bit-identical to fused=False."""
        q, ck, W, kst, kwq = self._chunk(quant)
        a = generate._attn_with_cache(
            q, ck, ck, W, q.shape[2], kstart=kst,
            k_rows=kwq.get("k_rows"), v_rows=kwq.get("v_rows"))
        b = sf.flash_chunk_attention_reference(q, ck, ck, W, kst, **kwq)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("quant", [False, True])
    def test_kernel_matches_reference(self, quant):
        """The flash kernel (interpret) reproduces the reference within
        online-softmax reassociation: per-row kstart + per-query causal
        masks agree on every valid row."""
        q, ck, W, kst, kwq = self._chunk(quant)
        r = sf.flash_chunk_attention_reference(q, ck, ck, W, kst, **kwq)
        fa.set_interpret(True)
        try:
            k = sf.flash_chunk_attention_kernel(q, ck, ck, W, kst, **kwq)
        finally:
            fa.set_interpret(False)
        np.testing.assert_allclose(np.asarray(r), np.asarray(k),
                                   atol=2e-4 if quant else 2e-6)

    def test_passed_together_validation(self):
        q, ck, W, kst, _ = self._chunk(False)
        with pytest.raises(ValueError, match="together"):
            sf.flash_chunk_attention_reference(
                q, ck, ck, W, kst, k_rows=jnp.ones((3, 24, 2)))


# ---------------- engine-level tier-vs-tier gates ----------------
class TestFusedEngineParity:
    """ACCEPTANCE: fused engine == unfused engine, token for token, at
    every tier — plain decode, chunked prefill and the kernel-forced
    (interpret) path."""

    @pytest.mark.parametrize("tier", list(_TIERS))
    def test_fused_matches_unfused(self, tier):
        ref = _mix_ref(tier)
        out = _run(tier, _MIX, fused=True)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        # chunked continuation prefill (ctx_cap > 0 legs of the flash
        # chunk kernel) through the same fused engine, same gate
        out = _run(tier, _MIX, fused=True, prefill_chunk=8)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("tier", ["int4"])
    def test_fused_kernels_interpret(self, tier):
        """use_kernel=True + interpret: the REAL fused kernel bodies
        (rope+attention decode, flash chunk) inside the engine's jitted
        step programs, still token-identical to the unfused jnp
        engine."""
        ref = _ref("kernel", tier,
                   lambda: _run(tier, _prompts([4], seed=5), new=4))
        fa.set_interpret(True)
        try:
            out = _run(tier, _prompts([4], seed=5), new=4, fused=True,
                       use_kernel=True, prefill_chunk=8, max_batch=1)
        finally:
            fa.set_interpret(False)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)


class TestLowbitTpParity:
    """ACCEPTANCE: int4 and w8/kv8 on the tp mesh — tp=2 shards the kv
    heads (and every per-group scale), tp=4 takes the GQA replication
    path (nkv=2 < tp: `_expand_kv_heads` runs on the int4 scales) —
    bit-identical to single-chip, fused and unfused."""

    @pytest.mark.parametrize("tier", ["int4", "w8kv8"])
    @pytest.mark.parametrize("tp", [2, 4])
    def test_tp_matches_single_chip(self, tp, tier):
        ref = _mix_ref(tier)
        # int4 runs BOTH legs (unfused-tp-lowbit is itself new
        # machinery); w8kv8 runs the fused leg — its unfused sharded
        # int8 path is PR 7 coverage and the fused leg subsumes the
        # tier-vs-tier gate
        for fused in ((False, True) if tier == "int4" else (True,)):
            out = _run(tier, _MIX, tp=tp, fused=fused)
            for a, b in zip(ref, out):
                np.testing.assert_array_equal(a, b)


class TestLowbitScenarios:
    @pytest.mark.parametrize("tier", ["int4", "w8kv8"])
    def test_prefix_resume_parity(self, tier):
        """A second admission sharing a system prompt maps the trie's
        pages (prefix HIT — counted) and still emits exactly the
        no-cache tokens, at the low-bit tiers, fused on."""
        rs = np.random.RandomState(9)
        sys_p = rs.randint(3, _CFG.vocab_size, (8,)).astype(np.int32)
        tails = [rs.randint(3, _CFG.vocab_size, (3,)).astype(np.int32)
                 for _ in range(2)]
        prompts = [np.concatenate([sys_p, t]) for t in tails]
        ref = _ref("prefix-" + tier, tier, lambda: _run(
            tier, prompts, enable_prefix_cache=False))
        eng = _engine(tier, fused=True, prefill_chunk=8)
        a = eng.generate([prompts[0]], max_new_tokens=6)
        shared, _ = eng.cache.prefix.match(prompts[1])
        assert shared, "second admission should prefix-HIT"
        b = eng.generate([prompts[1]], max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(a[0]), ref[0])
        np.testing.assert_array_equal(np.asarray(b[0]), ref[1])

    @pytest.mark.parametrize("tier", ["int4", "w8kv8"])
    def test_preempt_resume_replay_parity(self, tier):
        """Preempt→evict→resume (the PR 4 replay path) on the low-bit
        tiers: the victim finishes token-identical to an uninterrupted
        run, fused on."""
        from paddle_tpu.serving import Priority, ServingScheduler
        ref = _ref("resume-" + tier, tier, lambda: _run(
            tier, [_prompts([6], seed=2)[0]], new=8, max_batch=1))
        eng = _engine(tier, fused=True, max_batch=1)
        sched = ServingScheduler(eng)
        a = sched.submit(_prompts([6], seed=2)[0], max_new_tokens=8,
                         priority=Priority.LOW)
        while len(a.tokens) < 3:
            sched.step()
        sched.submit(_prompts([4], seed=3)[0], max_new_tokens=2,
                     priority=Priority.HIGH)
        sched.step()
        assert a.preemptions == 1
        sched.run()
        np.testing.assert_array_equal(np.asarray(a.output), ref[0])

    # int4 stays the tier-1 representative; the w8kv8 sweep is a
    # slow variant (ISSUE 13 watchdog-headroom satellite)
    @pytest.mark.parametrize("tier", [
        "int4", pytest.param("w8kv8", marks=pytest.mark.slow)])
    def test_spec_verify_parity(self, tier):
        """Speculative decoding (n-gram draft + fused verify forward)
        commits exactly the plain-decode tokens at the low-bit
        tiers."""
        rs = np.random.RandomState(7)
        motif = rs.randint(3, _CFG.vocab_size, (4,)).astype(np.int32)
        prompts = [np.concatenate([
            rs.randint(3, _CFG.vocab_size, (1,)).astype(np.int32),
            np.tile(motif, 3)]) for _ in range(2)]
        ref = _ref("spec-" + tier, tier,
                   lambda: _run(tier, prompts, new=8))
        out = [np.asarray(o) for o in _engine(
            tier, fused=True, spec_k=3).generate(prompts,
                                                 max_new_tokens=8)]
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)


# ---------------- partition rules for int4 group scales ----------------
class TestInt4PartitionRules:
    def test_group_scales_shard_on_output_axis(self):
        """Per-group int4 scales (L, G, out) match the same SERVING_TP
        rule as their matrices and shard the OUTPUT axis over tp —
        rule coverage for every quantized leaf, no leaf unmatched."""
        from jax.sharding import PartitionSpec as P
        q4 = generate.quantize_weights(_PARAMS, _CFG, bits=4)
        specs = llama.match_partition_rules(q4)
        lay = specs["layers"]
        for nm in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
            assert lay[nm] == P(None, None, "tp")
            assert lay[nm + "_scale"] == P(None, None, "tp"), nm
        assert specs["lm_head"] == P(None, "tp")
        assert specs["lm_head_scale"] == P(None, "tp")

    def test_gqa_replication_expands_int4_scales(self):
        """shard_serving_params at tp=4 (nkv=2 < tp) expands wk/wv AND
        their per-group int4 scales to one kv head per shard; per-shard
        slices reproduce the dense dequant exactly (the tp4 engine
        parity above is the end-to-end version of this gate)."""
        q4 = generate.quantize_weights(_PARAMS, _CFG, bits=4)
        mesh = serving_mesh(4)
        placed, specs = llama.shard_serving_params(q4, _CFG, mesh)
        hd = _CFG.hd
        # head extent expanded 2 -> 4 kv heads, scales alongside
        assert placed["layers"]["wk"].shape[-1] == 4 * hd
        assert placed["layers"]["wk_scale"].shape[-1] == 4 * hd
        assert str(placed["layers"]["wk"].dtype) == "int4"
        ex = llama._expand_kv_heads(q4["layers"]["wk_scale"], hd, 2)
        np.testing.assert_array_equal(
            np.asarray(placed["layers"]["wk_scale"]), np.asarray(ex))

    def test_engine_quantizes_and_reports(self):
        """weight_bits=4 on the engine equals passing a pre-quantized
        tree, and the stats surface the tier."""
        pre = generate.quantize_weights(_PARAMS, _CFG, bits=4)
        a = ContinuousBatchingEngine(_PARAMS, _CFG, max_batch=1,
                                     page_size=8, max_len=32,
                                     weight_bits=4)
        b = ContinuousBatchingEngine(pre, _CFG, max_batch=1,
                                     page_size=8, max_len=32)
        pa_, pb = _prompts([5], seed=11), _prompts([5], seed=11)
        oa = a.generate(pa_, max_new_tokens=5)
        ob = b.generate(pb, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(oa[0]),
                                      np.asarray(ob[0]))
        assert a.stats()["weight_bits"] == 4


# ---------------- fused page move ----------------
class TestFusedPageMove:
    def _filled_engine(self, tier="fp"):
        eng = _engine(tier, enable_prefix_cache=False)
        req = eng.submit(_prompts([6], seed=13)[0], max_new_tokens=4)
        while req.slot is None or req.slot in eng._pending:
            eng.step()
        return eng, req

    def test_direct_import_bytes_match_host_staged(self):
        """import_request_direct (the fused device-to-device move) puts
        EXACTLY the bytes in the destination pages that the host-staged
        export→import pair would — the handoff byte-identity gate on
        the fused path."""
        src, req = self._filled_engine()
        payload = src.export_prefilled(req)
        for tier_dst, direct in (("fp", False), ("fp", True)):
            dst = _engine(tier_dst, enable_prefix_cache=False)
            ok = dst.import_prefilled(req, payload,
                                      src_engine=src if direct else None)
            assert ok
            k = dst.cache.pages_for(payload["length"])
            pages = dst.cache._slot_pages[req.slot][:k]
            got = {n: np.asarray(a[:, pages])
                   for n, a in dst.cache.pool.items()}
            spages = src.cache._slot_pages[payload["slot"]][:k]
            want = {n: np.asarray(a[:, spages])
                    for n, a in src.cache.pool.items()}
            for n in want:
                np.testing.assert_array_equal(got[n], want[n])
            req.slot = None     # detach for the next import

    def test_direct_import_validates_geometry(self):
        src, req = self._filled_engine()
        dst = _engine("int8kv", enable_prefix_cache=False)
        with pytest.raises(ValueError, match="kv-dtype"):
            dst.cache.import_request_direct(0, src.cache, req.slot, 16)
        dst2 = ContinuousBatchingEngine(_PARAMS, _CFG, max_batch=2,
                                        page_size=16, max_len=32)
        with pytest.raises(ValueError, match="page_size"):
            dst2.cache.import_request_direct(0, src.cache, req.slot, 16)

    def test_cluster_direct_handoff_token_identical(self):
        """A disaggregated cluster with direct_handoff=True (fused
        device-to-device page moves) emits exactly the host-staged
        cluster's tokens — and actually hands off."""
        from paddle_tpu.serving.cluster import ServingCluster

        def factory():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=2, page_size=8, max_len=32)

        prompts = _prompts([6, 6, 5, 5], seed=17)

        def run(direct):
            cl = ServingCluster(factory, replicas=2, prefill_replicas=1,
                                direct_handoff=direct)
            hs = [cl.submit(p, max_new_tokens=6, tenant=f"t{i}")
                  for i, p in enumerate(prompts)]
            while cl.step():
                pass
            assert cl.handoffs_total > 0
            return [np.asarray(h.output) for h in hs]

        for a, b in zip(run(False), run(True)):
            np.testing.assert_array_equal(a, b)

    def test_defrag_inplace_preserves_live_bytes(self):
        """The in-place fused-move defrag: a retired front request
        leaves a hole, compaction MOVES the survivor's pages down,
        their bytes survive at the remapped ids and decode finishes
        token-identically to a never-defragged run."""
        ps = _prompts([4, 6], seed=19)

        def run(defrag):
            eng = _engine("fp", enable_prefix_cache=False)
            short = eng.submit(ps[0], max_new_tokens=2)   # front pages
            long = eng.submit(ps[1], max_new_tokens=10)
            while not short.done:
                eng.step()
            if defrag:
                sp = eng.cache._slot_pages[long.slot]
                before = {n: np.asarray(a[:, sp])
                          for n, a in eng.cache.pool.items()}
                eng.cache.defrag()
                np2 = eng.cache._slot_pages[long.slot]
                assert np2 != sp, "compaction should move the survivor"
                after = {n: np.asarray(a[:, np2])
                         for n, a in eng.cache.pool.items()}
                for n in before:
                    np.testing.assert_array_equal(before[n], after[n])
            eng.run()
            return np.asarray(long.output)

        np.testing.assert_array_equal(run(False), run(True))


# ---------------- telemetry ----------------
class TestFusedObservability:
    def test_serving_fused_metrics_emitted(self):
        """serving_fused_* family: trace-time dispatch + bytes-saved
        counters and the host-timed per-kernel latency histogram all
        land in the registry during a fused run (incl. a defrag's
        pool_move)."""
        from paddle_tpu import observability as obs
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng = _engine("int4", fused=True, prefill_chunk=8,
                          enable_prefix_cache=False)
            eng.generate(_prompts([5], seed=23), max_new_tokens=4)
            eng.cache.defrag()
            snap = {m.name for m in obs.REGISTRY.collect()}
            disp = obs.REGISTRY.get("serving_fused_dispatch_total")
            kernels = {lbl[0] for lbl, _ in disp.children()}
        finally:
            obs.disable()
            obs.REGISTRY.clear()
        assert "serving_fused_dispatch_total" in snap
        assert "serving_fused_bytes_saved_total" in snap
        assert "serving_fused_bytes_saved" in snap
        assert "serving_fused_step_ms" in snap
        assert "decode_rope_attn" in kernels
        assert "chunk_flash_attn" in kernels
