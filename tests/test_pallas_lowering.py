"""Pallas AOT lowering guard (VERDICT r4 weak #2 / next #5).

Every Pallas kernel is lowered for the REAL TPU platform via
``jax.export(platforms=['tpu'])`` on this CPU host — no device, no
execution. This catches the interpret-passes-but-won't-lower bug class
machine-side: rms/swiglu kernels once passed in interpret mode and failed
Mosaic lowering on the chip (a lane-dim slice); nothing in the tests would
have caught it before a chip run.

The assert is twofold: export succeeds AND the module actually contains
a Mosaic custom call (``tpu_custom_call``) — a kernel that silently fell
back to the jnp reference path would otherwise pass vacuously.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused


def _lower_tpu(fn, *args, expect_mosaic=True):
    with fa.force_compiled_lowering():
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    mlir = exp.mlir_module()
    if expect_mosaic:
        assert "tpu_custom_call" in mlir, \
            "kernel lowered without a Mosaic custom call (fell back?)"
    return mlir


# small operands, real tilings
B, S, H, HK, D = 2, 1024, 4, 2, 128


def _qkv(dtype=jnp.bfloat16):
    rs = np.random.RandomState(0)
    mk = lambda *sh: jnp.asarray(rs.randn(*sh), dtype)
    return mk(B, S, H, D), mk(B, S, HK, D), mk(B, S, HK, D)


class TestFlashLowering:
    def test_fwd_lowers(self):
        q, k, v = _qkv()
        _lower_tpu(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512), q, k, v)

    def test_fwd_bwd_lowers(self):
        q, k, v = _qkv()

        def loss(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, block_q=512,
                                   block_k=512)
            return o.astype(jnp.float32).sum()
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    @pytest.mark.parametrize("bq, bk, bqb, bkb", [
        (512, 1024, None, None),     # the kernel's default
        (512, 1024, 256, 1024),
        (512, 1024, 512, 512),
        (512, 1024, 1024, 512),
        (512, 1024, 256, 512),
        (512, 1024, 1024, 1024),
        (1024, 1024, None, None),
        (512, 2048, 512, 1024),
        # backward-focused: smaller q tiles cut the dkv kernel's
        # re-streamed q traffic, larger k tiles amortize the dq pass
        (256, 1024, 256, 1024),
        (512, 512, 512, 512),
        (256, 1024, 256, 2048),
        (512, 1024, 128, 1024),
    ])
    def test_bwd_retune_blocks_lower(self, bq, bk, bqb, bkb):
        """Forward and backward tilings a retune of the flash kernel
        (ROADMAP S3) would try must lower for the chip: a tiling that
        cannot lower would waste the chip run that compares it."""
        q, k, v = _qkv()

        def loss(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, block_q_bwd=bqb,
                                   block_k_bwd=bkb)
            return o.astype(jnp.float32).sum()
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    def test_noncausal_and_gqa_lower(self):
        q, k, v = _qkv()
        _lower_tpu(lambda q, k, v: fa.flash_attention(q, k, v), q, k, v)


class TestFusedLowering:
    def test_rms_norm_fwd_bwd(self):
        x = jnp.ones((256, 1024), jnp.bfloat16)
        w = jnp.ones((1024,), jnp.bfloat16)
        _lower_tpu(fused.rms_norm, x, w)
        _lower_tpu(jax.grad(
            lambda x, w: fused.rms_norm(x, w).astype(jnp.float32).sum(),
            argnums=(0, 1)), x, w)

    def test_rms_norm_residual(self):
        x = jnp.ones((256, 1024), jnp.bfloat16)
        w = jnp.ones((1024,), jnp.bfloat16)
        r = jnp.ones((256, 1024), jnp.bfloat16)
        _lower_tpu(lambda x, w, r: fused.rms_norm(x, w, residual=r),
                   x, w, r)

    def test_swiglu_fwd_bwd(self):
        g = jnp.ones((256, 1024), jnp.bfloat16)
        u = jnp.ones((256, 1024), jnp.bfloat16)
        _lower_tpu(fused.swiglu, g, u)
        _lower_tpu(jax.grad(
            lambda g, u: fused.swiglu(g, u).astype(jnp.float32).sum(),
            argnums=(0, 1)), g, u)

    def test_rope_fwd_bwd(self):
        q = jnp.ones((B, S, H, D), jnp.bfloat16)
        k = jnp.ones((B, S, HK, D), jnp.bfloat16)
        cos = jnp.ones((S, D // 2), jnp.float32)
        sin = jnp.ones((S, D // 2), jnp.float32)
        _lower_tpu(fused.rope_qk, q, k, cos, sin)

        def loss(q, k):
            qo, ko = fused.rope_qk(q, k, cos, sin)
            return (qo.astype(jnp.float32).sum()
                    + ko.astype(jnp.float32).sum())
        _lower_tpu(jax.grad(loss, argnums=(0, 1)), q, k)


class TestDecodeLowering:
    def test_contiguous_decode(self):
        q = jnp.ones((B, H, D), jnp.bfloat16)
        kc = jnp.ones((B, S, HK, D), jnp.bfloat16)
        vc = jnp.ones((B, S, HK, D), jnp.bfloat16)
        ln = jnp.full((B,), 17, jnp.int32)
        _lower_tpu(lambda q, kc, vc, ln: fused.decode_attention(
            q, kc, vc, ln), q, kc, vc, ln)

    def test_contiguous_decode_int8_kv(self):
        q = jnp.ones((B, H, D), jnp.bfloat16)
        kc = jnp.ones((B, S, HK, D), jnp.int8)
        vc = jnp.ones((B, S, HK, D), jnp.int8)
        ks = jnp.ones((B, S, HK), jnp.float32)
        vs = jnp.ones((B, S, HK), jnp.float32)
        ln = jnp.full((B,), 17, jnp.int32)
        _lower_tpu(lambda q, kc, vc, ks, vs, ln: fused.decode_attention(
            q, kc, vc, ln, k_dequant_rows=ks, v_dequant_rows=vs),
            q, kc, vc, ks, vs, ln)

    def test_paged_decode(self):
        page, npages, ppseq = 128, 16, 4
        q = jnp.ones((B, H, D), jnp.bfloat16)
        kp = jnp.ones((npages, HK, page, D), jnp.bfloat16)
        vp = jnp.ones((npages, HK, page, D), jnp.bfloat16)
        bt = jnp.zeros((B, ppseq), jnp.int32)
        ln = jnp.full((B,), 100, jnp.int32)
        _lower_tpu(lambda q, kp, vp, bt, ln: fused.paged_decode_attention(
            q, kp, vp, bt, ln), q, kp, vp, bt, ln)

    def test_paged_decode_int8(self):
        page, npages, ppseq = 128, 16, 4
        q = jnp.ones((B, H, D), jnp.bfloat16)
        kp = jnp.ones((npages, HK, page, D), jnp.int8)
        vp = jnp.ones((npages, HK, page, D), jnp.int8)
        ks = jnp.ones((HK,), jnp.float32)
        vs = jnp.ones((HK,), jnp.float32)
        bt = jnp.zeros((B, ppseq), jnp.int32)
        ln = jnp.full((B,), 100, jnp.int32)
        _lower_tpu(
            lambda q, kp, vp, bt, ln, ks, vs: fused.paged_decode_attention(
                q, kp, vp, bt, ln, k_dequant_scale=ks, v_dequant_scale=vs),
            q, kp, vp, bt, ln, ks, vs)
