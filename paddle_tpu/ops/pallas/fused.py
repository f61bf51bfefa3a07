"""Pallas TPU fused kernels: RMSNorm(+residual), SwiGLU, RoPE, and
decode-time block attention.

TPU-native counterparts of the reference's fused GPU kernels
(reference: paddle/phi/kernels/fusion/fused_layernorm_kernel.cu,
fused_bias_act_kernel.cu, fused_rope_kernel.cu,
block_multi_head_attention_kernel.cu). Each is a single HBM pass with fp32
on-chip math and a hand-written VJP, so the backward is also one fused
pass instead of XLA's recomputed chain.

All kernels run in interpret mode on CPU for tests (``set_interpret``) and
on real TPU otherwise; ``available()`` mirrors flash_attention's gate.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import available, set_interpret  # shared gate
from . import flash_attention as _fa


def _interp():
    return _fa._interpret_mode()


# ---------------- fused RMSNorm (+ residual) ----------------
def _rms_fwd_kernel(x_ref, w_ref, o_ref, r_ref, *, eps, has_res):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)
    r_ref[...] = rstd.astype(jnp.float32)


def _rms_norm_fwd(x, w, eps, block_rows):
    n, h = x.shape
    br = min(block_rows, n)
    grid = (pl.cdiv(n, br),)
    out, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps, has_res=False),
        grid=grid,
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x.dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=_interp(),
    )(x, w)
    return out, rstd


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dwp_ref, *, eps,
                    n_rows, block_rows):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]
    if n_rows % block_rows:
        # zero padded rows: their garbage would leak into the dw row-sum
        i = pl.program_id(0)
        rows = i * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, x.shape, 0)
        x = jnp.where(rows < n_rows, x, 0.0)
        g = jnp.where(rows < n_rows, g, 0.0)
        rstd = jnp.where(rows[:, :1] < n_rows, rstd, 0.0)
    xhat = x * rstd
    wg = g * w
    # dx = rstd * (wg - xhat * mean(wg * xhat))
    m = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (wg - xhat * m)).astype(dx_ref.dtype)
    # per-block dw partial, padded to an (8, h) tile: Mosaic requires the
    # second-to-last block dim divisible by 8 (a (1, h) block fails to
    # lower on hardware); row 0 carries the sum, rows 1-7 are zero
    part = jnp.sum(g * xhat, axis=0, keepdims=True)          # (1, h)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, part.shape[-1]), 0)
    dwp_ref[...] = jnp.where(row == 0, part, 0.0)[None]


def _rms_norm_bwd(eps, block_rows, res, g):
    x, w, rstd = res
    n, h = x.shape
    br = min(block_rows, n)
    nb = pl.cdiv(n, br)
    dx, dwp = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps, n_rows=n,
                          block_rows=br),
        grid=(nb,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((1, 8, h), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x.dtype),
                   jax.ShapeDtypeStruct((nb, 8, h), jnp.float32)],
        interpret=_interp(),
    )(x, w, rstd, g)
    return dx, jnp.sum(dwp, axis=(0, 1)).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_2d(x, w, eps, block_rows):
    out, _ = _rms_norm_fwd(x, w, eps, block_rows)
    return out


def _rms_norm_2d_fwd(x, w, eps, block_rows):
    out, rstd = _rms_norm_fwd(x, w, eps, block_rows)
    return out, (x, w, rstd)


_rms_norm_2d.defvjp(_rms_norm_2d_fwd, _rms_norm_bwd)


def rms_norm(x, w, eps: float = 1e-6, residual=None, block_rows: int = 256):
    """Fused RMSNorm over the last dim; optional residual add fused into
    the same pass (returns (out, x+residual) then, matching the
    reference's fused_rms_norm contract)."""
    if residual is not None:
        x = x + residual  # XLA fuses this add into the kernel's HBM read
        return rms_norm(x, w, eps, block_rows=block_rows), x
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = _rms_norm_2d(x2, w, float(eps), block_rows)
    return out.reshape(shape)


# ---------------- fused SwiGLU ----------------
def _swiglu_fwd_kernel(g_ref, u_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)


def _swiglu_bwd_kernel(g_ref, u_ref, d_ref, dg_ref, du_ref):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    dsilu = sig * (1.0 + g * (1.0 - sig))
    dg_ref[...] = (d * u * dsilu).astype(dg_ref.dtype)
    du_ref[...] = (d * silu).astype(du_ref.dtype)


def _swiglu_rows(n, h, block_rows):
    """Row block sized from the row width: the backward holds five
    double-buffered (br, h) blocks plus fp32 temporaries in the 16 MiB of
    scoped VMEM, so br * h stays within 128 Ki elements (br = 32 at
    width 4096), in multiples of the bf16 sublane tile."""
    return min(block_rows, n, max(16, (131072 // h) // 16 * 16))


def _swiglu_2d(g, u, block_rows):
    n, h = g.shape
    br = _swiglu_rows(n, h, block_rows)
    return pl.pallas_call(
        _swiglu_fwd_kernel,
        grid=(pl.cdiv(n, br),),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), g.dtype),
        interpret=_interp(),
    )(g, u)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _swiglu(g, u, block_rows):
    return _swiglu_2d(g, u, block_rows)


def _swiglu_fwd_rule(g, u, block_rows):
    return _swiglu_2d(g, u, block_rows), (g, u)


def _swiglu_bwd_rule(block_rows, res, d):
    g, u = res
    n, h = g.shape
    br = _swiglu_rows(n, h, block_rows)
    dg, du = pl.pallas_call(
        _swiglu_bwd_kernel,
        grid=(pl.cdiv(n, br),),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((n, h), g.dtype),
                   jax.ShapeDtypeStruct((n, h), u.dtype)],
        interpret=_interp(),
    )(g, u, d)
    return dg, du


_swiglu.defvjp(_swiglu_fwd_rule, _swiglu_bwd_rule)


def swiglu(g, u, block_rows: int = 256):
    """Fused silu(g) * u (reference: fused_bias_act_kernel.cu swiglu path);
    one HBM pass fwd, one bwd."""
    shape = g.shape
    out = _swiglu(g.reshape(-1, shape[-1]), u.reshape(-1, shape[-1]),
                  block_rows)
    return out.reshape(shape)


# ---------------- fused RoPE (q and k in one launch) ----------------
def _rope_kernel(x1_ref, x2_ref, cos_ref, sin_ref, o1_ref, o2_ref, *,
                 sign):
    # pure elementwise on pre-split halves: Mosaic rejects both lane-dim
    # slices at `half` (gather rule) and lane-splitting in-kernel
    # reshapes ("unsupported shape cast") — round-2's packed kernel hit
    # both on real hardware while CPU interpret mode hid it. The halves
    # and the per-head table tiling are prepared outside, in XLA.
    x1 = x1_ref[...].astype(jnp.float32)
    x2 = x2_ref[...].astype(jnp.float32)
    c = cos_ref[...].astype(jnp.float32)
    s = sin_ref[...].astype(jnp.float32) * sign
    o1_ref[...] = (x1 * c - x2 * s).astype(o1_ref.dtype)
    o2_ref[...] = (x2 * c + x1 * s).astype(o2_ref.dtype)


def _rope_apply(x, cos, sin, sign, block_seq):
    """x: (B, S, H, D) -> rotated; cos/sin: (S, D/2) half tables."""
    B, S, H, D = x.shape
    bs = min(block_seq, S)
    half = D // 2
    x1 = x[..., :half].reshape(B, S, H * half)
    x2 = x[..., half:].reshape(B, S, H * half)
    ct = jnp.tile(cos, (1, H))                   # (S, H*half)
    st = jnp.tile(sin, (1, H))
    o1, o2 = pl.pallas_call(
        functools.partial(_rope_kernel, sign=sign),
        grid=(B, pl.cdiv(S, bs)),
        in_specs=[pl.BlockSpec((1, bs, H * half), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, bs, H * half), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((bs, H * half), lambda b, i: (i, 0)),
                  pl.BlockSpec((bs, H * half), lambda b, i: (i, 0))],
        out_specs=[pl.BlockSpec((1, bs, H * half),
                                lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, bs, H * half),
                                lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * half), x.dtype),
                   jax.ShapeDtypeStruct((B, S, H * half), x.dtype)],
        interpret=_interp(),
    )(x1, x2, ct, st)
    return jnp.concatenate(
        [o1.reshape(B, S, H, half), o2.reshape(B, S, H, half)], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rope_qk(q, k, cos, sin, block_seq):
    return (_rope_apply(q, cos, sin, 1.0, block_seq),
            _rope_apply(k, cos, sin, 1.0, block_seq))


def _rope_qk_fwd(q, k, cos, sin, block_seq):
    return _rope_qk(q, k, cos, sin, block_seq), (cos, sin)


def _rope_qk_bwd(block_seq, res, g):
    cos, sin = res
    dq, dk = g
    # rotation is orthogonal: the VJP is rotation by -theta
    return (_rope_apply(dq, cos, sin, -1.0, block_seq),
            _rope_apply(dk, cos, sin, -1.0, block_seq), None, None)


_rope_qk.defvjp(_rope_qk_fwd, _rope_qk_bwd)


def rope_qk(q, k, cos, sin, block_seq: int = 256):
    """Fused neox-style RoPE on q and k (reference:
    fused_rope_kernel.cu). cos/sin: (S, D/2) half tables or (S, D)
    repeated-half tables; q (B,S,H,D), k (B,S,HK,D)."""
    half = q.shape[-1] // 2
    if cos.shape[-1] == 2 * half:   # repeated-half layout: halves equal
        cos, sin = cos[:, :half], sin[:, :half]
    return _rope_qk(q, k, cos.astype(jnp.float32),
                    sin.astype(jnp.float32), block_seq)


# ---------------- decode-time block attention (KV cache) ----------------
def _decode_softmax_step(q, k, v, cache_len, o_ref, acc, m_sc, l_sc,
                         *, scale, block_k, k_scale=None, v_scale=None,
                         num_valid=None):
    """Shared online-softmax step for the decode kernels (contiguous and
    paged): one (H_rep, D) query block against one (block_k, D) K/V block
    at sequence offset ki*block_k, masked by cache_len.

    ``k_scale``/``v_scale``: optional per-row DEQUANT scalars for int8
    pages (the cachekv-int8 tier) — dequantization happens here in VMEM,
    so the HBM reads stay 1 byte/element.

    ``num_valid``: optional traced count of LIVE column blocks for this
    grid row (the ragged paged grid: ``ceil(cache_len / block_k)``).
    Blocks past it are fully masked — their contribution is an exact
    no-op (p == 0, alpha == 1) — so the step early-outs: compute is
    skipped under ``pl.when`` and the output is finalized at the row's
    OWN last live block instead of the grid extent. The caller's index
    map must clamp exhausted iterations to a previously fetched block so
    no DMA is issued for them (Ragged Paged Attention, arxiv
    2604.15464). ``None`` keeps the dense behavior: every block live,
    finalize at ``num_programs(1) - 1``."""
    ki = pl.program_id(1)
    last = (pl.num_programs(1) if num_valid is None else num_valid) - 1

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)

    def _accum():
        kk, vv = k, v
        if k_scale is not None:
            kk = (kk.astype(jnp.float32) * k_scale).astype(q.dtype)
        if v_scale is not None:
            vv = (vv.astype(jnp.float32) * v_scale).astype(q.dtype)
        # zero possibly-padded cache rows: 0 * NaN would poison p @ v
        vrows = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, vv.shape, 0)
        vv = jnp.where(vrows < cache_len, vv, jnp.zeros_like(vv))
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H_rep, bk)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < cache_len, s, _fa.DEFAULT_MASK_VALUE)
        m_prev = m_sc[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        p = jnp.where(cols < cache_len, p, 0.0)
        l_sc[...] = alpha * l_sc[...] + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
        acc[...] = acc[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    if num_valid is None:
        _accum()
    else:
        pl.when(ki <= last)(_accum)

    @pl.when(ki == last)
    def _done():
        l = l_sc[:, :1]
        o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, acc, m_sc, l_sc,
                   *, scale, block_k):
    # len_ref is the WHOLE (B*HK,) SMEM vector (Mosaic rejects rank-1
    # blocks of size 1 that aren't lane-multiples — caught by the AOT
    # lowering guard); index it by grid row
    _decode_softmax_step(q_ref[0], k_ref[0], v_ref[0],
                         len_ref[pl.program_id(0)],
                         o_ref, acc, m_sc, l_sc, scale=scale,
                         block_k=block_k)


def _decode_kernel_qrow(q_ref, k_ref, v_ref, ks_ref, vs_ref, len_ref,
                        o_ref, acc, m_sc, l_sc, *, scale, block_k):
    """int8-cache variant with PER-ROW dequant scales (each cached token
    row carries its own scale — self-calibrating, no static calibration
    pass): scales ride a (block_k, 1) VMEM block and broadcast over D."""
    _decode_softmax_step(q_ref[0], k_ref[0], v_ref[0],
                         len_ref[pl.program_id(0)],
                         o_ref, acc, m_sc, l_sc, scale=scale,
                         block_k=block_k, k_scale=ks_ref[0],
                         v_scale=vs_ref[0])


def decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                     block_k: int = 512, k_dequant_rows=None,
                     v_dequant_rows=None):
    """Single-token flash attention against a padded KV cache (reference:
    block_multi_head_attention_kernel.cu decode path).

    q: (B, H, D) the current position's query
    k_cache/v_cache: (B, S_max, HK, D); positions >= cache_len are masked
    cache_len: scalar or (B,) int32 valid-length(s)
    returns (B, H, D). GQA/MQA handled by head-group mapping, no repeat.

    ``k/v_dequant_rows`` (cachekv-int8): (B, S_max, HK) fp32 PER-ROW
    dequant scales for int8 caches — each cached token row carries its
    own scale; dequantization happens in VMEM so HBM reads stay
    1 byte/element.
    """
    B, H, D = q.shape
    S = k_cache.shape[1]
    HK = k_cache.shape[2]
    assert H % HK == 0
    rep = H // HK
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    bk = min(block_k, S)
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    if (k_dequant_rows is None) != (v_dequant_rows is None):
        raise ValueError(
            "decode_attention: k_dequant_rows and v_dequant_rows must be "
            "passed together — int8 caches quantize both K and V")
    quant = k_dequant_rows is not None

    # (B, S, HK, D) -> (B*HK, S, D); q -> (B*HK, rep, D): one grid row per
    # kv-head group so GQA costs no HBM duplication
    kt = k_cache.transpose(0, 2, 1, 3).reshape(B * HK, S, D)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(B * HK, S, D)
    qt = q.reshape(B, HK, rep, D).reshape(B * HK, rep, D)
    lens = jnp.repeat(cache_len, HK)

    in_specs = [
        pl.BlockSpec((1, rep, D), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, bk, D), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, bk, D), lambda i, j: (i, j, 0)),
    ]
    inputs = [qt, kt, vt]
    if quant:
        def rows(sc):   # (B, S, HK) -> (B*HK, S, 1)
            return jnp.asarray(sc, jnp.float32).transpose(
                0, 2, 1).reshape(B * HK, S, 1)
        in_specs += [pl.BlockSpec((1, bk, 1), lambda i, j: (i, j, 0)),
                     pl.BlockSpec((1, bk, 1), lambda i, j: (i, j, 0))]
        inputs += [rows(k_dequant_rows), rows(v_dequant_rows)]
        kernel = functools.partial(_decode_kernel_qrow, scale=s,
                                   block_k=bk)
    else:
        kernel = functools.partial(_decode_kernel, scale=s, block_k=bk)
    # whole-vector SMEM block (Mosaic rank-1 rule: block dim must equal
    # the array dim or be a lane multiple); kernels index by grid row
    in_specs.append(pl.BlockSpec(
        (B * HK,), lambda i, j: (0,),
        memory_space=pltpu.SMEM))
    inputs.append(lens)

    out = pl.pallas_call(
        kernel,
        grid=(B * HK, pl.cdiv(S, bk)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rep, D), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * HK, rep, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, D), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
        ],
        interpret=_interp(),
    )(*inputs)
    return out.reshape(B, HK, rep, D).reshape(B, H, D)


# ---------------- paged decode attention (block tables) ----------------
def _paged_decode_kernel(bt_ref, q_ref, k_ref, v_ref, len_ref, o_ref,
                         acc, m_sc, l_sc, *, scale, page):
    """Same online-softmax as _decode_kernel; k/v blocks arrive via the
    scalar-prefetched block-table index map (vLLM-style indirection), so
    the block refs carry (1, 1, page, D) with the page-pool dims leading.
    len_ref/scale refs are whole SMEM vectors indexed by grid row (the
    Mosaic rank-1 block rule — AOT lowering guard)."""
    _decode_softmax_step(q_ref[0], k_ref[0, 0], v_ref[0, 0],
                         len_ref[pl.program_id(0)],
                         o_ref, acc, m_sc, l_sc, scale=scale,
                         block_k=page)


def _paged_decode_kernel_q(bt_ref, q_ref, k_ref, v_ref, len_ref, ks_ref,
                           vs_ref, o_ref, acc, m_sc, l_sc, *, scale,
                           page):
    """int8-page variant: per-row dequant scales ride SMEM; pages stay
    1 byte/element in HBM and dequantize in VMEM."""
    i = pl.program_id(0)
    _decode_softmax_step(q_ref[0], k_ref[0, 0], v_ref[0, 0], len_ref[i],
                         o_ref, acc, m_sc, l_sc, scale=scale,
                         block_k=page, k_scale=ks_ref[i],
                         v_scale=vs_ref[i])


def paged_decode_attention(q, k_pages, v_pages, block_tables, cache_len, *,
                           scale=None, k_dequant_scale=None,
                           v_dequant_scale=None):
    """Single-token flash attention over a PAGED KV cache (reference:
    block_multi_head_attention_kernel.cu + vLLM paged attention).

    q:            (B, H, D) current queries
    k/v_pages:    (num_pages, HK, page_size, D) page pool
    block_tables: (B, pages_per_seq) int32 page ids (-1 pad allowed)
    cache_len:    scalar or (B,) valid lengths
    returns (B, H, D). The page id feeds the kernel's BlockSpec index map
    via scalar prefetch — the gather over pages happens in the memory
    pipeline, not as a materialized contiguous copy.

    ``k/v_dequant_scale`` (cachekv-int8): per-head ``(HK,)`` or
    per-sequence-per-head ``(B, HK)`` fp32 dequant scales for int8
    pages; dequantization happens inside the kernel, so HBM reads stay
    1 byte/element — the paged long-context bandwidth win.
    """
    B, H, D = q.shape
    HK, page = k_pages.shape[1], k_pages.shape[2]
    assert H % HK == 0
    rep = H // HK
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    ppseq = block_tables.shape[1]
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))

    kp = k_pages.transpose(1, 0, 2, 3)       # (HK, P, page, D)
    vp = v_pages.transpose(1, 0, 2, 3)
    qt = q.reshape(B, HK, rep, D).reshape(B * HK, rep, D)
    lens = jnp.repeat(cache_len, HK)
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)  # clamp -1
    if (k_dequant_scale is None) != (v_dequant_scale is None):
        raise ValueError(
            "paged_decode_attention: k_dequant_scale and v_dequant_scale "
            "must be passed together — int8 pages quantize both K and V")
    quant = k_dequant_scale is not None

    def _rows(sc):
        # grid row i = b*HK + h: (HK,) tiles over B; (B, HK) flattens
        sc = jnp.asarray(sc, jnp.float32)
        return (jnp.tile(sc, B) if sc.ndim == 1
                else sc.reshape(B * HK))

    in_specs = [
        pl.BlockSpec((1, rep, D), lambda i, j, bt_: (i, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda i, j, bt_: (i % HK, bt_[i // HK, j], 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda i, j, bt_: (i % HK, bt_[i // HK, j], 0, 0)),
        pl.BlockSpec((B * HK,), lambda i, j, bt_: (0,),
                     memory_space=pltpu.SMEM),
    ]
    inputs = [bt, qt, kp, vp, lens]
    if quant:
        for _ in range(2):
            in_specs.append(pl.BlockSpec(
                (B * HK,), lambda i, j, bt_: (0,),
                memory_space=pltpu.SMEM))
        inputs += [_rows(k_dequant_scale), _rows(v_dequant_scale)]
        kernel = functools.partial(_paged_decode_kernel_q, scale=s,
                                   page=page)
    else:
        kernel = functools.partial(_paged_decode_kernel, scale=s,
                                   page=page)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * HK, ppseq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rep, D), lambda i, j, bt_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, D), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * HK, rep, D), q.dtype),
        interpret=_interp(),
    )(*inputs)
    return out.reshape(B, HK, rep, D).reshape(B, H, D)
