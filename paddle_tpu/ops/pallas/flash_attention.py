"""Pallas TPU flash attention.

TPU-native replacement for the reference's FlashAttention CUDA kernels
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu,
flash_attn_grad_kernel.cu; python surface
python/paddle/nn/functional/flash_attention.py:195).

Blockwise online-softmax forward saving per-row LSE; two-pass backward
(dkv sweep, dq sweep) recomputing probabilities from LSE — the standard
FlashAttention-2 decomposition, laid out for the MXU: 128-aligned q/k blocks,
fp32 accumulation, grid iterated sequentially so VMEM scratch carries state
across k-blocks.

Layout: (batch, seq, heads, head_dim) at the API, reshaped to
(batch*heads, seq, head_dim) for the kernels.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False  # set True to run kernels on CPU for tests


def set_interpret(v: bool):
    global _INTERPRET
    _INTERPRET = v


_FORCE_COMPILE = False   # AOT guard: lower and compile for TPU off-TPU


class force_compiled_lowering:
    """Context manager for ahead-of-time work on a host with no chip
    (tests/test_pallas_lowering.py, tests/test_v5e_aot.py): treat the
    target as a TPU, so every dispatcher picks its kernel and every
    kernel takes the COMPILED (Mosaic) path. Never use for execution —
    only lowering and compiling."""

    def __enter__(self):
        global _FORCE_COMPILE
        self._old = _FORCE_COMPILE
        _FORCE_COMPILE = True
        return self

    def __exit__(self, *exc):
        global _FORCE_COMPILE
        _FORCE_COMPILE = self._old
        return False


def on_tpu() -> bool:
    """True when programs are built for a TPU: the default backend's
    devices are TPU chips, or an AOT guard says the target is one. A
    backend that fails to initialise raises here — it never selects a
    reference path."""
    return _FORCE_COMPILE or jax.devices()[0].platform == "tpu"


def _interpret_mode() -> bool:
    """True when kernels must run in pallas interpret mode: forced by
    set_interpret, or whenever the target is not a TPU (CPU pallas
    lowering supports interpret only)."""
    if _FORCE_COMPILE:
        return False
    return _INTERPRET or not on_tpu()


def available() -> bool:
    return _INTERPRET or on_tpu()


def flash_eligible(seq_len: int, head_dim: int) -> bool:
    """The one rule for taking the flash kernel over the jnp attention
    (models/llama._attention, ring attention): kernels usable and the
    shape on the kernel's 128-row / 64-lane grid."""
    return available() and seq_len % 128 == 0 and head_dim >= 64


DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _block_iota(block_q, block_k, dim):
    return jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), dim)


def _zero_pad_rows(x, start, valid_len):
    """Zero rows >= valid_len (block-local). Out-of-bounds Pallas reads are
    undefined (NaN in interpret mode) and 0*NaN = NaN would leak through the
    matmul accumulators, so padded inputs must be zeroed at load time."""
    rows = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < valid_len, x, jnp.zeros_like(x))


# ---------------- forward ----------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # skip blocks strictly above the diagonal
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                # (block_q, d) bf16 ok:
        k = k_ref[0]                                # MXU takes bf16 inputs
        v = v_ref[0]                                # with fp32 accumulate
        if seq_k % block_k:
            k = _zero_pad_rows(k, ki * block_k, seq_k)
            v = _zero_pad_rows(v, ki * block_k, seq_k)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk) f32
        if causal:
            rows = qi * block_q + _block_iota(block_q, block_k, 0)
            cols = ki * block_k + _block_iota(block_q, block_k, 1)
            s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
        if seq_k % block_k:
            # last k-block is padded: Pallas out-of-bounds reads are
            # undefined, so mask columns >= seq_k out of the softmax
            cols = ki * block_k + _block_iota(block_q, block_k, 1)
            s = jnp.where(cols < seq_k, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[:]                            # (bq, 128)
        m_cur = jnp.max(s, axis=1, keepdims=True)    # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)              # (bq, 128)
        p = jnp.exp(s - m_new[:, :1])                # (bq, bk)
        l_new = alpha * l_ref[:] + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:] + jnp.log(jnp.where(l == 0.0, 1.0, l))
        lse_ref[0] = lse[:, :1].astype(jnp.float32)


def _fwd(q, k, v, scale, causal, block_q, block_k, out_dtype=None,
         kv_rep=1):
    """out_dtype: dtype of the normalized output (default q.dtype). The
    ring-attention partial merge passes fp32 so per-chunk partials are
    not rounded to bf16 before the cross-chunk combine.

    kv_rep: GQA — q rows are (B*H) while k/v rows are (B*H/kv_rep); the
    kv BlockSpec index map divides the grid's batch-head index, so the
    kernel reads each kv head group once with NO repeated HBM copy (same
    trick as the decode kernel in fused.py)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    grid = (bh, nq, nk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, r=kv_rep: (b // r, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, r=kv_rep: (b // r, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d),
                                 out_dtype if out_dtype is not None
                                 else q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret_mode(),
        name="flash_fwd", metadata={"kernel": "flash_fwd"},
    )(q, k, v)
    return out, lse


# ---------------- backward ----------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k, seq_q, seq_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                    # (block_q, 1)
        delta = delta_ref[0]                # (block_q, 1)
        if seq_q % block_q:
            q = _zero_pad_rows(q, qi * block_q, seq_q)
            do = _zero_pad_rows(do, qi * block_q, seq_q)
            lse = _zero_pad_rows(lse, qi * block_q, seq_q)
            delta = _zero_pad_rows(delta, qi * block_q, seq_q)
        if seq_k % block_k:
            k = _zero_pad_rows(k, ki * block_k, seq_k)
            v = _zero_pad_rows(v, ki * block_k, seq_k)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + _block_iota(block_q, block_k, 0)
            cols = ki * block_k + _block_iota(block_q, block_k, 1)
            s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)                # (bq, bk) f32
        if seq_q % block_q or seq_k % block_k:
            # padded q-rows would contaminate the dk/dv row-sums (their
            # lse/do are out-of-bounds garbage); padded k-cols only produce
            # garbage in dk/dv rows that get cropped, but zero them too so
            # inf/NaN can't leak through the accumulator
            rows = qi * block_q + _block_iota(block_q, block_k, 0)
            cols = ki * block_k + _block_iota(block_q, block_k, 1)
            p = jnp.where((rows < seq_q) & (cols < seq_k), p, 0.0)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        if seq_k % block_k:
            k = _zero_pad_rows(k, ki * block_k, seq_k)
            v = _zero_pad_rows(v, ki * block_k, seq_k)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + _block_iota(block_q, block_k, 0)
            cols = ki * block_k + _block_iota(block_q, block_k, 1)
            s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale)
        if seq_k % block_k:
            # padded k-cols would contaminate the dq column-sums
            cols = ki * block_k + _block_iota(block_q, block_k, 1)
            ds = jnp.where(cols < seq_k, ds, 0.0)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(scale, causal, block_q, block_k, block_q_bwd, block_k_bwd,
         res, g):
    q, k, v, out, lse = res
    do = g
    bh, sq, d = q.shape
    sk = k.shape[1]
    # bwd blocks tune independently of fwd (the dkv pass re-reads q/do
    # per k block and the dq pass re-reads k/v per q block — different
    # reuse patterns than the fwd)
    bq = min(block_q_bwd or block_q, sq)
    bk = min(block_k_bwd or block_k, sk)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(sk, bk)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (bh, sq, 1)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, seq_q=sq, seq_k=sk),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_interpret_mode(),
        name="flash_bwd_dkv", metadata={"kernel": "flash_bwd_dkv"},
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, seq_k=sk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_interpret_mode(),
        name="flash_bwd_dq", metadata={"kernel": "flash_bwd_dq"},
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k,
                block_q_bwd=None, block_k_bwd=None):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k,
                    block_q_bwd=None, block_k_bwd=None):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, block_q_bwd=None, block_k_bwd=None):
    """(B, S, H, D) flash attention. Raw jax arrays in/out (op-layer wraps
    it into the Tensor/autograd surface). block_q_bwd/block_k_bwd
    override the backward kernels' tiling (None = same as forward);
    the forward default is 512/1024."""
    if block_q is None:
        block_q = 512
    if block_k is None:
        block_k = 1024
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    if hk != h:  # GQA/MQA: repeat kv heads
        assert h % hk == 0
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    out = _flash_bhsd(qt, kt, vt, s, causal, block_q, block_k,
                      block_q_bwd, block_k_bwd)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
