"""Pallas TPU flash attention.

TPU-native replacement for the reference's FlashAttention CUDA kernels
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu,
flash_attn_grad_kernel.cu; python surface
python/paddle/nn/functional/flash_attention.py:195).

Blockwise online-softmax forward saving per-row LSE; two-pass backward
(dkv sweep, dq sweep) recomputing probabilities from LSE — the standard
FlashAttention-2 decomposition, laid out for the MXU: 128-aligned q/k blocks,
fp32 accumulation, grid iterated sequentially so VMEM scratch carries state
across k-blocks. Every kernel's schedule is built from where the causal
diagonal is ("the schedule", below): what lies above it is neither fetched
nor stepped, and only the steps it crosses build a mask.

Layout: (batch, seq, heads, head_dim) at the API, reshaped to
(batch*heads, seq, head_dim) for the kernels; K and V keep their own
(fewer) heads, and a query head reads its KV head through the index map.
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


def _zero_pad_rows(x, start, valid_len):
    """Zero rows >= valid_len (block-local). Out-of-bounds Pallas reads are
    undefined (NaN in interpret mode) and 0*NaN = NaN would leak through the
    matmul accumulators, so padded inputs must be zeroed at load time."""
    rows = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < valid_len, x, jnp.zeros_like(x))


# ---------------- the schedule ----------------
# Causal attention is the part of the (query, key) square under the
# diagonal, so every kernel is scheduled from where the diagonal is. A grid
# step holds one q block and a MAJOR block of K and V (forward, dq), or a
# major block of K and V and one of q and do (dkv), and loops over the key
# (q) steps inside it: steps wholly under the diagonal run a body with no
# mask, steps the diagonal or a ragged edge crosses build one, steps above
# it are never run. A major block is the whole sequence when that fits
# _RESIDENT_BYTES: K and V are then read from HBM once for a KV head's
# query heads and all their q blocks. A longer sequence is tiled, and a
# major block wholly above the diagonal is clamped by its index map to the
# last one needed, which is already in VMEM, so Pallas issues no copy.
_RESIDENT_BYTES = 2 << 20     # one operand's major block (8,192 rows of
#                               128 bf16 lanes)
# The dkv kernel holds the most: four majors and two outputs double
# buffered (24 MiB at the budget), two float32 accumulators (8) and the
# score tiles of a step (about 6); the v5e has 128 MiB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)


def _min(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _max(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _clip(x, lo, hi):
    return _max(lo, _min(x, hi))


def _key_ranges(r0, block_q, seq_k, causal, c_lo, c_hi, big, small):
    """Key steps of the q block whose first row is ``r0`` inside columns
    [c_lo, c_hi): steps [b0, b1) of width ``big`` and then [s0, s1) of
    width ``small`` lie wholly under the diagonal and inside the sequence,
    steps [s1, s2) of width ``small`` are crossed by the diagonal or by
    the sequence's end, later ones hold nothing a row of the block sees.
    Python ints or traced scalars."""
    clear = _min(r0 + 1, seq_k) if causal else seq_k
    end = _min(r0 + block_q, seq_k) if causal else seq_k
    clear, end = _clip(clear, c_lo, c_hi), _clip(end, c_lo, c_hi)
    b1 = clear // big
    return c_lo // big, b1, b1 * (big // small), clear // small, \
        pl.cdiv(end, small)


def _q_ranges(c0, block_k, seq_q, causal, r_lo, r_hi, step):
    """The mirror for the key block whose first column is ``c0``: q steps
    of height ``step`` inside rows [r_lo, r_hi). Steps [t0, min(t1, t2))
    are crossed by the diagonal, [t1, t2) lie wholly under it, and
    [max(t0, t2), t3) hold the sequence's ragged end."""
    first, clear = (c0, c0 + block_k - 1) if causal else (0, 0)
    t0 = _clip(first, r_lo, r_hi) // step
    t1 = pl.cdiv(_clip(clear, r_lo, r_hi), step)
    t2 = _clip(seq_q // step * step, r_lo, r_hi) // step
    t3 = pl.cdiv(_clip(seq_q, r_lo, r_hi), step)
    return t0, t1, t2, t3


def causal_blocks(sq, sk, block_q, block_k):
    """(interior, diagonal, skipped): how many of a head's (q block, key
    block) pairs lie wholly under the causal diagonal, are crossed by it
    (or by the end of the keys), and lie above it. The kernels run the
    first kind with no mask, the second with one, and neither fetch nor
    step the third; with blocks this size it is their static schedule."""
    nq, nk = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    interior = diagonal = 0
    for i in range(nq):
        _, _, _, s1, s2 = _key_ranges(i * block_q, block_q, sk, True, 0,
                                      nk * block_k, block_k, block_k)
        interior += s1
        diagonal += s2 - s1
    return interior, diagonal, nq * nk - interior - diagonal


def _tiling(seq, big, small, row_bytes):
    """(major, big): rows of one operand a grid step holds in VMEM, and the
    width of its large steps. A ragged sequence is tiled step by step, so
    that Pallas pads its last block; otherwise the whole sequence where
    it fits the budget, else as many whole large steps as do."""
    if seq % small:
        return small, small
    if seq * row_bytes <= _RESIDENT_BYTES:
        return seq, big
    if seq % big:
        big = small
    n = seq // big
    fit = max(1, _RESIDENT_BYTES // (big * row_bytes))
    return big * max(m for m in range(1, n + 1)
                     if n % m == 0 and m <= fit), big


def _diagonal_cut(causal, block_q, block_k):
    """Width of the key steps along the diagonal: the q block's own where
    that divides the large step (half of a step the diagonal crosses is
    masked away, so the narrower the better), else the large step."""
    return block_q if causal and block_k % block_q == 0 else block_k


def _keep(shape, q_dim, r0, c0, causal, seq_q=None, seq_k=None):
    """Mask of the scores a step keeps: under the diagonal, and inside
    the sequences where one is ragged (None: it is not). Query rows run
    along ``q_dim`` of the tile."""
    rows = r0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    cols = c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    keep = rows >= cols if causal else None
    for idx, n in ((rows, seq_q), (cols, seq_k)):
        if n is not None:
            keep = idx < n if keep is None else keep & (idx < n)
    return keep


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _loop(lo, hi, body):
    def step(i, carry):
        body(i)
        return carry
    jax.lax.fori_loop(lo, hi, step, 0)


def _for_key_steps(step, r0, block_q, seq_k, causal, c_lo, c_hi, big, small):
    """Run ``step(width, masked)``'s bodies over a q block's key steps."""
    b0, b1, s0, s1, s2 = _key_ranges(r0, block_q, seq_k, causal, c_lo, c_hi,
                                     big, small)
    _loop(b0, b1, step(big, False))
    if small != big:
        _loop(s0, s1, step(small, False))
    if causal or seq_k % small:
        _loop(s1, s2, step(small, True))


def _key_step(k_ref, v_ref, j, width, c_lo, r0, block_q, seq_k, causal,
              masked):
    """K and V rows of key step ``j`` and, in a masked step, the scores a
    q block starting at row ``r0`` keeps of it (K and V zeroed past a
    ragged end)."""
    off = pl.multiple_of(j * width - c_lo, width)
    k, v = k_ref[0, pl.ds(off, width), :], v_ref[0, pl.ds(off, width), :]
    if not masked:
        return k, v, None
    ragged = seq_k % width
    if ragged:
        k = _zero_pad_rows(k, j * width, seq_k)
        v = _zero_pad_rows(v, j * width, seq_k)
    return k, v, _keep((block_q, width), 0, r0, j * width, causal,
                       seq_k=seq_k if ragged else None)


# ---------------- forward ----------------
def _lanes(x, n):
    """A per-row statistic kept lane-replicated as (rows, 128), as (rows,
    n): whole vregs side by side where n allows, so that no step pays a
    lane broadcast for it."""
    if n % 128 == 0:
        return x if n == 128 else jnp.concatenate([x] * (n // 128), axis=1)
    if n < 128:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _softmax_update(s, v, m_ref, l_ref, acc_ref):
    """One key step of the online softmax over float32 scores ``s``. The
    row maximum is reduced over the lanes in every step (every lane of a
    row must subtract the same number); the row sum is kept as 128
    lane-partial sums and reduced once, in the kernel's last step."""
    w = s.shape[1]
    m_prev = m_ref[:]                            # (bq, 128), replicated
    m_cur = jnp.max(s, axis=1, keepdims=True)    # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)              # (bq, 128)
    p = jnp.exp(s - _lanes(m_new, w))            # (bq, w)
    if w % 128 == 0:
        l_cur = sum(p[:, c:c + 128] for c in range(0, w, 128))
    else:
        l_cur = jnp.sum(p, axis=1, keepdims=True) / 128.0
    l_ref[:] = alpha * l_ref[:] + l_cur
    acc_ref[:] = acc_ref[:] * _lanes(alpha, acc_ref.shape[1]) + _dot_nn(
        p.astype(v.dtype), v)
    m_ref[:] = m_new


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, block_d, seq_k):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    major = k_ref.shape[1]
    r0, c_lo = qi * block_q, kj * major

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                 # bf16 in, f32 accumulate

    def step(width, masked):
        def body(j):
            k, v, keep = _key_step(k_ref, v_ref, j, width, c_lo, r0, block_q,
                                   seq_k, causal, masked)
            s = _dot_nt(q, k) * scale            # (bq, width) f32
            if masked:
                s = jnp.where(keep, s, DEFAULT_MASK_VALUE)
            _softmax_update(s, v, m_ref, l_ref, acc_ref)
        return body

    _for_key_steps(step, r0, block_q, seq_k, causal, c_lo, c_lo + major,
                   block_k, block_d)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.sum(l_ref[:], axis=1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l_safe)


def _kv_map(causal, block_q, seq_k, major, kv_rep):
    """Index map of K and V under a (head, q block, key major) grid: the
    query head's KV head, and no major past the last one the q block sees."""
    def index(b, i, j):
        if causal:
            j = jnp.minimum(
                j, (jnp.minimum(i * block_q + block_q, seq_k) - 1) // major)
        return (b // kv_rep, j, 0)
    return index


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
    block_d = _diagonal_cut(causal, block_q, block_k)
    major, block_k = _tiling(sk, block_k, block_d, d * k.dtype.itemsize)
    kv_spec = pl.BlockSpec((1, major, d),
                           _kv_map(causal, block_q, sk, major, kv_rep))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, block_d=block_d,
                          seq_k=sk),
        grid=(bh, pl.cdiv(sq, block_q), pl.cdiv(sk, major)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            kv_spec, kv_spec,
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
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret_mode(),
        name="flash_fwd", metadata={"kernel": "flash_fwd"},
    )(q, k, v)
    return out, lse


# ---------------- backward ----------------
def _p_ds(a, b, c, e, lse, delta, scale, keep):
    """Probabilities and score gradients of one step, recomputed from the
    saved log-sum-exp: ``(q, k, do, v)`` give them with query rows down the
    tile (dq), ``(k, q, v, do)`` transposed (dkv, so that neither of its
    products transposes a tile); ``lse``/``delta`` broadcast either way."""
    s = _dot_nt(a, b) * scale
    if keep is not None:
        s = jnp.where(keep, s, DEFAULT_MASK_VALUE)
    p = jnp.exp(s - lse)
    ds = p * (_dot_nt(c, e) - delta) * scale
    return p, ds


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k, seq_q, seq_k):
    kj, rep_i, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    major_k, major_q = k_ref.shape[1], q_ref.shape[1]
    c_lo, r_lo = kj * major_k, qi * major_q

    @pl.when((rep_i == 0) & (qi == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def key_block(jj):
        off_k = pl.multiple_of(jj * block_k, block_k)
        c0 = c_lo + off_k
        k, v = k_ref[0, pl.ds(off_k, block_k), :], \
            v_ref[0, pl.ds(off_k, block_k), :]
        if seq_k % block_k:
            k = _zero_pad_rows(k, c0, seq_k)
            v = _zero_pad_rows(v, c0, seq_k)

        def step(masked):
            def body(i):
                off_q = pl.multiple_of(i * block_q - r_lo, block_q)
                q = q_ref[0, pl.ds(off_q, block_q), :]
                do = do_ref[0, pl.ds(off_q, block_q), :]
                keep = None
                if masked:
                    if seq_q % block_q:
                        # padded q rows are undefined: out of dk/dv's sums
                        q = _zero_pad_rows(q, i * block_q, seq_q)
                        do = _zero_pad_rows(do, i * block_q, seq_q)
                    keep = _keep((block_k, block_q), 1, i * block_q, c0,
                                 causal,
                                 seq_q=seq_q if seq_q % block_q else None)
                i_loc = i - qi * (major_q // block_q)
                p, ds = _p_ds(k, q, v, do, lse_ref[0, i_loc],
                              delta_ref[0, i_loc], scale, keep)
                sl = pl.ds(off_k, block_k)
                dv_acc[sl, :] += _dot_nn(p.astype(do.dtype), do)
                dk_acc[sl, :] += _dot_nn(ds.astype(q.dtype), q)
            return body

        t0, t1, t2, t3 = _q_ranges(c0, block_k, seq_q, causal, r_lo,
                                   r_lo + major_q, block_q)
        if causal:
            _loop(t0, _min(t1, t2), step(True))
        _loop(t1, t2, step(False))
        if seq_q % block_q:
            _loop(_max(t0, t2), t3, step(True))

    _loop(0, major_k // block_k, key_block)

    @pl.when((rep_i == pl.num_programs(2) - 1)
             & (qi == pl.num_programs(3) - 1))
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, block_d,
                   seq_k):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    major = k_ref.shape[1]
    r0, c_lo = qi * block_q, kj * major

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q, do, lse, delta = q_ref[0], do_ref[0], lse_ref[0], delta_ref[0]

    def step(width, masked):
        def body(j):
            k, v, keep = _key_step(k_ref, v_ref, j, width, c_lo, r0, block_q,
                                   seq_k, causal, masked)
            _, ds = _p_ds(q, k, do, v, lse, delta, scale, keep)
            dq_acc[:] += _dot_nn(ds.astype(k.dtype), k)
        return body

    _for_key_steps(step, r0, block_q, seq_k, causal, c_lo, c_lo + major,
                   block_k, block_d)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(scale, causal, block_q, block_k, block_q_bwd, block_k_bwd,
         res, g):
    q, k, v, out, lse = res
    do = g
    bh, sq, d = q.shape
    bhk, sk, _ = k.shape
    rep = bh // bhk
    # bwd blocks tune independently of fwd (the dkv pass re-reads q/do
    # per k block and the dq pass re-reads k/v per q block — different
    # reuse patterns than the fwd)
    bq = min(block_q_bwd or block_q, sq)
    bk = min(block_k_bwd or block_k, sk)
    bd = _diagonal_cut(causal, bq, bk)
    row_bytes = d * k.dtype.itemsize
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (bh, sq, 1)

    # dkv: key blocks as wide as the diagonal's cut; scores are computed
    # transposed, so lse and delta go in with the q rows along the lanes
    major_k, _ = _tiling(sk, bd, bd, row_bytes)
    major_q, _ = _tiling(sq, bq, bq, row_bytes)
    nq, nqm = pl.cdiv(sq, bq), pl.cdiv(sq, major_q)

    def rows(x):
        x = jnp.pad(x[..., 0], ((0, 0), (0, nq * bq - sq)))
        return x.reshape(bh, nq, 1, bq)

    def q_map(width):
        def index(g_, j, r, i):
            if causal:      # no q major before the key major's first row
                i = jnp.maximum(i, jnp.minimum(j * major_k // major_q,
                                               nqm - 1))
            return (g_ * rep + r, i) + (0,) * width
        return index

    q_spec = pl.BlockSpec((1, major_q, d), q_map(1))
    k_spec = pl.BlockSpec((1, major_k, d), lambda g_, j, r, i: (g_, j, 0))
    row_spec = pl.BlockSpec((1, major_q // bq, 1, bq), q_map(2))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bd, seq_q=sq, seq_k=sk),
        grid=(bhk, pl.cdiv(sk, major_k), rep, nqm),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhk, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((major_k, d), jnp.float32),
            pltpu.VMEM((major_k, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret_mode(),
        name="flash_bwd_dkv", metadata={"kernel": "flash_bwd_dkv"},
    )(q, k, v, do, rows(lse), rows(delta))

    major, bk = _tiling(sk, bk, bd, row_bytes)
    kv_spec = pl.BlockSpec((1, major, d), _kv_map(causal, bq, sk, major, rep))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, block_d=bd, seq_k=sk),
        grid=(bh, nq, pl.cdiv(sk, major)),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret_mode(),
        name="flash_bwd_dq", metadata={"kernel": "flash_bwd_dq"},
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k,
                block_q_bwd=None, block_k_bwd=None):
    """q is (B*H, S, D); k and v are (B*HK, S, D), a KV head serving the
    H/HK query heads that follow one another in q."""
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k,
                  kv_rep=q.shape[0] // k.shape[0])
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k,
                    block_q_bwd=None, block_k_bwd=None):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k,
                    kv_rep=q.shape[0] // k.shape[0])
    return out, (q, k, v, out, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, block_q_bwd=None, block_k_bwd=None):
    """(B, S, H, D) flash attention. Raw jax arrays in/out (op-layer wraps
    it into the Tensor/autograd surface). block_q_bwd/block_k_bwd
    override the backward kernels' tiling (None = same as forward);
    the forward default is 512/1024. GQA/MQA (fewer KV heads than query
    heads) is served through the kernels' index maps: K and V are never
    repeated, and dk/dv come back per KV head."""
    if block_q is None:
        block_q = 512
    if block_k is None:
        block_k = 1024
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    assert h % hk == 0
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    out = _flash_bhsd(qt, kt, vt, s, causal, block_q, block_k,
                      block_q_bwd, block_k_bwd)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
