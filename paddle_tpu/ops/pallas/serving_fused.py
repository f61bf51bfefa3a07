"""Fused Pallas serving kernels (ISSUE 11): the decode hot loop's
remaining kernel seams collapsed into single launches.

Decode on the serving tower is HBM-bandwidth-bound (PERF.md section
5: the weights and the live KV are read once a step), so every
intermediate a step writes to HBM and re-reads is tokens/s lost.
Two fusions live here (the third — the fused page gather/scatter — is a
plain donated XLA program in ``serving/paged_cache._pool_move``):

- :func:`fused_paged_decode_attention` — a ragged paged decode kernel
  (one page of one kv head a grid step) grown to apply the query's RoPE
  ROTATION IN-KERNEL next to the existing in-VMEM int8 KV dequant: the
  unfused step materializes the rotated q to HBM and re-reads it in the
  attention kernel (plus, on the reference path, a dequanted fp copy of
  the KV); fused, q streams in unrotated with its per-row cos/sin rows
  and both the rotation and the dequant happen in VMEM — two HBM
  round-trips removed per layer per step (reference: the rope+attention
  fusion of masked_multihead_attention_kernel.cu; TPU design: Ragged
  Paged Attention, arxiv 2604.15464 + the XLA operator-fusion analysis,
  PAPERS.md).
- :func:`flash_chunk_attention` — a flash-attention kernel for the
  MULTI-TOKEN serving programs (chunked/continuation prefill AND the
  speculative verify forward), reusing flash_attention.py's online-
  softmax structure with the ragged ``kstart``/``rpos`` machinery of
  ``models/generate._attn_with_cache``: per-row first-valid-column
  masks plus per-QUERY causal positions, with int8 temp-cache rows
  dequantized in VMEM. One kernel, two consumers —
  ``paged_prefill_chunk`` and ``paged_verify_forward`` — so the two
  programs cannot drift.

Every kernel follows the paged_attention fallback pattern: a pure-lax
reference with op-for-op the math of the unfused path (bit-identical on
CPU tier-1), and the Pallas kernel runs in interpret mode off-TPU
(``set_interpret``) so parity tests exercise the real kernel body under
``JAX_PLATFORMS=cpu``. Gates: fused output is TOKEN-IDENTICAL to the
unfused path PER TIER — fused-fp vs unfused-fp, fused-int8 vs
unfused-int8, fused-int4 vs unfused-int4, fused-w8kv8 vs unfused-w8kv8
— single-chip and under ``shard_map`` on the tp mesh
(tests/test_lowbit_decode.py); Mosaic lowering is gated by
``tools/aot_validate.py --config serving-lowbit``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import available, set_interpret  # noqa: F401 — gate
from . import flash_attention as _fa
from . import fused as _fused
from . import paged_attention as _pa


def rotate_half(x: jax.Array) -> jax.Array:
    """``concat([-x2, x1])`` of the last dim's halves — the full-width
    RoPE companion operand. Computed OUTSIDE the kernel (a sign flip +
    lane permutation XLA folds into the producing matmul's epilogue):
    Mosaic rejects lane-dim slices at ``D/2`` inside a kernel (the
    ``fused._rope_kernel`` lesson), so the kernel receives ``x`` and
    ``rotate_half(x)`` and computes ``x*cos + rotate_half(x)*sin`` as
    pure full-width elementwise math. The sign flip is exact in every
    dtype and ``a + (-b)*s == a - b*s`` op-for-op in IEEE, so the
    formulation reproduces ``generate._rope_rows``'s values — up to the
    compiler's fma contraction of the mul/add pair (last-ulp), which is
    why the KERNEL path's gate is token-identity per tier (the repo's
    standing contract for every Pallas decode kernel) while the
    REFERENCE path, which uses the literal ``_rope_rows`` expression,
    is bit-identical to the unfused composition."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope_full_tables(cos_row, sin_row):
    """(B, D/2) per-row half tables -> (B, D) full-width f32 tables
    (halves repeated — the rotate_half formulation's layout)."""
    c = jnp.asarray(cos_row, jnp.float32)
    s = jnp.asarray(sin_row, jnp.float32)
    return (jnp.concatenate([c, c], axis=-1),
            jnp.concatenate([s, s], axis=-1))


def rotate_q_reference(q, cos_row, sin_row):
    """Reference q rotation — op-for-op ``generate._rope_rows`` at T=1:
    q (B, H, D), cos/sin_row (B, D/2) gathered at each row's position.
    f32 elementwise math, cast back to q's dtype."""
    x1, x2 = jnp.split(q, 2, axis=-1)
    c = jnp.asarray(cos_row, jnp.float32)[:, None, :]
    s = jnp.asarray(sin_row, jnp.float32)[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(q.dtype)


def fused_paged_decode_reference(q, cos_row, sin_row, k_pages, v_pages,
                                 block_tables, lengths, *, scale=None,
                                 ks_pages=None, vs_pages=None):
    """Pure-lax reference of the fused decode op: the exact unfused
    composition — ``_rope_rows``-identical rotation, then
    :func:`~paddle_tpu.ops.pallas.paged_attention.
    paged_attention_reference` — so the fused reference path is
    BIT-identical to the unfused reference path by construction."""
    qr = rotate_q_reference(q, cos_row, sin_row)
    return _pa.paged_attention_reference(
        qr, k_pages, v_pages, block_tables, lengths, scale=scale,
        ks_pages=ks_pages, vs_pages=vs_pages)


# --------- fused dequant + RoPE + ragged paged decode attention ---------

def _fused_paged_kernel(bt_ref, cnt_ref, q_ref, qh_ref, ct_ref, st_ref,
                        k_ref, v_ref, len_ref, o_ref, acc, m_sc, l_sc,
                        *, scale, page):
    """The ragged ``_paged_kernel`` with the q RoPE rotation fused in:
    q arrives UNROTATED with its rotate_half companion and full-width
    per-row cos/sin tables; the rotation is f32 elementwise in VMEM
    (identical values to the XLA ``_rope_rows`` it replaces), then the
    shared online-softmax step runs unchanged."""
    i = pl.program_id(0)
    qrot = (q_ref[0].astype(jnp.float32) * ct_ref[0]
            + qh_ref[0].astype(jnp.float32) * st_ref[0]).astype(
                q_ref.dtype)
    _fused._decode_softmax_step(qrot, k_ref[0, 0], v_ref[0, 0],
                                len_ref[i],
                                o_ref, acc, m_sc, l_sc, scale=scale,
                                block_k=page, num_valid=cnt_ref[i])


def _fused_paged_kernel_rowq(bt_ref, cnt_ref, q_ref, qh_ref, ct_ref,
                             st_ref, k_ref, v_ref, ks_ref, vs_ref,
                             len_ref, o_ref, acc, m_sc, l_sc, *, scale,
                             page):
    """int8-page variant: per-row dequant scales ride the same
    block-table-indexed VMEM blocks as K/V, so rotation AND dequant both
    happen in VMEM — HBM reads stay 1 byte/element and the rotated q
    never round-trips."""
    i = pl.program_id(0)
    qrot = (q_ref[0].astype(jnp.float32) * ct_ref[0]
            + qh_ref[0].astype(jnp.float32) * st_ref[0]).astype(
                q_ref.dtype)
    _fused._decode_softmax_step(qrot, k_ref[0, 0], v_ref[0, 0],
                                len_ref[i],
                                o_ref, acc, m_sc, l_sc, scale=scale,
                                block_k=page, k_scale=ks_ref[0, 0],
                                v_scale=vs_ref[0, 0],
                                num_valid=cnt_ref[i])


def fused_paged_decode_kernel(q, cos_row, sin_row, k_pages, v_pages,
                              block_tables, lengths, *, scale=None,
                              ks_pages=None, vs_pages=None):
    """Pallas fused RoPE + (dequant +) ragged paged decode attention.

    q:            (B, H, D) UNROTATED single-token queries
    cos/sin_row:  (B, D/2) rope table rows at each row's position
    k/v_pages:    (P, page, HK, D) pools; ks/vs_pages (P, page, HK)
                  per-row int8 dequant scales
    block_tables: (B, ppseq) int32; lengths: (B,) incl. current token

    The ragged ``(B*HK, ppseq)`` grid — one page of one kv head a grid
    step, the index maps clamped to a row's live pages, the online
    softmax of ``fused._decode_softmax_step`` — that the unfused
    :func:`~paddle_tpu.ops.pallas.paged_attention.
    paged_attention_kernel` had before it was re-blocked to groups of
    pages with all heads (PERF.md, PR 27); this path is off in every
    configuration the benchmark runs and keeps that grid until it is
    compared (ROADMAP D3). The addition is the in-VMEM rotation, whose
    values match the unfused XLA rotation exactly."""
    B, H, D = q.shape
    P, page, HK = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    assert H % HK == 0
    rep = H // HK
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    ppseq = block_tables.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    kp = k_pages.transpose(2, 0, 1, 3)
    vp = v_pages.transpose(2, 0, 1, 3)

    def _rows(x):   # (B, H, D) -> (B*HK, rep, D)
        return x.reshape(B, HK, rep, D).reshape(B * HK, rep, D)

    qt = _rows(q)
    qh = _rows(rotate_half(q))
    cf, sf = _rope_full_tables(cos_row, sin_row)          # (B, D) f32
    ct = _rows(jnp.broadcast_to(cf[:, None, :], (B, H, D)))
    st = _rows(jnp.broadcast_to(sf[:, None, :], (B, H, D)))
    lens = jnp.repeat(lengths, HK)
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    cnt = jnp.clip(-(-lengths // page), 1, ppseq).astype(jnp.int32)
    cnt = jnp.repeat(cnt, HK)

    if (ks_pages is None) != (vs_pages is None):
        raise ValueError(
            "fused_paged_decode: ks_pages and vs_pages must be passed "
            "together — int8 pools quantize both K and V")
    quant = ks_pages is not None

    def _page_idx(i, j, bt_, cnt_):
        # clamp exhausted iterations to the row's last live page (the
        # ragged DMA early-out, same as the unfused kernel)
        return bt_[i // HK, jnp.minimum(j, cnt_[i] - 1)]

    qspec = pl.BlockSpec((1, rep, D), lambda i, j, bt_, cnt_: (i, 0, 0))
    in_specs = [
        qspec, qspec, qspec, qspec,
        pl.BlockSpec((1, 1, page, D),
                     lambda i, j, bt_, cnt_:
                     (i % HK, _page_idx(i, j, bt_, cnt_), 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda i, j, bt_, cnt_:
                     (i % HK, _page_idx(i, j, bt_, cnt_), 0, 0)),
    ]
    inputs = [bt, cnt, qt, qh, ct, st, kp, vp]
    if quant:
        def _scl(sc):   # (P, page, HK) -> (HK, P, page, 1)
            return jnp.asarray(sc, jnp.float32).transpose(
                2, 0, 1).reshape(HK, P, page, 1)
        in_specs += [
            pl.BlockSpec((1, 1, page, 1),
                         lambda i, j, bt_, cnt_:
                         (i % HK, _page_idx(i, j, bt_, cnt_), 0, 0)),
            pl.BlockSpec((1, 1, page, 1),
                         lambda i, j, bt_, cnt_:
                         (i % HK, _page_idx(i, j, bt_, cnt_), 0, 0)),
        ]
        inputs += [_scl(ks_pages), _scl(vs_pages)]
        kernel = functools.partial(_fused_paged_kernel_rowq, scale=s,
                                   page=page)
    else:
        kernel = functools.partial(_fused_paged_kernel, scale=s,
                                   page=page)
    in_specs.append(pl.BlockSpec(
        (B * HK,), lambda i, j, bt_, cnt_: (0,),
        memory_space=pltpu.SMEM))
    inputs.append(lens)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * HK, ppseq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rep, D),
                               lambda i, j, bt_, cnt_: (i, 0, 0)),
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
        interpret=_fa._interpret_mode(),
    )(*inputs)
    return out.reshape(B, HK, rep, D).reshape(B, H, D)


def fused_paged_decode_attention(q, cos_row, sin_row, k_pages, v_pages,
                                 block_tables, lengths, *, scale=None,
                                 ks_pages=None, vs_pages=None,
                                 use_kernel=None):
    """Dispatcher (the paged_attention pattern): Pallas kernel on real
    TPU or when forced (interpret mode in tests), pure-lax reference —
    bit-identical to the unfused reference composition — elsewhere."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    if use_kernel:
        return fused_paged_decode_kernel(
            q, cos_row, sin_row, k_pages, v_pages, block_tables,
            lengths, scale=scale, ks_pages=ks_pages, vs_pages=vs_pages)
    return fused_paged_decode_reference(
        q, cos_row, sin_row, k_pages, v_pages, block_tables, lengths,
        scale=scale, ks_pages=ks_pages, vs_pages=vs_pages)


# --------- flash chunk attention (prefill chunk + spec verify) ---------

def _chunk_softmax_step(q, k, v, kstart, o_ref, acc, m_sc, l_sc, *,
                        scale, block_k, rep, qoff, seq_len,
                        k_scale=None, v_scale=None, anc=None,
                        window=None):
    """Online-softmax step for MULTI-TOKEN queries against one
    (block_k, D) cache block: query row r (= t*rep + h_rep) attends to
    columns ``kstart <= col <= qoff + t`` — the exact masks of
    ``generate._attn_with_cache`` with per-row ``kstart`` (ragged
    right-aligned context) and causal chunk positions. ``k/v_scale``:
    per-row int8 dequant scalars (dequant in VMEM). ``anc`` (ISSUE
    20): per-NODE ancestor bitmasks for TREE verify — a python list of
    T scalar int32s (SMEM reads), bit j of ``anc[t]`` set iff chunk
    node j lies on node t's root path; the intra-chunk causal triangle
    is replaced by the ancestor bit (committed columns below ``qoff``
    stay fully visible), everything else — kstart, online softmax,
    dequant — is byte-for-byte the linear path."""
    ki = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)

    kk, vv = k, v
    if k_scale is not None:
        kk = (kk.astype(jnp.float32) * k_scale).astype(q.dtype)
    if v_scale is not None:
        vv = (vv.astype(jnp.float32) * v_scale).astype(q.dtype)
    # zero possibly-garbage cache rows: 0 * NaN would poison p @ v
    vrows = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, vv.shape, 0)
    vv = jnp.where(vrows < seq_len, vv, jnp.zeros_like(vv))
    s = jax.lax.dot_general(
        q, kk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (T*rep, bk)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if anc is None:
        qpos = qoff + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // rep
        ok = (cols <= qpos) & (cols >= kstart)
        if window is not None:
            ok &= cols > qpos - window
    else:
        # tree verify: select each query row's ancestor bitmask (T is
        # small and static — an unrolled select chain, no gather), then
        # allow committed columns plus chunk columns whose bit is set
        T = len(anc)
        rowt = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep
        av = jnp.zeros(s.shape, jnp.int32)
        for t in range(T):
            av = jnp.where(rowt == t, anc[t], av)
        rel = cols - qoff                    # chunk-node column index
        bit = (av >> jnp.clip(rel, 0, 31)) & 1
        ok = (cols < qoff) | ((rel < T) & (bit == 1))
        ok = ok & (cols >= kstart)
    s = jnp.where(ok, s, _fa.DEFAULT_MASK_VALUE)
    m_prev = m_sc[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    p = jnp.where(ok, p, 0.0)
    l_sc[...] = alpha * l_sc[...] + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
    acc[...] = acc[...] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ki == last)
    def _done():
        l = l_sc[:, :1]
        o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _chunk_kernel(q_ref, k_ref, v_ref, kst_ref, o_ref, acc, m_sc, l_sc,
                  *, scale, block_k, rep, qoff, seq_len, window=None):
    _chunk_softmax_step(q_ref[0], k_ref[0], v_ref[0],
                        kst_ref[pl.program_id(0)],
                        o_ref, acc, m_sc, l_sc, scale=scale,
                        block_k=block_k, rep=rep, qoff=qoff,
                        seq_len=seq_len, window=window)


def _chunk_kernel_rowq(q_ref, k_ref, v_ref, sk_ref, sv_ref, kst_ref,
                       o_ref, acc, m_sc, l_sc, *, scale, block_k, rep,
                       qoff, seq_len, window=None):
    """int8 temp-cache variant: per-row dequant scales ride (block_k, 1)
    VMEM blocks and broadcast over D — the dequanted fp copy of the
    gathered context never reaches HBM."""
    _chunk_softmax_step(q_ref[0], k_ref[0], v_ref[0],
                        kst_ref[pl.program_id(0)],
                        o_ref, acc, m_sc, l_sc, scale=scale,
                        block_k=block_k, rep=rep, qoff=qoff,
                        seq_len=seq_len, k_scale=sk_ref[0],
                        v_scale=sv_ref[0], window=window)


def _chunk_kernel_tree(q_ref, k_ref, v_ref, kst_ref, anc_ref, o_ref,
                       acc, m_sc, l_sc, *, scale, block_k, rep, qoff,
                       seq_len, nnodes):
    i = pl.program_id(0)
    _chunk_softmax_step(q_ref[0], k_ref[0], v_ref[0], kst_ref[i],
                        o_ref, acc, m_sc, l_sc, scale=scale,
                        block_k=block_k, rep=rep, qoff=qoff,
                        seq_len=seq_len,
                        anc=[anc_ref[i, t] for t in range(nnodes)])


def _chunk_kernel_rowq_tree(q_ref, k_ref, v_ref, sk_ref, sv_ref,
                            kst_ref, anc_ref, o_ref, acc, m_sc, l_sc,
                            *, scale, block_k, rep, qoff, seq_len,
                            nnodes):
    i = pl.program_id(0)
    _chunk_softmax_step(q_ref[0], k_ref[0], v_ref[0], kst_ref[i],
                        o_ref, acc, m_sc, l_sc, scale=scale,
                        block_k=block_k, rep=rep, qoff=qoff,
                        seq_len=seq_len, k_scale=sk_ref[0],
                        v_scale=sv_ref[0],
                        anc=[anc_ref[i, t] for t in range(nnodes)])


def flash_chunk_attention_reference(q, ck, cv, length, kstart, *,
                                    scale=None, k_rows=None,
                                    v_rows=None, tree_mask=None,
                                    window=None):
    """Pure-lax reference — op-for-op the jnp composition of
    ``generate._attn_with_cache`` (same einsums, f32 accumulation,
    -1e30 masks, dequant-then-cast), so the CPU fallback is
    BIT-identical to the unfused path. ``tree_mask`` (ISSUE 20):
    optional (B, T, T) ancestor-or-self matrix replacing the
    intra-chunk causal triangle for TREE verify (committed columns
    below the chunk stay fully visible; a chain tree reproduces the
    causal mask exactly). ``window``: a sliding layer's lower bound,
    ``kpos > qpos - window``; not with a tree."""
    B, T, H, D = q.shape
    if window is not None and tree_mask is not None:
        raise ValueError("flash_chunk_attention: tree_mask with a window")
    if (k_rows is None) != (v_rows is None):
        raise ValueError(
            "flash_chunk_attention: k_rows and v_rows must be passed "
            "together — int8 caches quantize both K and V")
    if k_rows is not None:
        ck = (ck.astype(jnp.float32) * k_rows[..., None]).astype(q.dtype)
        cv = (cv.astype(jnp.float32) * v_rows[..., None]).astype(q.dtype)
    nkv = ck.shape[2]
    if nkv != H:
        ck = jnp.repeat(ck, H // nkv, axis=2)
        cv = jnp.repeat(cv, H // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   ck.astype(jnp.float32))
    s = s * scale if scale is not None else s / math.sqrt(D)
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 3)
    if tree_mask is None:
        qpos = (length - T) + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= qpos, s, -1e30)
        if window is not None:
            s = jnp.where(kpos > qpos - window, s, -1e30)
    else:
        Smax = ck.shape[1]
        allow = jnp.concatenate(
            [jnp.ones((B, T, Smax - T), bool),
             jnp.asarray(tree_mask, bool)], axis=2)
        s = jnp.where(allow[:, None], s, -1e30)
    s = jnp.where(kpos >= jnp.asarray(kstart, jnp.int32)
                  [:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(cv.dtype), cv)


def flash_chunk_attention_kernel(q, ck, cv, length, kstart, *,
                                 scale=None, k_rows=None, v_rows=None,
                                 block_k: int = 512, tree_mask=None,
                                 window=None):
    """Pallas flash attention for the multi-token serving programs.

    q:       (B, T, H, D) rotated chunk queries
    ck/cv:   (B, W, HK, D) gathered right-aligned temp cache (int8 with
             ``k_rows``/``v_rows`` (B, W, HK) per-row dequant scales)
    length:  STATIC total width (``ctx_cap + T`` — the serving chunk
             and verify programs always pass their static window)
    kstart:  (B,) traced first valid cache column per row
    returns (B, T, H, D); query row t sees columns
    ``[kstart_b, ctx_cap + t]`` — exactly the unfused masks.

    tree_mask (ISSUE 20): optional (B, T, T) bool ancestor-or-self
    matrix — the chunk lanes become token-TREE nodes and node t sees
    chunk column j only when the matrix row allows it. The matrix
    packs into per-node int32 BITMASKS riding SMEM next to ``kstart``
    (hence T <= 32 in tree mode — comb trees are shallow and narrow),
    and only the mask predicate changes inside the step.

    window: a sliding layer's (static). The key loop is bounded below
    as well as above: the cache is cut to the blocks from the one that
    holds the first query's oldest key (``qoff - window + 1``) on, and
    the mask adds ``col > qpos - window``.
    """
    B, T, H, D = q.shape
    if window is not None:
        if tree_mask is not None:
            raise ValueError(
                "flash_chunk_attention: tree_mask with a window")
        bk0 = min(block_k, ck.shape[1])
        cut = max(int(length) - T - window + 1, 0) // bk0 * bk0
        if cut:
            ck, cv = ck[:, cut:], cv[:, cut:]
            if k_rows is not None:
                k_rows, v_rows = k_rows[:, cut:], v_rows[:, cut:]
            length = int(length) - cut
            kstart = jnp.maximum(jnp.asarray(kstart, jnp.int32) - cut, 0)
    W, HK = ck.shape[1], ck.shape[2]
    assert H % HK == 0
    rep = H // HK
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    length = int(length)
    qoff = length - T
    bk = min(block_k, W)
    if (k_rows is None) != (v_rows is None):
        raise ValueError(
            "flash_chunk_attention: k_rows and v_rows must be passed "
            "together — int8 caches quantize both K and V")
    quant = k_rows is not None
    if tree_mask is not None and T > 32:
        raise ValueError(
            f"flash_chunk_attention: tree mode packs ancestor rows "
            f"into int32 bitmasks, so the tree is capped at 32 nodes "
            f"(got T={T})")

    # (B, T, H, D) -> (B*HK, T*rep, D): one grid row per kv-head group
    qt = q.reshape(B, T, HK, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B * HK, T * rep, D)
    kt = ck.transpose(0, 2, 1, 3).reshape(B * HK, W, D)
    vt = cv.transpose(0, 2, 1, 3).reshape(B * HK, W, D)
    kst = jnp.repeat(jnp.broadcast_to(
        jnp.asarray(kstart, jnp.int32), (B,)), HK)

    in_specs = [
        pl.BlockSpec((1, T * rep, D), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, bk, D), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, bk, D), lambda i, j: (i, j, 0)),
    ]
    inputs = [qt, kt, vt]
    tkw = {}
    if tree_mask is not None:
        tkw = {"nnodes": T}
        kernel_plain, kernel_quant = _chunk_kernel_tree, \
            _chunk_kernel_rowq_tree
    else:
        kernel_plain, kernel_quant = _chunk_kernel, _chunk_kernel_rowq
        if window is not None:
            tkw = {"window": window}
    if quant:
        def rows(sc):   # (B, W, HK) -> (B*HK, W, 1)
            return jnp.asarray(sc, jnp.float32).transpose(
                0, 2, 1).reshape(B * HK, W, 1)
        in_specs += [pl.BlockSpec((1, bk, 1), lambda i, j: (i, j, 0)),
                     pl.BlockSpec((1, bk, 1), lambda i, j: (i, j, 0))]
        inputs += [rows(k_rows), rows(v_rows)]
        kernel = functools.partial(kernel_quant, scale=s,
                                   block_k=bk, rep=rep, qoff=qoff,
                                   seq_len=length, **tkw)
    else:
        kernel = functools.partial(kernel_plain, scale=s, block_k=bk,
                                   rep=rep, qoff=qoff, seq_len=length,
                                   **tkw)
    in_specs.append(pl.BlockSpec(
        (B * HK,), lambda i, j: (0,),
        memory_space=pltpu.SMEM))
    inputs.append(kst)
    if tree_mask is not None:
        # per-node ancestor bitmask, repeated over kv-head groups like
        # kstart: bit j of anc[b*HK + g, t] = node j on node t's path
        bits = (jnp.asarray(tree_mask, jnp.int32)
                * (1 << jnp.arange(T, dtype=jnp.int32))[None, None, :]
                ).sum(axis=2)                             # (B, T)
        in_specs.append(pl.BlockSpec(
            (B * HK, T), lambda i, j: (0, 0),
            memory_space=pltpu.SMEM))
        inputs.append(jnp.repeat(bits, HK, axis=0))

    out = pl.pallas_call(
        kernel,
        grid=(B * HK, pl.cdiv(W, bk)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T * rep, D), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * HK, T * rep, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((T * rep, D), jnp.float32),
            pltpu.VMEM((T * rep, 128), jnp.float32),
            pltpu.VMEM((T * rep, 128), jnp.float32),
        ],
        interpret=_fa._interpret_mode(),
    )(*inputs)
    return out.reshape(B, HK, T, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, H, D)


def flash_chunk_attention(q, ck, cv, length, kstart, *, scale=None,
                          k_rows=None, v_rows=None, use_kernel=None,
                          tree_mask=None, window=None):
    """Dispatcher for the multi-token serving attention: Pallas flash
    kernel on real TPU or when forced (interpret mode in tests),
    pure-lax reference — bit-identical to the unfused
    ``_attn_with_cache`` composition — elsewhere. Consumers:
    ``paged_prefill_chunk`` (the fused PREFILL kernel) and
    ``paged_verify_forward`` (the fused VERIFY kernel, linear AND —
    via ``tree_mask`` — tree speculative)."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    if use_kernel:
        return flash_chunk_attention_kernel(
            q, ck, cv, length, kstart, scale=scale, k_rows=k_rows,
            v_rows=v_rows, tree_mask=tree_mask, window=window)
    return flash_chunk_attention_reference(
        q, ck, cv, length, kstart, scale=scale, k_rows=k_rows,
        v_rows=v_rows, tree_mask=tree_mask, window=window)
