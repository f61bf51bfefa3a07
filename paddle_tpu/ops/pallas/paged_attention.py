"""Paged decode attention over block-table-indexed KV pools.

The serving-side op of the paged KV cache (paddle_tpu/serving/): K/V
live in a global page pool ``(num_pages, page_size, nkv, hd)`` per layer
and each request owns an ordered list of page ids (its block table), so
HBM is sized by TOKENS IN FLIGHT instead of ``batch * longest_request``
(reference: block_multi_head_attention_kernel.cu; TPU-native design:
Ragged Paged Attention, arxiv 2604.15464 / vLLM block tables).

Two implementations with IDENTICAL semantics:

- :func:`paged_attention_kernel` — Pallas TPU kernel: the block table
  feeds the K/V BlockSpec index maps via scalar prefetch, so the page
  gather happens in the memory pipeline (no materialized contiguous
  copy). The grid is RAGGED: a second scalar-prefetched vector of
  per-row page counts clamps the index maps (no DMA past a row's last
  live page) and early-outs the softmax step, so a mixed-length batch
  pays ``Σ ceil(len_i/page)`` pages of attention work instead of
  ``B * ppseq``. int8 pages carry PER-ROW dequant scales (the
  cachekv-int8 tier of the dense path) and dequantize in VMEM — HBM
  reads stay 1 byte/element.
- :func:`paged_attention_reference` — pure ``lax`` gather + the exact
  attention composition of ``models/generate._attn_with_cache`` (same
  einsums, f32 accumulation, -1e30 masking), so tier-1 CPU tests
  exercise the same numerics the dense decode path produces.

:func:`paged_attention` dispatches: kernel on real TPU (or when forced
via ``use_kernel=True`` — interpret mode in tests), reference elsewhere.

TENSOR-PARALLEL serving (ISSUE 7) runs this op UNCHANGED, per shard:
inside the engine's ``shard_map`` each shard holds ``nkv/tp`` heads of
every page (``(P, page, nkv/tp, hd)`` local pools, the same page ids
everywhere) and its own ``nh/tp`` query heads. Attention softmax is
per-head, so the kernel needs NO cross-shard communication — the grid
simply has ``B * nkv/tp`` rows instead of ``B * nkv``, and the GQA
``rep = H // HK`` grouping still holds because query and kv heads shard
along the same head-group boundaries (``models/llama.
validate_serving_tp`` guarantees the divisibility; the ``nkv < tp``
replication path presents exactly one kv head per shard). Lowering of
the sharded program is gated by ``tools/aot_validate.py --config
serving-tp``.
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


def gather_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize each request's pages in logical-position order:
    pages (P, page, ...) + block_tables (B, ppseq) -> (B, ppseq*page,
    ...). Slot ``s`` of the result is logical token position ``s`` —
    the contiguous-cache view of the paged storage (reference fallback;
    the TPU kernel never materializes this copy)."""
    B, ppseq = block_tables.shape
    page = pages.shape[1]
    g = jnp.take(pages, block_tables.reshape(-1), axis=0)
    return g.reshape((B, ppseq * page) + pages.shape[2:])


def paged_attention_reference(q, k_pages, v_pages, block_tables, lengths,
                              *, scale=None, ks_pages=None, vs_pages=None):
    """Pure-lax paged decode attention (CPU tier-1 semantics anchor).

    q:            (B, H, D) single-token queries
    k/v_pages:    (P, page, HK, D) page pools
    block_tables: (B, ppseq) int32 page ids (logical-position order)
    lengths:      (B,) valid lengths INCLUDING the current token
    ks/vs_pages:  (P, page, HK) per-row dequant scales for int8 pools

    The math after the gather is kept OP-FOR-OP identical to
    ``models/generate._attn_with_cache`` so a paged decode is
    token-identical to the dense-cache decode it replaces.
    """
    B, H, D = q.shape
    ck = gather_pages(k_pages, block_tables)      # (B, S, HK, D)
    cv = gather_pages(v_pages, block_tables)
    if (ks_pages is None) != (vs_pages is None):
        raise ValueError(
            "paged_attention: ks_pages and vs_pages must be passed "
            "together — int8 pools quantize both K and V")
    if ks_pages is not None:
        k_rows = gather_pages(ks_pages, block_tables)   # (B, S, HK)
        v_rows = gather_pages(vs_pages, block_tables)
        ck = (ck.astype(jnp.float32) * k_rows[..., None]).astype(q.dtype)
        cv = (cv.astype(jnp.float32) * v_rows[..., None]).astype(q.dtype)
    nkv = ck.shape[2]
    if nkv != H:
        ck = jnp.repeat(ck, H // nkv, axis=2)
        cv = jnp.repeat(cv, H // nkv, axis=2)
    qf = q[:, None]                                # (B, 1, H, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf.astype(jnp.float32),
                   ck.astype(jnp.float32))
    # keep the default path literally `/ sqrt(hd)` — bit-parity with the
    # dense `_attn_with_cache` composition is the tier-1 gate
    s = s * scale if scale is not None else s / math.sqrt(D)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 3)
    qpos = (lengths[:, None, None, None] - 1)
    s = jnp.where(kpos <= qpos, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cv.dtype), cv)
    return o[:, 0]                                 # (B, H, D)


# ------------- Pallas RAGGED kernel (per-row-scale int8 tier) -------------
#
# The grid's column extent is the SLOT extent (ppseq pages — static
# shapes), but per-row work is LENGTH-PROPORTIONAL (Ragged Paged
# Attention, arxiv 2604.15464): a scalar-prefetched per-row page count
# drives (a) the K/V index maps, which CLAMP exhausted iterations to the
# row's last live page — the pipeline sees an unchanged block index and
# issues no new DMA — and (b) an early-out in the softmax step, which
# skips the dots and finalizes the output at the row's own last page.
# A mixed-length batch therefore streams Σ ceil(len_i/page) pages of KV
# instead of B * ppseq.

def _paged_kernel(bt_ref, cnt_ref, q_ref, k_ref, v_ref, len_ref, o_ref,
                  acc, m_sc, l_sc, *, scale, page):
    """One (rep, D) query block vs one page of K/V; pages arrive via the
    scalar-prefetched block-table index maps, so grid column j IS logical
    page j of this request (online-softmax offset j*page) while j is
    live; cnt_ref (the per-row page count) early-outs the rest. len_ref
    is the whole (B*HK,) SMEM vector (Mosaic rank-1 block rule)."""
    i = pl.program_id(0)
    _fused._decode_softmax_step(q_ref[0], k_ref[0, 0], v_ref[0, 0],
                                len_ref[i],
                                o_ref, acc, m_sc, l_sc, scale=scale,
                                block_k=page, num_valid=cnt_ref[i])


def _paged_kernel_rowq(bt_ref, cnt_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, len_ref, o_ref, acc, m_sc, l_sc, *,
                       scale, page):
    """int8-page variant: PER-ROW dequant scales ride (1, 1, page, 1)
    VMEM blocks gathered by the same block-table index map as K/V, so
    each cached token row dequantizes with its own scale in VMEM (the
    self-calibrating cachekv-int8 tier of the dense decode kernel)."""
    i = pl.program_id(0)
    _fused._decode_softmax_step(q_ref[0], k_ref[0, 0], v_ref[0, 0],
                                len_ref[i],
                                o_ref, acc, m_sc, l_sc, scale=scale,
                                block_k=page, k_scale=ks_ref[0, 0],
                                v_scale=vs_ref[0, 0],
                                num_valid=cnt_ref[i])


def paged_attention_kernel(q, k_pages, v_pages, block_tables, lengths, *,
                           scale=None, ks_pages=None, vs_pages=None):
    """Pallas ragged paged decode attention; same contract (and the same
    results, bit for bit — masked pages were exact no-ops) as
    :func:`paged_attention_reference` (pool layout (P, page, HK, D),
    per-row int8 scales (P, page, HK)), but per-row attention work is
    sized by ``ceil(length/page)`` instead of the slot extent."""
    B, H, D = q.shape
    P, page, HK = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    assert H % HK == 0
    rep = H // HK
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    ppseq = block_tables.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    # pool -> (HK, P, page, D): kv-head leads so one grid row serves a
    # whole GQA head group with no HBM duplication
    kp = k_pages.transpose(2, 0, 1, 3)
    vp = v_pages.transpose(2, 0, 1, 3)
    qt = q.reshape(B, HK, rep, D).reshape(B * HK, rep, D)
    lens = jnp.repeat(lengths, HK)
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)  # clamp -1
    # per-ROW live page counts (broadcast over the HK grid rows of each
    # request); >= 1 so every row finalizes its output block
    cnt = jnp.clip(-(-lengths // page), 1, ppseq).astype(jnp.int32)
    cnt = jnp.repeat(cnt, HK)

    if (ks_pages is None) != (vs_pages is None):
        raise ValueError(
            "paged_attention: ks_pages and vs_pages must be passed "
            "together — int8 pools quantize both K and V")
    quant = ks_pages is not None

    def _page_idx(i, j, bt_, cnt_):
        # clamp exhausted iterations to the row's LAST live page: the
        # block index is unchanged vs the previous iteration, so the
        # pipeline skips the copy — the ragged grid's DMA early-out
        return bt_[i // HK, jnp.minimum(j, cnt_[i] - 1)]

    in_specs = [
        pl.BlockSpec((1, rep, D), lambda i, j, bt_, cnt_: (i, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda i, j, bt_, cnt_:
                     (i % HK, _page_idx(i, j, bt_, cnt_), 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda i, j, bt_, cnt_:
                     (i % HK, _page_idx(i, j, bt_, cnt_), 0, 0)),
    ]
    inputs = [bt, cnt, qt, kp, vp]
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
        kernel = functools.partial(_paged_kernel_rowq, scale=s, page=page)
    else:
        kernel = functools.partial(_paged_kernel, scale=s, page=page)
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
        name="paged_attention", metadata={"kernel": "paged_attention"},
    )(*inputs)
    return out.reshape(B, HK, rep, D).reshape(B, H, D)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale=None, ks_pages=None, vs_pages=None,
                    use_kernel=None):
    """Paged decode attention: Pallas kernel on real TPU (or when forced
    — interpret mode in tests), pure-lax gather fallback elsewhere so
    tier-1 CPU runs exercise dense-decode-identical numerics."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    if use_kernel:
        return paged_attention_kernel(
            q, k_pages, v_pages, block_tables, lengths, scale=scale,
            ks_pages=ks_pages, vs_pages=vs_pages)
    return paged_attention_reference(
        q, k_pages, v_pages, block_tables, lengths, scale=scale,
        ks_pages=ks_pages, vs_pages=vs_pages)
