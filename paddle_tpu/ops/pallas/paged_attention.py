"""Paged decode attention over block-table-indexed KV pools.

The serving-side op of the paged KV cache (paddle_tpu/serving/): K/V
live in a global page pool ``(num_pages, page_size, nkv, hd)`` per layer
and each request owns an ordered list of page ids (its block table), so
HBM is sized by TOKENS IN FLIGHT instead of ``batch * longest_request``
(reference: block_multi_head_attention_kernel.cu; TPU-native design:
Ragged Paged Attention, arxiv 2604.15464 / vLLM block tables).

Two implementations of one contract:

- :func:`paged_attention_kernel` — Pallas TPU kernel. The pools stay in
  HBM as they are stored: in ``(P, page, HK, D)`` a page with all its
  kv heads is one contiguous slab, and the kernel fetches slabs by page
  id (block table in SMEM by scalar prefetch) with async copies into a
  double-buffered VMEM scratch — no transposed or gathered copy of a
  pool exists (where XLA pads a position's HK rows to a tile, as for 12
  heads or 2 int8 heads, it re-tiles the pool into slabs first; for 8
  heads, or 1 or 2 bf16 heads, no byte moves). The grid has one step
  per request row; inside it a loop walks the row's LIVE pages in
  groups of G pages (G from the slab size and a VMEM budget: hundreds
  of KB of K and of V a group), the next group, or the next row's
  first, in flight while this one is computed. A mixed-length batch
  therefore reads and computes
  ``Σ ceil(len_i/page)`` pages, and nothing runs for a dead page. The
  row's whole ``(H, D)`` query block multiplies a slab's ``page*HK``
  key rows at once; a mask keeps each query head to the rows of its own
  kv head (GQA, MQA and MHA alike). int8 pages carry PER-ROW dequant
  scales (the cachekv-int8 tier of the dense path) that ride the same
  page ids and apply in VMEM — HBM reads of K and V stay 1
  byte/element.
- :func:`paged_attention_reference` — pure ``lax`` gather + the exact
  attention composition of ``models/generate._attn_with_cache`` (same
  einsums, f32 accumulation, -1e30 masking), so tier-1 CPU tests
  exercise the same numerics the dense decode path produces.

The kernel keeps the reference's arithmetic (scores and online-softmax
state in f32, ``p`` cast to the compute dtype for ``p @ v``) but one
softmax step spans a group of pages, so its f32 sums run in another
order: kernel and reference agree to ``rtol = atol = 2e-5`` in f32
(greedy tokens equal on the engine parity tests), not bit for bit.

:func:`paged_attention` dispatches: kernel on real TPU (or when forced
via ``use_kernel=True`` — interpret mode in tests), reference elsewhere.

TENSOR-PARALLEL serving (ISSUE 7) runs this op UNCHANGED, per shard:
inside the engine's ``shard_map`` each shard holds ``nkv/tp`` heads of
every page (``(P, page, nkv/tp, hd)`` local pools, the same page ids
everywhere) and its own ``nh/tp`` query heads. Attention softmax is
per-head, so the kernel needs NO cross-shard communication — a shard's
slabs are simply ``nkv/tp`` heads wide, and the GQA ``rep = H // HK``
grouping still holds because query and kv heads shard along the same
head-group boundaries (``models/llama.validate_serving_tp`` guarantees
the divisibility; the ``nkv < tp`` replication path presents exactly
one kv head per shard). Lowering of the sharded program is gated by
``tools/aot_validate.py --config serving-tp``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import available, set_interpret  # noqa: F401 — gate
from . import flash_attention as _fa


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
                              *, scale=None, ks_pages=None, vs_pages=None,
                              window=None):
    """Pure-lax paged decode attention (CPU tier-1 semantics anchor).

    q:            (B, H, D) single-token queries
    k/v_pages:    (P, page, HK, D) page pools
    block_tables: (B, ppseq) int32 page ids (logical-position order)
    lengths:      (B,) valid lengths INCLUDING the current token
    ks/vs_pages:  (P, page, HK) per-row dequant scales for int8 pools
    window:       a sliding layer's: the query (position ``length - 1``)
                  sees the keys at ``kpos > length - 1 - window`` only;
                  table entries of pages wholly below that may hold any
                  page id (the allocator's trash page)

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
    if window is not None:
        s = jnp.where(kpos > qpos - window, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cv.dtype), cv)
    return o[:, 0]                                 # (B, H, D)


# ---------------- Pallas kernel: rows x live page groups ----------------

# VMEM the K and V page buffers may take together, both slots of the
# double buffer counted; a group is unrolled over at most _MAX_GROUP pages
_KV_VMEM_BUDGET = 2 * 1024 * 1024
_MAX_GROUP = 16
_NO_HEAD = 1 << 30      # offset of a key row of another kv head: never live


def _pages_per_group(ppseq, page, HK, D, itemsize):
    """Pages one step of the kernel moves and computes: as many whole
    page slabs ``(page*HK, D)`` of K and of V as fit the VMEM budget
    twice over (double buffer), no more than a row can hold."""
    slab = page * HK * D * itemsize
    return max(1, min(_KV_VMEM_BUDGET // (4 * slab), _MAX_GROUP, ppseq))


def _in_hbm(pool):
    """The kernel streams pages from HBM, and its roofline is HBM's. Inside
    a compiled program XLA may otherwise park a pool operand that fits on
    chip across the call (a 100 MiB K pool of one layer in the v5e's 128
    of VMEM), and the copies then read VMEM: say where the pools are read
    from. The decode step hands over the pools of all layers as one run
    of pages, too large to park; a caller with a small pool still can be.
    The constraint exists only under ``jit`` and for the compiled
    kernel."""
    if _fa._interpret_mode() or not isinstance(pool, jax.core.Tracer):
        return pool
    return pltpu.with_memory_space_constraint(pool, pltpu.HBM)


def _paged_kernel(bt_ref, cnt_ref, len_ref, q_ref, off_ref, k_hbm, v_hbm,
                  *rest, scale, page, G, quant, window):
    """One request row per grid step; inside it a loop over the row's
    LIVE page groups, ``ceil(cnt/G)`` of them.

    The pools stay in HBM as ``(P, page*HK, D)``: a page with all its kv
    heads is one contiguous slab. A group is up to G slabs of K and of V,
    fetched by page id from the scalar-prefetched block table with one
    async copy each into a double-buffered VMEM scratch; the next group
    (of this row, or the first of the next row) is in flight while this
    one is computed. Dead pages are neither fetched nor looped over.

    A slab's key rows are ``(position, kv head)`` pairs. The row's whole
    ``(H, D)`` query block multiplies all of them in one product per
    page, and ``off_ref`` (``(H, page*HK)``: the position in the page
    where query head and key row share a kv head, ``_NO_HEAD``
    elsewhere) masks the scores of other heads together with the
    positions at or past the row's length; a masked score's p is exactly
    0, so each query head's softmax runs over its own kv head only.
    Online softmax in f32 per group; p is cast to the pool's compute
    dtype for ``p @ v`` as the reference does.

    int8 pools: the per-row scales arrive as ``(1, page*HK)`` lane rows
    by the same page ids and multiply the scores (K) and p (V) in VMEM;
    the int8 values themselves are exact in the compute dtype.

    The buffers are zeroed once, so what a partial group leaves in the
    slots it did not fetch is zeros or an earlier page's finite data,
    and ``p == 0`` there contributes nothing.

    ``window`` (static; None for a full layer): the wrapper has already
    moved the row's table and length to start at the window's first
    live page, so the loop walks the window's pages alone; the mask
    adds the lower bound ``position >= length - window`` inside that
    first page."""
    if quant:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem, slot_ref = rest
        streams = ((k_hbm, kbuf), (v_hbm, vbuf),
                   (ks_hbm, ksbuf), (vs_hbm, vsbuf))
    else:
        o_ref, kbuf, vbuf, sem, slot_ref = rest
        streams = ((k_hbm, kbuf), (v_hbm, vbuf))
    b = pl.program_id(0)
    nrows = pl.num_programs(0)

    def group_copies(row, j, slot, fn):
        """``start`` or ``wait`` for the copies of group j of ``row``:
        its live pages only."""
        live = cnt_ref[row] - j * G
        for g in range(G):
            @pl.when(g < live)
            def _():
                pid = bt_ref[row, j * G + g]
                for n, (hbm, buf) in enumerate(streams):
                    getattr(pltpu.make_async_copy(
                        hbm.at[pid], buf.at[slot, g], sem.at[n, slot]),
                        fn)()

    @pl.when(b == 0)
    def _first():
        for _, buf in streams:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        group_copies(0, 0, 0, "start")

    q = q_ref[0]                                   # (H, D)
    off = off_ref[...]                             # (H, page*HK)
    rows = off.shape[1]
    length = len_ref[b]
    ngroups = pl.cdiv(cnt_ref[b], G)
    cdt = q.dtype

    def group(j, carry):
        m, l, acc, slot = carry
        last = j + 1 == ngroups
        nrow = jnp.where(last, b + 1, b)

        @pl.when(nrow < nrows)
        def _():
            group_copies(nrow, jnp.where(last, 0, j + 1), 1 - slot, "start")
        group_copies(b, j, slot, "wait")

        ss, lives = [], []
        for g in range(G):
            s = jax.lax.dot_general(
                q, kbuf[slot, g].astype(cdt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quant:
                s = s * ksbuf[slot, g][:, :rows]
            pos = (j * G + g) * page + off
            live = pos < length
            if window is not None:
                live &= pos >= length - window
            ss.append(jnp.where(live, s, _fa.DEFAULT_MASK_VALUE))
            lives.append(live)
        m_cur = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s in ss])
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        l = alpha * l
        acc = acc * alpha
        for g in range(G):
            p = jnp.where(lives[g], jnp.exp(ss[g] - m_new), 0.0)
            l = l + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * vsbuf[slot, g][:, :rows]
            acc = acc + jax.lax.dot_general(
                p.astype(cdt), vbuf[slot, g].astype(cdt),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return m_new, l, acc, 1 - slot

    H, D = q.shape
    m, l, acc, slot = lax.fori_loop(
        0, ngroups, group,
        (jnp.full((H, 1), -jnp.inf, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, D), jnp.float32), slot_ref[0]))
    slot_ref[0] = slot
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, block_tables, lengths, *,
                           scale=None, ks_pages=None, vs_pages=None,
                           window=None):
    """Pallas paged decode attention; same contract as
    :func:`paged_attention_reference` (pool layout (P, page, HK, D),
    per-row int8 scales (P, page, HK)), with work and HBM reads sized by
    each row's ``ceil(length/page)`` live pages. The pools are read
    from HBM as they are stored (for the head counts of the module's
    note, a reshape that moves no byte); only the small scales pools of
    the int8 tier are laid out anew, as lane rows.

    A group's keys enter one softmax step, so the f32 sums run in
    another order than the reference's: the results agree to
    ``rtol = atol = 2e-5`` in f32, not bit for bit. A row of length 0
    attends to nothing and returns zeros.

    ``window``: a sliding layer's (static). Each row's table is cut to
    the ``ceil(window/page) + 1`` entries from the window's first live
    page on and its length counted from there, so the same body reads
    only the window's pages, whatever the row's context."""
    B, H, D = q.shape
    P, page, HK = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    assert H % HK == 0
    rep = H // HK
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    ppseq = block_tables.shape[1]
    if (ks_pages is None) != (vs_pages is None):
        raise ValueError(
            "paged_attention: ks_pages and vs_pages must be passed "
            "together — int8 pools quantize both K and V")
    quant = ks_pages is not None
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)  # clamp -1
    if window is not None:
        first = jnp.maximum(lengths - window, 0) // page       # (B,)
        ppseq = min(ppseq, -(-window // page) + 1)
        bt = jnp.take_along_axis(bt, jnp.minimum(
            first[:, None] + jnp.arange(ppseq, dtype=jnp.int32)[None, :],
            bt.shape[1] - 1), axis=1)
        lengths = lengths - first * page
    # live pages per row; >= 1 so every row has a group to finish on
    cnt = jnp.clip(-(-lengths // page), 1, ppseq).astype(jnp.int32)

    rows = page * HK
    G = _pages_per_group(ppseq, page, HK, D, k_pages.dtype.itemsize)
    col = np.arange(rows)
    off = np.where(col[None, :] % HK == np.arange(H)[:, None] // rep,
                   col[None, :] // HK, _NO_HEAD).astype(np.int32)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    row_block = pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0))
    in_specs = [row_block,
                pl.BlockSpec((H, rows), lambda b, *_: (0, 0)), hbm, hbm]
    inputs = [bt, cnt, lengths, q, jnp.asarray(off),
              _in_hbm(k_pages.reshape(P, rows, D)),
              _in_hbm(v_pages.reshape(P, rows, D))]
    scratch = [pltpu.VMEM((2, G, rows, D), k_pages.dtype),
               pltpu.VMEM((2, G, rows, D), v_pages.dtype)]
    if quant:
        # one lane row a page, padded to whole lane tiles for the copy
        pad = -rows % 128
        in_specs += [any_space, any_space]
        inputs += [jnp.pad(jnp.asarray(sc, jnp.float32).reshape(P, 1, rows),
                           ((0, 0), (0, 0), (0, pad)))
                   for sc in (ks_pages, vs_pages)]
        scratch += [pltpu.VMEM((2, G, 1, rows + pad), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((4 if quant else 2, 2)),
                pltpu.SMEM((1,), jnp.int32)]

    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=s, page=page, G=G,
                          quant=quant, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,), in_specs=in_specs,
            out_specs=row_block, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=_fa._interpret_mode(),
        name="paged_attention", metadata={"kernel": "paged_attention"},
    )(*inputs)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale=None, ks_pages=None, vs_pages=None,
                    use_kernel=None, window=None):
    """Paged decode attention: Pallas kernel on real TPU (or when forced
    — interpret mode in tests), pure-lax gather fallback elsewhere so
    tier-1 CPU runs exercise dense-decode-identical numerics."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    if use_kernel:
        return paged_attention_kernel(
            q, k_pages, v_pages, block_tables, lengths, scale=scale,
            ks_pages=ks_pages, vs_pages=vs_pages, window=window)
    return paged_attention_reference(
        q, k_pages, v_pages, block_tables, lengths, scale=scale,
        ks_pages=ks_pages, vs_pages=vs_pages, window=window)
