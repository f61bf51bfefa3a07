"""Attention of a prefill chunk over its row's pages in a pool of keys and
values.

A chunk of C query tokens at positions ``ctx_len ..`` (token i sees the
positions ``<= ctx_len + i``, and on a sliding layer only the last
``window`` of them) attends over one layer's pools ``(P, page, nkv, hd)``
through the row's block table, the chunk's own keys and values already
written there. Two implementations of one contract:

- :func:`kv_chunk_attention_kernel` — Pallas TPU kernel, the page walk of
  ``paged_latent_attention.latent_chunk_attention`` over a K/V pool. One
  grid step a block of ``tq`` chunk tokens with all their heads; inside it
  a loop over the row's page groups from the first page the block's window
  lets it see (page 0 on a full layer) to the block's last valid token: the
  row's true length, not the bucket the program was compiled for. A page
  is one slab ``(page * nkv, hd)`` in HBM, a position's kv heads side by
  side, as the decode kernel reads it; a group's slabs of K and of V are
  fetched once a block by their ids into a double-buffered VMEM scratch,
  the next group (or the next block's first) in flight while this one is
  computed. A key head's keys are its rows of the slabs, taken by a
  strided load, and its ``rep = nh // nkv`` query heads come as ``tq *
  rep`` rows (the wrapper lays the queries out by key head): K and V are
  neither repeated nor multiplied against another head's queries. Online
  softmax in f32; nothing of the scores leaves VMEM; a block wholly past
  ``chunk_len`` fetches nothing and writes zeros.
- :func:`kv_chunk_attention_reference` — gather the pages the table names,
  dequantise on the int8 tier, and attend in ``jnp`` with the composition
  of ``models/generate._attn_with_cache`` (f32 scores over ``sqrt(hd)``,
  ``-1e30`` masking, ``p`` in the pool's compute dtype), a key head's
  query heads at a time where the float32 scores of all heads would pass
  ``_SCORE_BYTES``. The path off the chip, the int8 tier's path, and what
  the kernel is tested against.

:func:`kv_chunk_attention` dispatches: kernel on a real TPU (or when forced
— interpret mode in tests), the twin elsewhere and on the int8 tier.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from .flash_attention import _dot_nt, _softmax_update
from .paged_attention import _in_hbm, gather_pages

# pages a step of the kernel fetches and multiplies at once: 512 keys of
# 64-token pages, as the latent chunk kernel's groups
_MAX_GROUP = 8
# query rows (tokens x heads) a grid step holds: 64 tokens of 48 heads,
# 128 of 32, 256 of 16; a page is fetched once a block
_CHUNK_ROWS = 4096
# float32 scores the twin holds at once
_SCORE_BYTES = 512 << 20


def kv_chunk_attention_reference(q, k_pages, v_pages, table, ctx_len, *,
                                 window=None, ks_pages=None, vs_pages=None):
    """q (C, nh, hd) the chunk's queries after rotary, query i at position
    ``ctx_len + i``; k/v_pages (P, page, nkv, hd) one layer's pools (or all
    layers', the table moved up to the layer's first page); table (n,) the
    row's pages from position 0, in order, covering the context and the
    chunk; ``window`` a sliding layer's, under which query i sees the
    positions ``> ctx_len + i - window`` only (entries of pages wholly
    below the first query's window may hold any page id); ks/vs_pages (P,
    page, nkv) the int8 tier's per-row dequant scales. Returns (C, nh,
    hd)."""
    C, nh, hd = q.shape
    page, nkv = k_pages.shape[1], k_pages.shape[2]
    rep = nh // nkv
    lo = 0
    if window is not None:
        # the pages from the one that holds the first query's window edge
        n = min(table.shape[0], -(-(window - 1) // page) + -(-C // page) + 1)
        p0 = jnp.clip(jnp.maximum(ctx_len - window + 1, 0) // page, 0,
                      table.shape[0] - n)
        table = lax.dynamic_slice_in_dim(table, p0, n)
        lo = p0 * page
    ck = gather_pages(k_pages, table[None])[0]             # (S, nkv, hd)
    cv = gather_pages(v_pages, table[None])[0]
    if ks_pages is not None:
        ck = (ck.astype(jnp.float32) * gather_pages(
            ks_pages, table[None])[0][..., None]).astype(q.dtype)
        cv = (cv.astype(jnp.float32) * gather_pages(
            vs_pages, table[None])[0][..., None]).astype(q.dtype)
    S = ck.shape[0]
    kpos = lo + jnp.arange(S, dtype=jnp.int32)[None, :]
    qpos = ctx_len + jnp.arange(C, dtype=jnp.int32)[:, None]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window

    def head(qh, kh, vh):              # (C, rep, hd), (S, hd), (S, hd)
        s = jnp.einsum("crd,kd->rck", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("rck,kd->crd", p.astype(vh.dtype), vh)

    parts = (q.reshape(C, nkv, rep, hd), ck, cv)
    if 4 * C * nh * S > _SCORE_BYTES:
        o = jnp.moveaxis(lax.map(lambda a: head(*a), tuple(
            jnp.moveaxis(a, 1, 0) for a in parts)), 0, 1)
    else:
        o = jax.vmap(head, in_axes=1, out_axes=1)(*parts)
    return o.reshape(C, nh, hd)


def _head_rows(ref, nkv: int):
    """The rows of each key head of a group's slabs, ``ref`` ``(rows,
    hd)`` with a position's ``nkv`` heads side by side: one strided load a
    head, in head order. A 16-bit dtype packs two rows in a 32-bit word
    (the even one in the low half): a pair of heads is one strided load of
    the words, split by shifting (as the ragged paged kernel of JAX's
    examples reads its pages)."""
    n = ref.shape[0] // nkv
    if nkv == 1:
        return [ref[...]]
    if ref.dtype.itemsize == 4:
        return [ref[pl.ds(g, n, stride=nkv), :] for g in range(nkv)]
    words = ref.bitcast(jnp.uint32)
    out = []
    for j in range(nkv // 2):
        w = words[pl.ds(j, n, stride=nkv // 2), :]
        out.append(pltpu.bitcast(w << 16, jnp.float32).astype(ref.dtype))
        out.append(pltpu.bitcast(w & jnp.uint32(0xFFFF0000),
                                 jnp.float32).astype(ref.dtype))
    return out


def _chunk_kernel(meta_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
                  slot_ref, m_ref, l_ref, acc_ref, *, scale, page, G, tq,
                  rep, window):
    """One block of ``tq`` chunk tokens a grid step, key head g's query
    heads as the ``tq * rep`` rows of ``q_ref[g]`` (row r is token r //
    rep). ``meta_ref`` holds ``ctx_len``, ``chunk_len`` and the row's table.
    Inside the step a loop over the page groups from the first page the
    block's first token sees to the last its last valid token sees
    (``paged_latent_attention._chunk_kernel``'s scheme: pages outside them
    are neither fetched nor looped over; the buffers are zeroed once, and
    what a partial group leaves in the slots it did not fetch lies past
    every valid row's limit). A group's copies are a loop, not unrolled."""
    b = pl.program_id(0)
    ctx_len, chunk_len = meta_ref[0], meta_ref[1]
    npages = meta_ref.shape[0] - 2
    nblocks = pl.cdiv(chunk_len, tq)
    nkv, rows, hd = q_ref.shape
    slab = kbuf.shape[2]

    def pages(blk):
        # [first, live): the pages the block's valid tokens see
        last = jnp.minimum((blk + 1) * tq, chunk_len)
        live = jnp.clip(pl.cdiv(ctx_len + last, page), 1, npages)
        if window is None:
            return 0, live
        first = jnp.maximum(ctx_len + blk * tq - window + 1, 0) // page
        return jnp.minimum(first, live - 1), live

    def group_copies(blk, j, slot, fn):
        first, live = pages(blk)

        def one(i, carry):
            pid = meta_ref[2 + first + j * G + i]
            for n, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                getattr(pltpu.make_async_copy(
                    hbm.at[pid], buf.at[slot, i], sem.at[n, slot]), fn)()
            return carry
        lax.fori_loop(0, jnp.minimum(live - first - j * G, G), one, 0)

    @pl.when(b == 0)
    def _first():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0

        @pl.when(nblocks > 0)
        def _():
            group_copies(0, 0, 0, "start")

    @pl.when(b >= nblocks)
    def _past():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(b < nblocks)
    def _attend():
        w = G * page
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        first, live = pages(b)
        ngroups = pl.cdiv(live - first, G)
        # row r sees key k of a group where k - r // rep <= the group's lim
        rel = (lax.broadcasted_iota(jnp.int32, (rows, w), 1)
               - lax.broadcasted_iota(jnp.int32, (rows, w), 0) // rep)

        def group(j, slot):
            last = j + 1 == ngroups
            nblk = jnp.where(last, b + 1, b)

            @pl.when(nblk < nblocks)
            def _():
                group_copies(nblk, jnp.where(last, 0, j + 1), 1 - slot,
                             "start")
            group_copies(b, j, slot, "wait")

            lim = ctx_len + b * tq - (first + j * G) * page
            seen = rel <= lim
            if window is not None:
                seen &= rel > lim - window
            keys = _head_rows(kbuf.at[slot].reshape(G * slab, hd), nkv)
            values = _head_rows(vbuf.at[slot].reshape(G * slab, hd), nkv)
            for g in range(nkv):
                q = q_ref[g]
                s = _dot_nt(q, keys[g].astype(q.dtype)) * scale
                s = jnp.where(seen, s, _fa.DEFAULT_MASK_VALUE)
                _softmax_update(s, values[g].astype(q.dtype), m_ref.at[g],
                                l_ref.at[g], acc_ref.at[g])
            return 1 - slot

        slot_ref[0] = lax.fori_loop(0, ngroups, group, slot_ref[0])
        l = jnp.sum(l_ref[...], axis=2, keepdims=True)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def kernel_reads(k_pages) -> bool:
    """Whether the kernel can take a head's rows out of this pool's slabs:
    one head, a 32-bit dtype, or a 16-bit one with heads in pairs."""
    nkv, size = k_pages.shape[2], k_pages.dtype.itemsize
    return nkv == 1 or size == 4 or (size == 2 and nkv % 2 == 0)


def kv_chunk_attention_kernel(q, k_pages, v_pages, table, ctx_len,
                              chunk_len, *, window=None):
    """The Pallas kernel; :func:`kv_chunk_attention_reference`'s contract
    without the int8 tier (and for the pools :func:`kernel_reads`). Query
    rows past ``chunk_len`` are padding: a block of them fetches nothing
    and returns zeros. Work and HBM reads are sized by the row's true
    length, ``ctx_len + chunk_len``, whatever ``table``'s length; the
    table and the two lengths go in as one ``int32`` operand."""
    lens = jnp.stack([jnp.asarray(ctx_len, jnp.int32).reshape(()),
                      jnp.asarray(chunk_len, jnp.int32).reshape(())])
    meta = jnp.concatenate([lens, jnp.maximum(jnp.asarray(table, jnp.int32),
                                              0)])
    return _chunk_call(meta, q, k_pages, v_pages, window=window,
                       interpret=_fa._interpret_mode())


def _block_tokens(C: int, nh: int) -> int:
    """Chunk tokens a grid step: ``_CHUNK_ROWS`` query rows at most, a
    divisor of C."""
    tq = max(1, min(C, _CHUNK_ROWS // nh))
    while C % tq:
        tq -= 1
    return tq


# jitted so that the kernel is traced once a shape: every context bucket
# builds a program of its own, and set-up would trace the kernel in each
@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _chunk_call(meta, q, k_pages, v_pages, *, window, interpret):
    C, nh, hd = q.shape
    P, page, nkv = k_pages.shape[:3]
    rep = nh // nkv
    tq = _block_tokens(C, nh)
    G = min(_MAX_GROUP, meta.shape[0] - 2)
    slab = page * nkv
    # key head g's query heads as rows (token, head), token-major
    by_head = q.reshape(C, nkv, rep, hd).transpose(1, 0, 2, 3).reshape(
        nkv, C * rep, hd)
    block = pl.BlockSpec((nkv, tq * rep, hd), lambda b, *_: (0, b, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    o = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=1.0 / math.sqrt(hd),
                          page=page, G=G, tq=tq, rep=rep, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(C // tq,),
            in_specs=[block, hbm, hbm], out_specs=block,
            scratch_shapes=[pltpu.VMEM((2, G, slab, hd), k_pages.dtype),
                            pltpu.VMEM((2, G, slab, hd), v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((nkv, tq * rep, 128), jnp.float32),
                            pltpu.VMEM((nkv, tq * rep, 128), jnp.float32),
                            pltpu.VMEM((nkv, tq * rep, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(by_head.shape, q.dtype),
        interpret=interpret,
        compiler_params=_fa._COMPILER_PARAMS,
        name="kv_chunk_attention",
        metadata={"kernel": "kv_chunk_attention"},
    )(meta, by_head, _in_hbm(k_pages.reshape(P, slab, hd)),
      _in_hbm(v_pages.reshape(P, slab, hd)))
    return o.reshape(nkv, C, rep, hd).transpose(1, 0, 2, 3).reshape(
        C, nh, hd)


def kv_chunk_attention(q, k_pages, v_pages, table, ctx_len, chunk_len, *,
                       bucket_pages: int, window=None, ks_pages=None,
                       vs_pages=None, use_kernel=None):
    """Kernel on a real TPU (or when forced — interpret mode in tests), the
    twin elsewhere, on the int8 tier (``ks_pages`` / ``vs_pages``) and for
    a pool whose heads the kernel cannot take apart (:func:`kernel_reads`).
    ``table`` is the row's whole block table; the kernel walks it to the
    row's true length, the twin attends over its first ``bucket_pages``
    pages (the context's bucket and the chunk) under the mask. Rows past
    ``chunk_len`` are padding, which the two fill differently."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    with jax.named_scope("kv_chunk_attention"):
        if use_kernel and ks_pages is None and kernel_reads(k_pages):
            return kv_chunk_attention_kernel(
                q, k_pages, v_pages, table, ctx_len, chunk_len,
                window=window)
        return kv_chunk_attention_reference(
            q, k_pages, v_pages, table[:bucket_pages], ctx_len,
            window=window, ks_pages=ks_pages, vs_pages=vs_pages)
