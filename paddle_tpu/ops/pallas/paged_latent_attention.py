"""Paged decode attention over a pool of LATENTS (latent attention, MLA, in
its absorbed form).

A layer of latent attention keeps ONE row a token, ``[c | k_r]``: the
normed latent (``R`` numbers) and the rotated key all heads share (``r``
more), with no head axis (``models/latent.py``). With the queries taken
into the latent (``q_h = [q_h^nope W_UK,h^T | q_h^rope]``, ``R + r`` wide),
a head's score against a cached token is ``q_h . [c | k_r]`` and its result
``sum_t p_t c_t``, still in the latent: every one of the H query heads
reads the same rows, as key (all ``R + r`` lanes) and as value (the first
``R``). That is multi-query attention with one "KV head" whose value is a
slice of its key. The pool lays a row out in whole 128-lane tiles (D lanes,
zeros past ``R + r``: a page is copied out of HBM in whole tiles, and the
chip's layout pads the last axis to tiles whatever the shape says), and the
queries come zero-padded to the same width.

Two implementations of one contract:

- :func:`paged_latent_attention_kernel` — Pallas TPU kernel, built like
  ``paged_attention.py``'s: the pool stays in HBM as it is stored, ``(P,
  page, D)``; one grid step a request row; inside it a loop over the
  row's LIVE pages in groups of G, each page fetched ONCE by its id (block
  table in SMEM by scalar prefetch) with an async copy into a
  double-buffered VMEM scratch, the next group (or the next row's first) in
  flight while this one is computed. A group's ``G * page`` rows multiply
  the row's whole ``(H, D)`` query block in one product for the scores
  and, their first R lanes, the probabilities in one product for the
  values: the bytes of a page are read from HBM once and serve both.
  Online softmax in f32 a group, ``p`` cast to the pool's dtype for ``p @
  c`` as the reference does. ``paged_attention.py`` would be handed the
  pool twice, as K and as V, and read every page twice.
- :func:`paged_latent_attention_reference` — pure ``lax``: gather each
  row's pages, dequantise on the int8 tier (a token's two scales, the
  latent's and the key's), masked softmax in f32. The path off the chip,
  the int8 tier's path everywhere, and what the kernel is tested against
  (``rtol = atol = 2e-5`` in f32: a group's keys enter one softmax step,
  so the f32 sums run in another order).

:func:`paged_latent_attention` dispatches: kernel on a real TPU (or when
forced — interpret mode in tests), reference elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from .paged_attention import _in_hbm, gather_pages

# pages a step of the kernel fetches and multiplies at once: 512 rows of a
# 64-token page fill the MXU's columns four times over, and both slots of
# the double buffer stay near 1.3 MB of VMEM at 576 (laid out as 640) lanes
_MAX_GROUP = 8


def paged_latent_attention_reference(q, pages, block_tables, lengths, *,
                                     scale: float, value_dim: int,
                                     scales=None):
    """q (B, H, D) queries in the latent, ``[q^lat | q^rope | zeros]``;
    pages (P, page, D) the pool of one layer (or of all layers, the tables
    moved up to the layer's first page); block_tables (B, ppseq); lengths
    (B,) tokens each row attends over, the current one included; ``scales``
    (P, page, 2) the int8 tier's per-token dequant scales (latent, key).
    Returns the heads' results still in the latent, ``(B, H, value_dim)``,
    ``value_dim = R``. A row of length 0 returns zeros."""
    B, H, D = q.shape
    c = gather_pages(pages, block_tables)               # (B, S, D)
    if scales is not None:
        sc = gather_pages(scales, block_tables)         # (B, S, 2)
        c = jnp.concatenate(
            [c[..., :value_dim].astype(jnp.float32) * sc[..., :1],
             c[..., value_dim:].astype(jnp.float32) * sc[..., 1:]],
            axis=-1).astype(q.dtype)
    s = jnp.einsum("bhd,bkd->bhk", q.astype(jnp.float32),
                   c.astype(jnp.float32)) * scale
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 2)
    live = kpos < lengths[:, None, None]
    s = jnp.where(live, s, -1e30)
    p = jnp.where(live, jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("bhk,bkd->bhd", p.astype(c.dtype),
                      c[..., :value_dim]).astype(q.dtype)


def _latent_kernel(bt_ref, cnt_ref, len_ref, q_ref, c_hbm, o_ref, buf, sem,
                   slot_ref, *, scale, page, G, R):
    """One request row a grid step; inside it a loop over the row's live
    page groups (``paged_attention._paged_kernel`` has the scheme: dead
    pages are neither fetched nor looped over; the buffers are zeroed once,
    so what a partial group leaves in the slots it did not fetch is zeros
    or an earlier page's finite data, and ``p == 0`` there contributes
    nothing)."""
    b = pl.program_id(0)
    nrows = pl.num_programs(0)

    def group_copies(row, j, slot, fn):
        live = cnt_ref[row] - j * G
        for g in range(G):
            @pl.when(g < live)
            def _():
                getattr(pltpu.make_async_copy(
                    c_hbm.at[bt_ref[row, j * G + g]], buf.at[slot, g],
                    sem.at[slot]), fn)()

    @pl.when(b == 0)
    def _first():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        group_copies(0, 0, 0, "start")

    q = q_ref[0]                                   # (H, D)
    H, D = q.shape
    length = len_ref[b]
    ngroups = pl.cdiv(cnt_ref[b], G)
    cdt = q.dtype
    rows = G * page

    def group(j, carry):
        m, l, acc, slot = carry
        last = j + 1 == ngroups
        nrow = jnp.where(last, b + 1, b)

        @pl.when(nrow < nrows)
        def _():
            group_copies(nrow, jnp.where(last, 0, j + 1), 1 - slot, "start")
        group_copies(b, j, slot, "wait")

        # the group's rows once, as keys and (their first R lanes) values
        c = buf[slot].reshape(rows, D).astype(cdt)
        s = lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        pos = j * rows + lax.broadcasted_iota(jnp.int32, (H, rows), 1)
        live = pos < length
        s = jnp.where(live, s, _fa.DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + lax.dot_general(
            p.astype(cdt), c[:, :R], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc, 1 - slot

    m, l, acc, slot = lax.fori_loop(
        0, ngroups, group,
        (jnp.full((H, 1), -jnp.inf, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, R), jnp.float32), slot_ref[0]))
    slot_ref[0] = slot
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_latent_attention_kernel(q, pages, block_tables, lengths, *,
                                  scale: float, value_dim: int):
    """The Pallas kernel; :func:`paged_latent_attention_reference`'s
    contract without the int8 tier. Work and HBM reads are sized by each
    row's ``ceil(length / page)`` live pages."""
    B, H, D = q.shape
    P, page = pages.shape[0], pages.shape[1]
    ppseq = block_tables.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    # live pages a row; >= 1 so every row has a group to finish on
    cnt = jnp.clip(-(-lengths // page), 1, ppseq).astype(jnp.int32)
    G = min(_MAX_GROUP, ppseq)
    row_q = pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0))
    row_o = pl.BlockSpec((1, H, value_dim), lambda b, *_: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, page=page, G=G,
                          R=value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[row_q, pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=row_o,
            scratch_shapes=[pltpu.VMEM((2, G, page, D), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        interpret=_fa._interpret_mode(),
        name="paged_latent_attention",
        metadata={"kernel": "paged_latent_attention"},
    )(bt, cnt, lengths, q, _in_hbm(pages))


def paged_latent_attention(q, pages, block_tables, lengths, *, scale: float,
                           value_dim: int, scales=None, use_kernel=None):
    """Kernel on a real TPU (or when forced — interpret mode in tests), the
    reference elsewhere and on the int8 tier (``scales``)."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    with jax.named_scope("paged_latent_attention"):
        if use_kernel and scales is None:
            return paged_latent_attention_kernel(
                q, pages, block_tables, lengths, scale=scale,
                value_dim=value_dim)
        return paged_latent_attention_reference(
            q, pages, block_tables, lengths, scale=scale,
            value_dim=value_dim, scales=scales)
