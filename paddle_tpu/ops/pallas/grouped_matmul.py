"""Grouped matmul for routed experts: rows sorted by expert against the
expert stacks as they are stored.

The serving expert layer (``models/generate.py:_expert_apply``) sorts a
step's routed items by expert and multiplies each group of rows by its
expert's matrix. A decode step has a few rows an expert (32 rows x top-8
over 64 experts: four), so the work is reading every expert that was hit,
once: 4.1 MB a matrix at the published widths (2304 x 896, bf16).

Two implementations of one contract:

- :func:`grouped_matmul_kernel` — Pallas TPU kernel. The grid has one step
  for each pair of (a group with rows, a tile of ``tm`` rows it touches),
  in row order; a step's blocks are the tile of rows, the group's WHOLE
  ``(K, N)`` matrix, fetched by its index in the stack of all layers'
  experts (scalar prefetch), and the tile of the output, which stays in
  VMEM while consecutive steps (the groups that share the tile) fill their
  own rows of it. So each expert that was hit is read once in one DMA,
  nothing is read for an expert that was not, and no layer's slice of the
  stack is copied out for the call. ``lax.ragged_dot``'s own TPU kernel
  tiles K and N by 256 x 128: 63 steps over the groups times 9 x 7 tiles a
  matrix is some four thousand grid steps a call, and read 1.75 ms a call
  against a floor of 0.32 ms on the v5e (PERF.md, PR 28); one step a
  matrix is 63.
- :func:`grouped_matmul_reference` — ``lax.ragged_dot`` over the same
  arguments: the path off the chip, and what the kernel is tested against.

:func:`grouped_matmul` dispatches: kernel on a real TPU (or when forced —
interpret mode in tests), reference elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa

# rows a tile: few where a call has few rows a group (a decode step), so
# that a step multiplies little that it then masks away
_TM_SMALL, _TM_LARGE, _SMALL_ROWS = 32, 128, 512


def _all_sizes(sizes, layer, layers: int):
    """The sizes of one layer's groups among all layers' (the others 0)."""
    if layers == 1:
        return sizes
    n = sizes.shape[0]
    return lax.dynamic_update_slice(
        jnp.zeros((layers * n,), jnp.int32), sizes, (layer * n,))


def grouped_matmul_reference(xs, w, sizes, layer=0):
    """``xs`` (n, K) rows sorted by group, ``w`` (L, G, K, N) the groups'
    matrices for every layer, ``sizes`` (G,) rows a group of layer
    ``layer``: row i of the result is ``xs[i] @ w[layer, group of i]``."""
    L, G = w.shape[:2]
    return lax.ragged_dot(xs, w.reshape((L * G,) + w.shape[2:]),
                          _all_sizes(sizes, layer, L))


def _work_list(sizes, layer, tm: int, steps: int):
    """For each grid step: the matrix to fetch, the row tile, and the
    group's row range; steps past the last pair repeat it (no new copy) and
    are skipped. Pairs come in row order: by group, then by tile."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)
    count = upto[-1]
    w = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    g = jnp.minimum(jnp.searchsorted(upto, w, side="right"),
                    G - 1).astype(jnp.int32)
    tile = first[g] + w - (upto[g] - tiles[g])
    return (layer * G + g, tile.astype(jnp.int32), starts[g], ends[g],
            count.reshape(1))


def _kernel(mat_ref, tile_ref, lo_ref, hi_ref, count_ref, x_ref, w_ref,
            o_ref, *, tm):
    s = pl.program_id(0)

    @pl.when(s < count_ref[0])
    def _():
        tile = tile_ref[s]
        # the first group to reach a tile finds what the buffer held
        opens = jnp.logical_or(s == 0,
                               tile_ref[jnp.maximum(s - 1, 0)] != tile)
        acc = lax.dot_general(x_ref[...], w_ref[0],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        rows = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (rows >= lo_ref[s]) & (rows < hi_ref[s])
        held = jnp.where(opens, jnp.zeros_like(o_ref), o_ref[...])
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), held)


def grouped_matmul_kernel(xs, w, sizes, layer=0):
    """Pallas grouped matmul; the contract of
    :func:`grouped_matmul_reference`. The products accumulate in float32
    and are rounded once to ``xs.dtype``, as ``ragged_dot``'s are."""
    n, K = xs.shape
    L, G, _, N = w.shape
    tm = _TM_SMALL if n <= _SMALL_ROWS else _TM_LARGE
    pad = -n % tm
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    ntiles = (n + pad) // tm
    steps = ntiles + min(G, n)
    work = _work_list(jnp.asarray(sizes, jnp.int32),
                      jnp.asarray(layer, jnp.int32), tm, steps)
    item = jnp.dtype(w.dtype).itemsize
    # both buffers of a matrix, of a row tile and of an output tile, and
    # the float32 product
    vmem = 2 * K * N * item + 4 * tm * (K + N) * xs.dtype.itemsize \
        + tm * N * 4
    in_specs = [pl.BlockSpec((tm, K), lambda s, m, t, *_: (t[s], 0)),
                pl.BlockSpec((1, K, N), lambda s, m, *_: (m[s], 0, 0))]
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(steps,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, N), lambda s, m, t, *_: (t[s], 0))),
        out_shape=jax.ShapeDtypeStruct((n + pad, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(max(vmem * 5 // 4 + (2 << 20), 16 << 20))),
        interpret=_fa._interpret_mode(),
        name="grouped_expert_matmul",
        metadata={"kernel": "grouped_expert_matmul"},
    )(*work, xs, w.reshape((L * G, K, N)))
    return out[:n] if pad else out


def grouped_matmul(xs, w, sizes, layer=0, use_kernel=None):
    """Rows sorted by group times their groups' matrices: the Pallas kernel
    on a real TPU (or when forced — interpret mode in tests),
    ``lax.ragged_dot`` elsewhere."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    if use_kernel:
        return grouped_matmul_kernel(xs, w, sizes, layer)
    return grouped_matmul_reference(xs, w, sizes, layer)
