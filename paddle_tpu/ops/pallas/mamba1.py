"""The Mamba-1 recurrence over a pool of recurrent state: one token of every
row in place (decode), and a row's prefill chunk token by token (prefill);
and the one-token step of the causal convolution before it, over the pool of
convolution tails in place.

A Mamba-1 mixer keeps, a row and a layer, a state ``S`` of ``(N, D)``
float32: a decay for every channel AND state (``A`` is ``(D, N)``, not one
scalar a head as in Mamba-2), so a token advances it by

    S[n, d] <- exp(dt[d] A[d, n]) S[n, d] + dt[d] x[d] B[n]
    y[d]     = sum_n S[n, d] C[n]

At the published widths (D 5,120, N 16) that is 327,680 B a row-layer read
and written, and the decay is an ``exp`` of every element: the work is
moving the state once and computing that ``exp`` where the state lies.

The pool is ``(layers * slots, N, D)``: channels on the lanes, the 16 states
down the sublanes, so ``dt`` and ``x`` are lane rows that broadcast down the
tile as they lie, ``B`` and ``C`` sublane columns broadcast across it, and
``y`` a sublane reduction. No operand is relaid in a kernel.

The tails' pool is ``(layers * slots, (K - 1) * D)`` in the model's dtype: a
row's last ``K - 1`` inputs of the convolution laid flat, tap after tap,
each tap's ``D`` channels on the lanes. Laid ``(K - 1, D)`` a row, the 3
taps would pad a bfloat16 tile of 16 sublanes and XLA:TPU re-lays the whole
pool to cut a batch of rows out of it.

Three operations, each with two implementations of one contract:

- :func:`mamba1_state_update` — one token of the rows ``base .. base + B``.
  The Pallas kernel aliases the pool to its result and touches only those
  rows' blocks, each read once and written once; a row that is not
  ``active`` is written back as read. ``base`` is a multiple of ``B`` (a
  decode step's layer offset is ``layer * slots`` with ``B == slots``).
- :func:`mamba1_conv_step` — one token of the causal convolution for the
  rows ``base .. base + B`` of the tails' pool: the window ``[tail, x]``
  convolved in float32, and the last ``K - 1`` columns written back where
  a row is ``active``. The Pallas kernel aliases the pool to its result,
  as the state update does, and moves a block of whole rows (every tap)
  at a time. It is the only writer of the pool in a decode step, so the
  pool keeps the layout it is stored in: with ``jnp`` slices and an
  update in its place, XLA:TPU laid the carried pool column-major, copied
  it whole at the decode program's entry and exit and wrote a layer's
  rows at a tenth of the bandwidth (PERF.md §5).
- :func:`mamba1_chunk_scan` — T tokens of ONE row from its state. The
  Pallas kernel holds the state of a block of channels in registers and
  walks the tokens in order (a decay that varies with ``n`` has no
  single-decay quadratic form to cut the chunk with; PERF.md has the
  timing that chose this over sub-chunks of cumulative log-decay).

The ``jnp`` twins are the path off the chip and what the kernels are tested
against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa

F32 = jnp.float32

#: rows and channels a block of the state update, and channels a block of
#: the chunk scan (PERF.md: chosen by a timing at the published widths)
UPDATE_ROWS, UPDATE_LANES, SCAN_LANES = 8, 5120, 1024
#: rows a block of the convolution step (every tap of a row in one block)
CONV_ROWS = 32


def _fit(want: int, n: int, quantum: int = 1) -> int:
    """The largest divisor of ``n`` that is at most ``want`` and a multiple
    of ``quantum`` (``n`` itself when none is)."""
    for b in range(min(want, n), 0, -1):
        if n % b == 0 and b % quantum == 0:
            return b
    return n


# ---------------- one token of every row ----------------
def mamba1_state_update_reference(pool, base, dt, x, A, bm, cm, active):
    """``pool`` (R, N, D) state; rows ``base .. base + B`` advance one
    token: ``dt`` and ``x`` (B, D), ``A`` (N, D) (``-exp(A_log)``
    transposed), ``bm`` / ``cm`` (B, N), all float32; ``active`` (B,) bool.
    Returns ``(y (B, D) float32, pool)``; an inactive row's state stays and
    its ``y`` is whatever the stale state gives."""
    B = dt.shape[0]
    s = lax.dynamic_slice_in_dim(pool, base, B, 0).astype(F32)
    new = (jnp.exp(dt[:, None, :] * A[None]) * s
           + (dt * x)[:, None, :] * bm[:, :, None])
    y = jnp.sum(new * cm[:, :, None], axis=1)
    new = jnp.where(active[:, None, None], new, s)
    return y, lax.dynamic_update_slice_in_dim(
        pool, new.astype(pool.dtype), base, 0)


def _update_kernel(base_ref, act_ref, s_ref, dt_ref, x_ref, a_ref, b_ref,
                   c_ref, y_ref, o_ref, *, rb):
    a = a_ref[...]                                   # (N, db)
    for r in range(rb):
        live = act_ref[pl.program_id(0) * rb + r] != 0
        dt = dt_ref[r:r + 1, :]                      # (1, db)
        s = s_ref[r].astype(F32)                     # (N, db)
        new = jnp.exp(dt * a) * s + (dt * x_ref[r:r + 1, :]) * b_ref[r]
        y_ref[r:r + 1, :] = jnp.sum(new * c_ref[r], axis=0, keepdims=True)
        o_ref[r] = jnp.where(live, new, s).astype(o_ref.dtype)


def mamba1_state_update_kernel(pool, base, dt, x, A, bm, cm, active,
                               rows: int = UPDATE_ROWS,
                               lanes: int = UPDATE_LANES):
    """Pallas state update; the contract of
    :func:`mamba1_state_update_reference`. A grid step takes ``rows`` rows
    (a multiple of 8 dividing the batch, else the whole batch) of
    ``lanes`` channels (a multiple of 128 dividing the width)."""
    B, D = dt.shape
    N = A.shape[0]
    rb = rows if B % rows == 0 and rows % 8 == 0 else B
    db = _fit(lanes, D, 128)
    state = pl.BlockSpec((rb, N, db),
                         lambda r, j, base, act: (base[0] // rb + r, 0, j))
    lane_rows = pl.BlockSpec((rb, db), lambda r, j, *_: (r, j))
    column = pl.BlockSpec((rb, N, 1), lambda r, j, *_: (r, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_update_kernel, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B // rb, D // db),
            in_specs=[state, lane_rows, lane_rows,
                      pl.BlockSpec((N, db), lambda r, j, *_: (0, j)),
                      column, column],
            out_specs=[lane_rows, state]),
        out_shape=[jax.ShapeDtypeStruct((B, D), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 2 (after the two prefetched scalars) is the pool
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_fa._interpret_mode(),
        name="mamba1_state_update",
        metadata={"kernel": "mamba1_state_update"},
    )(jnp.asarray(base, jnp.int32).reshape(1), active.astype(jnp.int32),
      pool, dt, x, A, bm[:, :, None], cm[:, :, None])
    return y, new


def mamba1_state_update(pool, base, dt, x, A, bm, cm, active,
                        use_kernel=None):
    """The Pallas kernel on a real TPU (or when forced — interpret mode in
    tests), the ``jnp`` twin elsewhere."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    with jax.named_scope("mamba1_state_update"):
        if use_kernel:
            return mamba1_state_update_kernel(pool, base, dt, x, A, bm, cm,
                                              active)
        return mamba1_state_update_reference(pool, base, dt, x, A, bm, cm,
                                             active)


# ---------------- one token of the causal convolution ----------------
def mamba1_conv_step_reference(pool, base, x, w, b, active):
    """``pool`` (R, (K - 1) * D) tails; rows ``base .. base + B`` take one
    token ``x`` (B, D) through the convolution ``w`` (K, D), ``b`` (D,):
    ``c = b + sum_j w[j] win[j]`` in float32 over the window ``win =
    [tail, x]``. Returns ``(c (B, D) float32, pool)`` with each ``active``
    row's tail now ``win[1:]`` and an inactive row's as it was."""
    B, D = x.shape
    K = w.shape[0]
    tail = lax.dynamic_slice_in_dim(pool, base, B, 0)
    win = jnp.concatenate([tail.reshape(B, K - 1, D),
                           x[:, None].astype(pool.dtype)], axis=1)
    wf = w.astype(F32)
    c = b.astype(F32)[None]
    for j in range(K):
        c = c + wf[j] * win[:, j].astype(F32)
    new = jnp.where(active[:, None], win[:, 1:].reshape(B, -1), tail)
    return c, lax.dynamic_update_slice_in_dim(pool, new, base, 0)


def _conv_kernel(base_ref, act_ref, t_ref, x_ref, w_ref, b_ref, c_ref,
                 o_ref):
    K, D = w_ref.shape
    live = act_ref[...] != 0                         # (rb, 1)
    w = w_ref[...].astype(F32)
    c = b_ref[...].astype(F32)                       # (1, D)
    for j in range(K - 1):
        tap = t_ref[:, j * D:(j + 1) * D]            # (rb, D)
        nxt = (t_ref[:, (j + 1) * D:(j + 2) * D] if j + 2 < K
               else x_ref[...].astype(o_ref.dtype))
        c = c + w[j:j + 1] * tap.astype(F32)
        o_ref[:, j * D:(j + 1) * D] = jnp.where(live, nxt, tap)
    c_ref[...] = c + w[K - 1:] * x_ref[...].astype(o_ref.dtype).astype(F32)


def mamba1_conv_step_kernel(pool, base, x, w, b, active,
                            rows: int = CONV_ROWS):
    """Pallas convolution step; the contract of
    :func:`mamba1_conv_step_reference`. A grid step takes ``rows`` whole
    rows of the pool (a multiple of 16 dividing the batch, else the whole
    batch); ``base`` is a multiple of it."""
    B, D = x.shape
    K = w.shape[0]
    rb = rows if B % rows == 0 and rows % 16 == 0 else B
    tails = pl.BlockSpec((rb, (K - 1) * D),
                         lambda r, base: (base[0] // rb + r, 0))
    lane_rows = pl.BlockSpec((rb, D), lambda r, *_: (r, 0))
    c, new = pl.pallas_call(
        _conv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // rb,),
            in_specs=[pl.BlockSpec((rb, 1), lambda r, *_: (r, 0)), tails,
                      lane_rows, pl.BlockSpec((K, D), lambda r, *_: (0, 0)),
                      pl.BlockSpec((1, D), lambda r, *_: (0, 0))],
            out_specs=[lane_rows, tails]),
        out_shape=[jax.ShapeDtypeStruct((B, D), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 2 (after the prefetched base and the rows' flags) is the
        # pool
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_fa._interpret_mode(),
        name="mamba1_conv_step",
        metadata={"kernel": "mamba1_conv_step"},
    )(jnp.asarray(base, jnp.int32).reshape(1),
      active.astype(jnp.int32)[:, None], pool, x, w, b.reshape(1, D))
    return c, new


def mamba1_conv_step(pool, base, x, w, b, active, use_kernel=None):
    """The Pallas kernel on a real TPU (or when forced — interpret mode in
    tests), the ``jnp`` twin elsewhere."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    with jax.named_scope("mamba1_conv_step"):
        if use_kernel:
            return mamba1_conv_step_kernel(pool, base, x, w, b, active)
        return mamba1_conv_step_reference(pool, base, x, w, b, active)


# ---------------- a row's chunk, token by token ----------------
def mamba1_chunk_scan_reference(dt, x, A, bm, cm, state):
    """T tokens of one row: ``dt`` and ``x`` (T, D) float32, 0 in ``dt``
    where a token is padding (it then neither decays nor feeds the state);
    ``A`` (N, D); ``bm`` / ``cm`` (T, N); ``state`` (N, D). Returns ``(y
    (T, D) float32, state after the T tokens)``."""
    def step(s, inp):
        d, xx, b, c = inp
        s = jnp.exp(d[None, :] * A) * s + (d * xx)[None, :] * b[:, None]
        return s, jnp.sum(s * c[:, None], axis=0)
    s, y = lax.scan(step, state.astype(F32), (dt, x, bm, cm))
    return y, s.astype(state.dtype)


def _scan_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, s_ref):
    a = a_ref[...]                                   # (N, db)

    def step(t, s):
        dt = dt_ref[pl.ds(t, 1), :]                  # (1, db)
        s = jnp.exp(dt * a) * s + (dt * x_ref[pl.ds(t, 1), :]) * b_ref[t]
        y_ref[pl.ds(t, 1), :] = jnp.sum(s * c_ref[t], axis=0, keepdims=True)
        return s
    s_ref[...] = lax.fori_loop(0, dt_ref.shape[0], step,
                               s0_ref[...].astype(F32)).astype(s_ref.dtype)


def mamba1_chunk_scan_kernel(dt, x, A, bm, cm, state,
                             lanes: int = SCAN_LANES):
    """Pallas chunk scan; the contract of
    :func:`mamba1_chunk_scan_reference`. A grid step takes ``lanes``
    channels through all T tokens, its state never leaving the core."""
    T, D = dt.shape
    N = A.shape[0]
    db = _fit(lanes, D, 128)
    rows = pl.BlockSpec((T, db), lambda j: (0, j))
    tile = pl.BlockSpec((N, db), lambda j: (0, j))
    column = pl.BlockSpec((T, N, 1), lambda j: (0, 0, 0))
    return pl.pallas_call(
        _scan_kernel,
        grid=(D // db,),
        in_specs=[rows, rows, tile, column, column, tile],
        out_specs=[rows, tile],
        out_shape=[jax.ShapeDtypeStruct((T, D), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_fa._interpret_mode(),
        name="mamba1_chunk_scan",
        metadata={"kernel": "mamba1_chunk_scan"},
    )(dt, x, A, bm[:, :, None], cm[:, :, None], state)


def mamba1_chunk_scan(dt, x, A, bm, cm, state, use_kernel=None):
    """The Pallas kernel on a real TPU (or when forced — interpret mode in
    tests), the ``jnp`` twin elsewhere."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    with jax.named_scope("mamba1_chunk_scan"):
        if use_kernel:
            return mamba1_chunk_scan_kernel(dt, x, A, bm, cm, state)
        return mamba1_chunk_scan_reference(dt, x, A, bm, cm, state)
