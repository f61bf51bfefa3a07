"""One-token update of a pool of Mamba-2 recurrent state, in place.

A decode step advances every live row's state by one token in every
state-space layer: ``S <- exp(dt A) S + (dt x) B^T`` and ``y = S C`` a head,
with ``S`` (head_dim, state) float32. At the published widths (128 heads x
64 x 128) that is 4.2 MB a row-layer read and written, 5.4 GB a step at 128
rows and 5 layers, and nothing else in the layer comes near it: the work is
moving the state once.

The pool is ``(layers * slots, P, N, H)``: heads LAST, so that what is a
scalar a head (the decay, ``dt``) is a lane vector that broadcasts over the
``(N, H)`` tile as it lies, ``dt x`` a row of ``(P, H)`` broadcast down the
sublanes, and ``y`` a sublane reduction. No operand is relaid in the kernel.

Two implementations of one contract:

- :func:`ssm_state_update_kernel` — Pallas TPU kernel. The pool is aliased
  to the result and only the blocks of the ``B`` rows from ``base`` on are
  touched, each read once and written once; the layer's slice of the pool
  is never copied out. A row that is not ``active`` is written back as read.
- :func:`ssm_state_update_reference` — ``jnp`` over the same arguments: the
  path off the chip, and what the kernel is tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa

# rows of head_dim a block: (16, N, H) float32 is 1 MB at the published sizes
_PB = 16


def ssm_state_update_reference(pool, base, dtx, decay, bx, cx, active):
    """``pool`` (R, P, N, H) state; rows ``base .. base + B`` advance one
    token: ``dtx`` (B, P, H) is ``dt * x``, ``decay`` (B, H) is
    ``exp(dt * A)``, ``bx`` / ``cx`` (B, N, H) the row's B and C spread to
    heads, all float32; ``active`` (B,) bool. Returns ``(y (B, P, H)
    float32, pool)``; an inactive row's state stays and its ``y`` is
    whatever the stale state gives."""
    B = dtx.shape[0]
    s = lax.dynamic_slice_in_dim(pool, base, B, 0).astype(jnp.float32)
    new = (s * decay[:, None, None, :]
           + dtx[:, :, None, :] * bx[:, None, :, :])
    y = jnp.sum(new * cx[:, None, :, :], axis=2)
    new = jnp.where(active[:, None, None, None], new, s)
    return y, lax.dynamic_update_slice_in_dim(
        pool, new.astype(pool.dtype), base, 0)


def _kernel(base_ref, act_ref, s_ref, dtx_ref, dec_ref, b_ref, c_ref,
            y_ref, o_ref, *, pb):
    live = act_ref[pl.program_id(0)] != 0
    dec, bx, cx = dec_ref[0], b_ref[0], c_ref[0]     # (1, H), (N, H), (N, H)
    for p in range(pb):
        s = s_ref[0, p].astype(jnp.float32)          # (N, H)
        new = s * dec + dtx_ref[0, p:p + 1, :] * bx
        y_ref[0, p:p + 1, :] = jnp.sum(new * cx, axis=0, keepdims=True)
        o_ref[0, p] = jnp.where(live, new, s).astype(o_ref.dtype)


def ssm_state_update_kernel(pool, base, dtx, decay, bx, cx, active):
    """Pallas state update; the contract of
    :func:`ssm_state_update_reference`."""
    B, P, H = dtx.shape
    N = bx.shape[1]
    pb = _PB if P % _PB == 0 else P
    row = lambda r, p, base, act: (base[0] + r, p, 0, 0)
    per_row = lambda r, p, *_: (r, 0, 0)
    y, new = pl.pallas_call(
        functools.partial(_kernel, pb=pb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, P // pb),
            in_specs=[pl.BlockSpec((1, pb, N, H), row),
                      pl.BlockSpec((1, pb, H), lambda r, p, *_: (r, p, 0)),
                      pl.BlockSpec((1, 1, H), per_row),
                      pl.BlockSpec((1, N, H), per_row),
                      pl.BlockSpec((1, N, H), per_row)],
            out_specs=[pl.BlockSpec((1, pb, H), lambda r, p, *_: (r, p, 0)),
                       pl.BlockSpec((1, pb, N, H), row)]),
        out_shape=[jax.ShapeDtypeStruct((B, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 2 (after the two prefetched scalars) is the pool
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_fa._interpret_mode(),
        name="ssm_state_update",
        metadata={"kernel": "ssm_state_update"},
    )(jnp.asarray(base, jnp.int32).reshape(1), active.astype(jnp.int32),
      pool, dtx, decay[:, None, :], bx, cx)
    return y, new


def ssm_state_update(pool, base, dtx, decay, bx, cx, active,
                     use_kernel=None):
    """The Pallas kernel on a real TPU (or when forced — interpret mode in
    tests), the ``jnp`` twin elsewhere."""
    if use_kernel is None:
        use_kernel = _fa.on_tpu()
    with jax.named_scope("ssm_state_update"):
        if use_kernel:
            return ssm_state_update_kernel(pool, base, dtx, decay, bx, cx,
                                           active)
        return ssm_state_update_reference(pool, base, dtx, decay, bx, cx,
                                          active)
