"""Sharded training step for the flagship LM.

Re-design of the reference's hybrid-parallel training loop (reference:
python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:820
train_batch; meta_optimizers/dygraph_optimizer/hybrid_parallel_optimizer.py:525
step; dygraph_sharding_optimizer.py ZeRO stage-1): one jitted SPMD program
per step. Optimizer state inherits each parameter's PartitionSpec, so with
"fsdp" in the mesh the master weights + Adam moments are ZeRO-sharded and
the gradient reduce-scatter / param all-gather are inserted by XLA GSPMD —
no EagerReducer (reference: paddle/fluid/distributed/collective/reducer.h:88)
bucket bookkeeping is needed.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import llama


class TrainState(NamedTuple):
    step: jax.Array
    params: Any           # model dtype (bf16) working copy
    master: Any           # fp32 master weights (AMP O2 parity)
    m: Any                # Adam first moment (fp32)
    v: Any                # Adam second moment (fp32)


def init_train_state(key: jax.Array, cfg, model=None) -> TrainState:
    params = (model if model is not None else llama).init_params(key, cfg)
    # copy=True: when the model dtype is already fp32, astype would alias
    # the param buffer and break donation (same buffer donated twice)
    master = jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                          params)
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return TrainState(jnp.zeros((), jnp.int32), params, master, zeros,
                      jax.tree.map(jnp.copy, zeros))


def state_specs(cfg, model=None) -> TrainState:
    ps = (model if model is not None else llama).param_specs(cfg)
    return TrainState(P(), ps, ps, ps, ps)


def _prune_spec(spec: P, mesh: Mesh) -> P:
    """Drop spec entries naming axes the mesh doesn't have (e.g. "fsdp"
    specs on a dp×cp×tp mesh) — that dimension replicates instead."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in mesh.axis_names else None)
    return P(*out)


def state_shardings(mesh: Mesh, cfg, model=None) -> TrainState:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _prune_spec(s, mesh)),
        state_specs(cfg, model), is_leaf=lambda x: isinstance(x, P))


def _adamw(g, p32, m, v, step, lr, b1, b2, eps, wd):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * (g * g)
    t = step.astype(jnp.float32) + 1.0
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    p32 = p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p32)
    return p32, m, v


def make_train_step(cfg, mesh: Optional[Mesh] = None, *,
                    lr: float = 3e-4, b1: float = 0.9, b2: float = 0.95,
                    eps: float = 1e-8, weight_decay: float = 0.1,
                    grad_clip: float = 1.0, data_axes=("dp", "fsdp"),
                    tp_axis="tp", cp_axis=None, ep_axis=None,
                    seq_chunk: Optional[int] = None, model=None):
    """Returns jitted ``step(state, tokens) -> (state, metrics)``.

    With a mesh: tokens sharded over ``data_axes`` (dp × fsdp batch
    sharding), params/opt-state per :func:`llama.param_specs` (tp + ZeRO),
    Megatron-SP activation constraints inside the model. ``cp_axis``: also
    shard the sequence dim over this axis and run ring attention (context
    parallelism) inside the step.
    """
    mesh_axes = None
    if mesh is not None:
        data = tuple(a for a in data_axes if a in mesh.axis_names)
        if not data:
            data = None
        mesh_axes = {"mesh": mesh,
                     "data": data if (data is None or len(data) != 1)
                     else data[0],
                     "tp": tp_axis if tp_axis in mesh.axis_names else None,
                     "cp": cp_axis if (cp_axis and
                                       cp_axis in mesh.axis_names) else None,
                     "ep": ep_axis if (ep_axis and
                                       ep_axis in mesh.axis_names) else None}

    mdl = model if model is not None else llama

    def loss(params, tokens):
        return mdl.loss_fn(params, tokens, cfg, mesh_axes,
                           seq_chunk=seq_chunk)

    def train_step(state: TrainState, tokens: jax.Array):
        lv, grads = jax.value_and_grad(loss)(state.params, tokens)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * scale, grads)

        def upd(g, p32, m, v):
            return _adamw(g, p32, m, v, state.step, lr, b1, b2, eps,
                          weight_decay)
        out = jax.tree.map(upd, grads, state.master, state.m, state.v)
        # tree of (p32, m, v) tuples -> three trees
        master = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        params = jax.tree.map(
            lambda p32, p: p32.astype(p.dtype), master, state.params)
        new_state = TrainState(state.step + 1, params, master, m, v)
        return new_state, {"loss": lv, "grad_norm": gnorm}

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,))

    st_sh = state_shardings(mesh, cfg, mdl)
    data_spec = P(mesh_axes["data"], mesh_axes["cp"])
    tok_sh = NamedSharding(mesh, data_spec)
    rep = NamedSharding(mesh, P())
    return jax.jit(train_step, donate_argnums=(0,),
                   in_shardings=(st_sh, tok_sh),
                   out_shardings=(st_sh, {"loss": rep, "grad_norm": rep}))
