"""Pipeline-parallel training step for the flagship LM.

The reference runs PP as a multi-process 1F1B engine with eager NCCL p2p
(reference: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py:575 forward_backward_pipeline, interleave :1174;
passes/pipeline_scheduler_pass/pipeline_zero_bubble.py). TPU-native, the
pipeline is ONE jitted SPMD program: decoder layers live stacked (L, ...)
with the L dim sharded over the "pp" mesh axis, each pp coordinate applies
its L/P-layer stage, and activations hop the pp ring via ppermute inside a
lax.scan wavefront (meta_parallel/pp_spmd.py). AD through the scan gives
the reverse wavefront — the backward schedule the reference hand-codes.

Composes with dp (batch axis) and tp (param specs) on the same mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import llama
from .train import TrainState, _adamw, init_train_state, state_specs


def state_shardings_pp(mesh: Mesh, cfg: llama.LlamaConfig,
                       pp_axis: str = "pp") -> TrainState:
    """Like train.state_shardings but the layer-stack dim shards over pp
    (each pipeline stage owns its own layers' weights + opt state)."""
    from .train import _prune_spec

    def fix(path_spec):
        return P(pp_axis, *path_spec[1:])

    base = state_specs(cfg)

    def map_state(specs):
        out = dict(specs)
        out["layers"] = {k: fix(s) for k, s in specs["layers"].items()}
        return out

    sp = TrainState(base.step, map_state(base.params), map_state(base.master),
                    map_state(base.m), map_state(base.v))
    return jax.tree.map(lambda s: NamedSharding(mesh, _prune_spec(s, mesh)),
                        sp, is_leaf=lambda x: isinstance(x, P))


def interleave_layer_perm(cfg: llama.LlamaConfig, num_stages: int,
                          num_chunks: int) -> "jnp.ndarray":
    """Storage permutation for the interleaved (VPP) schedule: device d
    must hold its num_chunks non-adjacent virtual stages contiguously, so
    the state stores layers device-major ([d, c] order) and the step's
    reshape to [P, v, layers/chunk] is zero-cost (no cross-shard moves).

    ``params["layers"] = tree.map(lambda a: a[perm], layers)`` converts
    canonical order to storage order; ``jnp.argsort(perm)`` converts back
    (checkpoint IO should store canonical order).
    """
    L = cfg.num_layers
    lc = L // (num_stages * num_chunks)
    idx = []
    for d in range(num_stages):
        for c in range(num_chunks):
            s = c * num_stages + d
            idx.extend(range(s * lc, (s + 1) * lc))
    return jnp.asarray(idx)


def _permute_layer_stacks(state: TrainState, idx, cfg, mesh,
                          pp_axis: str) -> TrainState:
    """Apply a layer-dim index to every layer stack of the state and
    re-place on the pp shardings (the permuting gather drops them)."""
    reorder = lambda tr: {
        **tr, "layers": jax.tree.map(lambda a: a[idx], tr["layers"])}
    st = TrainState(state.step, reorder(state.params),
                    reorder(state.master), reorder(state.m),
                    reorder(state.v))
    return jax.device_put(st, state_shardings_pp(mesh, cfg, pp_axis))


def to_interleave_storage(state: TrainState, cfg: llama.LlamaConfig,
                          mesh: Mesh, num_chunks: int,
                          pp_axis: str = "pp") -> TrainState:
    """Permute a CANONICAL-layer-order train state into the round-robin
    storage order the interleaved schedules require. Checkpoints should
    store canonical order: apply this after load / before the first
    interleaved step."""
    perm = interleave_layer_perm(cfg, mesh.shape[pp_axis], num_chunks)
    return _permute_layer_stacks(state, perm, cfg, mesh, pp_axis)


def from_interleave_storage(state: TrainState, cfg: llama.LlamaConfig,
                            mesh: Mesh, num_chunks: int,
                            pp_axis: str = "pp") -> TrainState:
    """Inverse of :func:`to_interleave_storage` — storage order back to
    canonical (what checkpoint IO should persist)."""
    perm = interleave_layer_perm(cfg, mesh.shape[pp_axis], num_chunks)
    return _permute_layer_stacks(state, jnp.argsort(perm), cfg, mesh,
                                 pp_axis)


def make_train_step_pp(cfg: llama.LlamaConfig, mesh: Mesh, *,
                       num_microbatches: int, schedule: str = "gpipe",
                       num_chunks: int = 1, pp_axis: str = "pp",
                       dp_axis: str = "dp", lr: float = 3e-4,
                       b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                       weight_decay: float = 0.1, grad_clip: float = 1.0):
    """jitted ``step(state, tokens) -> (state, metrics)`` pipelined over
    ``pp_axis`` with the selected schedule (pp_spmd module docstring):
    "gpipe" AD wavefront, "interleave" VPP AD backward (state must be in
    ``interleave_layer_perm`` storage order), "interleave_1f1b" VPP with
    the hand-written depth-bounded backward (same storage order; the
    schedule for VPP at scale — AD-VPP's residency grows with M),
    "1f1b" depth-bounded residency, "zero_bubble" 1F1B with deferred dW,
    "vpp_zb" ZB-V (interleaved 1F1B with deferred dW: the VPP bubble AND
    dW off the serialized tick path).
    Batch dim must divide num_microbatches.
    """
    assert schedule in ("gpipe", "interleave", "interleave_1f1b",
                        "vpp_zb", "1f1b", "zero_bubble")
    num_stages = mesh.shape[pp_axis]
    chunked = schedule in ("interleave", "interleave_1f1b", "vpp_zb")
    nseg = num_stages * (num_chunks if chunked else 1)
    assert cfg.num_layers % nseg == 0
    lp_per_stage = cfg.num_layers // nseg
    dp = dp_axis if dp_axis in mesh.axis_names else None

    # pp × MoE composition: the MoE load-balance aux loss must (a) reach
    # the final loss and (b) backprop into each stage's router — but the
    # pipeline carry is ONE static-shape array. The aux scalar rides IN
    # the carry as one extra sequence position (spread uniformly over the
    # hidden dim so its bf16 transport keeps ~0.4% relative precision on
    # a regularizer term): stages slice the real activations, run their
    # blocks, add their aux into the extra row, and re-concat. Works
    # identically under every schedule (gpipe AD, 1F1B, zero-bubble, VPP)
    # because gradients flow through the slice/concat like any other op.
    # Reference capability: pp+EP hybrid (fleet hybrid_configs with moe;
    # experts shard over an "ep" mesh axis via the param specs).
    moe_aux = cfg.moe is not None

    from ..distributed.fleet.meta_parallel.pp_spmd import (
        pipeline_spmd, pipeline_interleave, pipeline_1f1b,
        pipeline_interleave_1f1b)

    attn_axes = {"mesh": mesh, "data": dp,
                 "tp": "tp" if "tp" in mesh.axis_names else None}

    def make_stage_fn(cos, sin):
        def stage_fn(stage_params, xin):
            x = xin[:, :-1] if moe_aux else xin

            def body(c, lp):
                y, aux = llama._block(c, lp, cos, sin, cfg, None,
                                      attn_axes=attn_axes)
                return y, aux
            y, auxs = lax.scan(body, x, stage_params)
            if not moe_aux:
                return y
            aux_row = xin[:, -1:] + (jnp.sum(auxs) /
                                     xin[:, -1:].size).astype(xin.dtype)
            return jnp.concatenate([y, aux_row], axis=1)
        return stage_fn

    def head_of(params):
        return params["embed"].T if cfg.tie_embeddings else \
            params["lm_head"]

    def _split_aux(y):
        """(activations, accumulated aux scalar) from a carry."""
        if not moe_aux:
            return y, jnp.float32(0.0)
        return y[:, :-1], jnp.sum(y[:, -1:].astype(jnp.float32))

    def _augment(x):
        """Append the zeroed aux row to embedded microbatch activations."""
        if not moe_aux:
            return x
        pad = jnp.zeros(x.shape[:-2] + (1, x.shape[-1]), x.dtype)
        return jnp.concatenate([x, pad], axis=-2)

    def head_loss(hp, y, label):
        y, aux = _split_aux(y)
        h = llama.rms_norm(y, hp["final_norm"], cfg.rms_eps)
        logits = (h @ hp["head"].astype(h.dtype)).astype(jnp.float32)
        ce = llama._ce(logits[:, :-1], label[:, 1:])
        return jnp.mean(ce) + aux

    def loss(params, tokens):
        B, S = tokens.shape
        M = num_microbatches
        mb = B // M
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        cos, sin = llama.rope_tables(S, cfg.hd, cfg.rope_theta)
        stage_fn = make_stage_fn(cos, sin)

        if schedule == "interleave":
            stacked = jax.tree.map(
                lambda a: a.reshape(num_stages, num_chunks, lp_per_stage,
                                    *a.shape[1:]),
                params["layers"])
            mbs = _augment(x.reshape(M, mb, S, cfg.hidden_size))
            outs = pipeline_interleave(stage_fn, stacked, mbs, mesh,
                                       num_chunks, pp_axis)
        else:
            stacked = jax.tree.map(
                lambda a: a.reshape(num_stages, lp_per_stage,
                                    *a.shape[1:]),
                params["layers"])
            mbs = _augment(x.reshape(M, mb, S, cfg.hidden_size))
            outs = pipeline_spmd(stage_fn, stacked, mbs, mesh, pp_axis)
        if moe_aux:
            # per-microbatch aux rows -> mean over microbatches (same
            # accounting as the per-microbatch head_loss path)
            aux = jnp.sum(outs[:, :, -1:].astype(jnp.float32)) / M
            outs = outs[:, :, :-1]
        else:
            aux = jnp.float32(0.0)
        outs = outs.reshape(B, S, cfg.hidden_size)
        return _full_head_loss(params, outs, tokens) + aux

    def _full_head_loss(params, outs, tokens):
        h = llama.rms_norm(outs, params["final_norm"], cfg.rms_eps)
        head = head_of(params)
        logits = (h @ head.astype(h.dtype)).astype(jnp.float32)[:, :-1]
        ce = llama._ce(logits, tokens[:, 1:])
        return jnp.mean(ce)

    def loss_and_grads_1f1b(params, tokens):
        B, S = tokens.shape
        M = num_microbatches
        mb = B // M
        cos, sin = llama.rope_tables(S, cfg.hd, cfg.rope_theta)
        stage_fn = make_stage_fn(cos, sin)

        def embed_fn(emb):
            x = jnp.take(emb, tokens, axis=0).astype(cfg.dtype)
            return _augment(x.reshape(M, mb, S, cfg.hidden_size))

        mbs, vjp_embed = jax.vjp(embed_fn, params["embed"])
        labels = tokens.reshape(M, mb, S)
        hp = {"final_norm": params["final_norm"], "head": head_of(params)}
        if schedule in ("interleave_1f1b", "vpp_zb"):
            # [P, C, layers/chunk, ...] round-robin storage order
            # (state must be in interleave_layer_perm order, as for
            # "interleave"); "vpp_zb" = ZB-V, deferred dW at the VPP
            # bubble
            stacked = jax.tree.map(
                lambda a: a.reshape(num_stages, num_chunks, lp_per_stage,
                                    *a.shape[1:]),
                params["layers"])
            lv, d_stacked, d_head, d_mbs = pipeline_interleave_1f1b(
                stage_fn, head_loss, stacked, hp, mbs, labels, mesh,
                num_chunks, pp_axis, defer_dw=(schedule == "vpp_zb"))
        else:
            stacked = jax.tree.map(
                lambda a: a.reshape(num_stages, lp_per_stage,
                                    *a.shape[1:]),
                params["layers"])
            lv, d_stacked, d_head, d_mbs = pipeline_1f1b(
                stage_fn, head_loss, stacked, hp, mbs, labels, mesh,
                pp_axis, defer_dw=(schedule == "zero_bubble"))
        d_embed = vjp_embed(d_mbs.astype(mbs.dtype))[0].astype(jnp.float32)
        # flatten the stage dims back to [L, ...] in STORAGE order (the
        # same contiguous reinterpretation the forward reshape used)
        lead = 3 if schedule in ("interleave_1f1b", "vpp_zb") else 2
        grads = {
            "embed": d_embed + (d_head["head"].T if cfg.tie_embeddings
                                else 0.0),
            "layers": jax.tree.map(
                lambda a: a.reshape(cfg.num_layers, *a.shape[lead:]),
                d_stacked),
            "final_norm": d_head["final_norm"],
        }
        if not cfg.tie_embeddings:
            grads["lm_head"] = d_head["head"]
        return lv, grads

    def step_fn(state: TrainState, tokens):
        if schedule in ("1f1b", "zero_bubble", "interleave_1f1b",
                        "vpp_zb"):
            lv, grads = loss_and_grads_1f1b(state.params, tokens)
        else:
            lv, grads = jax.value_and_grad(loss)(state.params, tokens)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * scale, grads)

        def upd(g, p32, m, v):
            return _adamw(g, p32, m, v, state.step, lr, b1, b2, eps,
                          weight_decay)
        out = jax.tree.map(upd, grads, state.master, state.m, state.v)
        master = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        params = jax.tree.map(lambda p32, p: p32.astype(p.dtype), master,
                              state.params)
        return (TrainState(state.step + 1, params, master, m, v),
                {"loss": lv, "grad_norm": gnorm})

    st_sh = state_shardings_pp(mesh, cfg, pp_axis)
    tok_sh = NamedSharding(mesh, P(dp))
    rep = NamedSharding(mesh, P())
    return jax.jit(step_fn, donate_argnums=(0,),
                   in_shardings=(st_sh, tok_sh),
                   out_shardings=(st_sh, {"loss": rep, "grad_norm": rep}))
