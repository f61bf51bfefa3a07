"""Mixture-of-Experts feed-forward, TPU-first (GShard formulation).

Capability target: the reference's MoE stack (reference:
python/paddle/incubate/distributed/models/moe/moe_layer.py:263 MoELayer,
gates moe/gate/{gshard,switch,naive}_gate.py, alltoall dispatch
python/paddle/distributed/utils/moe_utils.py global_scatter:20 /
global_gather:153, fused python/paddle/incubate/nn/functional/fused_moe.py).

TPU-native design: capacity-based static-shape dispatch/combine as einsums
(the GShard/Mesh-TF lineage XLA was built around) instead of
variable-length NCCL alltoall. Experts carry a leading E axis sharded over
the "ep" mesh axis; the dispatch einsum reshards tokens→experts and XLA
lowers it to AllToAll over ICI. Router in fp32; top-1 (Switch) and top-2
(GShard) with load-balance aux loss + router z-loss.

This module is the TRAINER's expert layer (``llama._block`` calls
``moe_ffn``): a token over an expert's capacity is dropped, and the loop
over k is written for small k. The SERVING path does not come here:
``models/generate.py:_moe_ffn`` routes without capacity or drops and
applies the experts as one grouped matmul a projection. The two agree
where nothing is dropped (``capacity_factor >= num_experts / top_k``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """``num_experts`` and ``top_k`` are the model's and both paths read
    them; capacity and the loss weights are the trainer's router's alone
    (serving never drops a token). The routing rule, the expert's width
    and the shared expert are read by the serving expert layer
    (``models/generate.py:_moe_ffn``) alone."""
    num_experts: int = 8
    top_k: int = 2
    # how the chosen experts' weights are made: "softmax" over the chosen
    # logits, or "sigmoid" scores with a selection bias, the chosen scores
    # normalised and multiplied by ``routed_scale`` (``scoring_func``,
    # ``topk_method: noaux_tc``, ``routed_scaling_factor``)
    score: str = "softmax"
    routed_scale: float = 1.0
    # an expert's width where it is not the config's ``intermediate_size``
    # (``moe_intermediate_size`` beside a dense layer's width)
    expert_size: Optional[int] = None
    # width of the shared expert every token passes through (0: none;
    # ``moe_intermediate_size * n_shared_experts``)
    shared_size: int = 0
    capacity_factor: float = 1.25
    min_capacity: int = 4
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3

    def capacity(self, tokens_per_batch: int) -> int:
        c = int(tokens_per_batch * self.capacity_factor * self.top_k /
                self.num_experts)
        return max(c, self.min_capacity)


def router(x: jax.Array, w_gate: jax.Array, cfg: MoEConfig,
           ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x (T, H) -> (dispatch (T, E, C), combine (T, E, C), aux_losses).

    Dispatch/combine tensors are the GShard one-hot forms consumed by the
    dispatch/combine einsums. fp32 routing math.
    """
    T, H = x.shape
    E, K, C = cfg.num_experts, cfg.top_k, cfg.capacity(x.shape[0])
    logits = (x.astype(jnp.float32) @ w_gate.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k selection, sequential (K small: 1 or 2)
    combine = jnp.zeros((T, E, C), jnp.float32)
    dispatch = jnp.zeros((T, E, C), jnp.bool_)
    remaining = probs
    # position counters per expert accumulate across the k passes
    base_fill = jnp.zeros((E,), jnp.int32)
    total_weight = jnp.zeros((T,), jnp.float32)
    sel_masks = []
    for _ in range(K):
        idx = jnp.argmax(remaining, axis=-1)               # (T,)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (T, E)
        sel_masks.append(onehot)
        # position within the expert buffer (tokens in order; capacity drop)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # (T, E)
        pos = pos + base_fill[None, :] * onehot
        keep = (pos < C) & (onehot > 0)                     # (T, E)
        w = probs * onehot * keep                            # gate weight
        posc = jnp.clip(pos.astype(jnp.int32), 0, C - 1)
        oh_c = jax.nn.one_hot(posc, C, dtype=jnp.float32) * keep[..., None]
        combine = combine + w[..., None] * oh_c
        dispatch = dispatch | (oh_c > 0)
        total_weight = total_weight + jnp.sum(w, axis=-1)
        base_fill = base_fill + jnp.sum(onehot * keep, axis=0).astype(
            jnp.int32)
        remaining = remaining * (1.0 - onehot)

    # renormalize combine weights over the selected experts
    denom = jnp.where(total_weight == 0.0, 1.0, total_weight)
    combine = combine / denom[:, None, None]

    # aux losses (Switch Transformer formulation)
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(sel_masks[0], axis=0)                      # top-1 counts
    aux = cfg.aux_loss_weight * E * jnp.sum(me * ce)
    z = cfg.z_loss_weight * jnp.mean(
        jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    losses = {"aux_loss": aux, "z_loss": z}
    return dispatch.astype(x.dtype), combine.astype(jnp.float32), logits, \
        losses


def moe_ffn(x: jax.Array, params: Dict[str, jax.Array], cfg: MoEConfig,
            rms_eps_unused: float = 0.0, mesh_axes=None,
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """SwiGLU expert FFN. x: (B, S, H); params: w_gate (H, E),
    wg/wu (E, H, I), wd (E, I, H). Returns (out (B, S, H), aux losses)."""
    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    dispatch, combine, _, losses = router(xt, params["w_gate"], cfg)
    # tokens -> expert buffers: (T,E,C)x(T,H) -> (E,C,H); with E sharded
    # over "ep" XLA lowers this to an AllToAll over ICI
    buf = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
    ec = _expert_constraint(mesh_axes)
    buf = ec(buf)
    g = jax.nn.silu(jnp.einsum("ech,ehi->eci", buf, params["wg"]
                               ).astype(jnp.float32)).astype(x.dtype)
    u = jnp.einsum("ech,ehi->eci", buf, params["wu"])
    out = jnp.einsum("eci,eih->ech", g * u, params["wd"])
    out = ec(out)
    # combine back to token order with gate weights
    y = jnp.einsum("tec,ech->th", combine.astype(x.dtype), out)
    return y.reshape(B, S, H), losses


def _expert_constraint(mesh_axes):
    if not mesh_axes or not mesh_axes.get("ep"):
        return lambda t: t
    from jax.sharding import NamedSharding

    def f(t):
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh_axes["mesh"],
                             P(mesh_axes["ep"], None, None)))
    return f


def init_moe_params(key: jax.Array, hidden: int, intermediate: int,
                    cfg: MoEConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    import math
    k = jax.random.split(key, 4)
    E = cfg.num_experts

    def norm(kk, shape, fan_in):
        return (jax.random.normal(kk, shape, jnp.float32) /
                math.sqrt(fan_in)).astype(dtype)

    return {
        "w_gate": norm(k[0], (hidden, E), hidden).astype(jnp.float32),
        "wg": norm(k[1], (E, hidden, intermediate), hidden),
        "wu": norm(k[2], (E, hidden, intermediate), hidden),
        "wd": norm(k[3], (E, intermediate, hidden), intermediate),
    }


def moe_param_specs() -> Dict[str, P]:
    """Experts sharded over "ep"; within-expert dims over fsdp/tp."""
    return {
        "w_gate": P(None, None),
        "wg": P("ep", "fsdp", "tp"),
        "wu": P("ep", "fsdp", "tp"),
        "wd": P("ep", "tp", "fsdp"),
    }
