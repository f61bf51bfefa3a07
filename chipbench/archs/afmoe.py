"""``model_type: afmoe`` for the serving driver ``drivers/serve_arch.py``:
the configuration file's keys as the program's ``LlamaConfig`` (a K/V
decoder with gated attention, QK-norm, sliding layers with rotary and full
layers without, sandwich norms, an input scale, leading dense layers, then
expert layers with sigmoid routing and a shared expert), seeded weights in
the program's layout (``dense_layers`` the leading layers' stack, ``layers``
the expert layers'; the expert stacks hold the experts this chip holds), and
the plain reference to compare with.

The file runs layers ``first_layer_run .. + num_hidden_layers`` of the
published ``layer_types``; those below ``num_dense_layers`` are dense. Its
``num_experts`` is the number of routed experts HELD HERE, experts
``first_expert_held`` onwards; the router keeps ``router_outputs`` outputs
and chooses ``num_experts_per_tok`` of them."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from ..reference import afmoe as reference  # noqa: F401 — the driver's hook

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}
F32_LEAVES = ("moe_gate", "moe_bias")


def _period(kinds):
    """The shortest run of kinds that, repeated, gives ``kinds``."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return tuple(kinds[:p])


def program_config(c: Dict, max_len: int, remat: bool = True):
    """bf16, every width as published. A program without the K/V decoder's
    gate, QK-norm, NoPE layers, sandwich norms, input scale and dense
    prologue (the one before they were added) fails here."""
    from paddle_tpu.models import llama
    from paddle_tpu.models.moe import MoEConfig
    if (c["score_func"] != "sigmoid" or not c["route_norm"]
            or c["n_group"] != 1 or c["topk_group"] != 1
            or c["rope_scaling"] is not None):
        raise ValueError("afmoe: sigmoid scores with a selection bias, "
                         "normalised, one routing group; rotary unscaled")
    first, L = c["first_layer_run"], c["num_hidden_layers"]
    kinds = [KINDS[t] for t in c["layer_types"][first:first + L]]
    lead = min(max(c["num_dense_layers"] - first, 0), L)
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"], num_layers=L,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        max_seq_len=max_len, rope_theta=float(c["rope_theta"]),
        rms_eps=c["rms_norm_eps"], dtype=jnp.bfloat16,
        tie_embeddings=c["tie_word_embeddings"], remat=remat,
        moe=MoEConfig(num_experts=c["router_outputs"],
                      top_k=c["num_experts_per_tok"], score="sigmoid",
                      routed_scale=float(c["route_scale"]),
                      expert_size=c["moe_intermediate_size"],
                      shared_size=(c["moe_intermediate_size"]
                                   * c["num_shared_experts"])),
        layer_pattern=_period(kinds[lead:]), sliding_window=c["sliding_window"],
        dense_layers=lead, dense_kinds=tuple(kinds[:lead]),
        nope_kinds=tuple(KINDS[t] for t in c["nope_layer_types"]),
        qk_norm=c["qk_norm"], attn_gate=c["attn_output_gate"],
        sandwich_norms=c["sandwich_norms"],
        embed_scale=(math.sqrt(c["hidden_size"]) if c["mup_enabled"]
                     else None))


def weights(key: jax.Array, c: Dict, dtype=jnp.bfloat16) -> Dict:
    """The distributions the configuration file's ``assumed.weights``
    states, in the leaves ``llama.kv_param_shapes`` names. Call under
    ``jax.jit``. A stack of layers is drawn a layer at a time, so that the
    float32 draw of an expert stack is never whole."""
    from paddle_tpu.models import llama
    shapes = llama.kv_param_shapes(program_config(c, 1),
                                   experts_held=c["num_experts"])
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=is_leaf)
    out = []
    for k, (path, (shape, kind)) in zip(
            jax.random.split(key, len(with_paths)), with_paths):
        to = jnp.float32 if path[-1].key in F32_LEAVES else dtype

        def draw(kk, shape=shape, kind=kind, to=to):
            if kind == "first_expert":
                return jnp.full(shape, c["first_expert_held"], jnp.int32)
            n = jax.random.normal(kk, shape, jnp.float32)
            if kind == "norm":
                return (1.0 + 0.1 * n).astype(to)
            scale = {"embed": 0.02, "router_bias": 0.01}.get(kind) \
                or kind ** -0.5
            return (n * scale).astype(to)
        if "layers" in str(path[0]) and len(shape) > 2:
            out.append(jax.lax.map(lambda kk: draw(kk, shape=shape[1:]),
                                   jax.random.split(k, shape[0])))
        else:
            out.append(draw(k))
    return jax.tree.unflatten(treedef, out)
