"""``model_type: nemotron_h`` for the serving driver ``drivers/serve_arch.py``:
the configuration file's keys as the program's ``LlamaConfig`` (a hybrid
model: Mamba-2, attention and expert layers, one part a layer), seeded
weights in the program's layout (a stack per layer kind; the expert stacks
hold the experts this chip holds), and the plain reference to compare with.

The file's ``n_routed_experts`` is the number of routed experts HELD HERE,
experts ``first_expert_held`` onwards; the router keeps ``router_outputs``
outputs and chooses ``num_experts_per_tok`` of them."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from ..reference import nemotron_h as reference  # noqa: F401 — the driver's hook

KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}


def period(c: Dict):
    """The shortest run of layer kinds that, repeated, gives the first
    ``num_hidden_layers`` entries of the published pattern."""
    kinds = [KINDS[t] for t in
             c["hybrid_override_pattern"][:c["num_hidden_layers"]]]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return tuple(kinds[:p])


def program_config(c: Dict, max_len: int, remat: bool = True):
    """bf16, every width as published. A program without hybrid layers (the
    one before they were added) has no ``HybridConfig`` and fails here."""
    from paddle_tpu.models import llama
    from paddle_tpu.models.moe import MoEConfig
    if c["mamba_num_heads"] * c["mamba_head_dim"] != c["expand"] * c["hidden_size"]:
        raise ValueError("nemotron_h: d_inner is heads x head_dim = expand x hidden")
    if c["n_group"] != 1 or c["topk_group"] != 1 or c["n_shared_experts"] != 1:
        raise ValueError("nemotron_h: one routing group, one shared expert")
    hybrid = llama.HybridConfig(
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_groups=c["n_groups"], ssm_state=c["ssm_state_size"],
        conv_kernel=c["conv_kernel"], chunk_size=c["chunk_size"],
        latent_size=c["moe_latent_size"],
        expert_size=c["moe_intermediate_size"],
        shared_size=c["moe_shared_expert_intermediate_size"],
        routed_scale=float(c["routed_scaling_factor"]))
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        max_seq_len=max_len, rms_eps=c["norm_eps"], dtype=jnp.bfloat16,
        tie_embeddings=c["tie_word_embeddings"], remat=remat,
        moe=MoEConfig(num_experts=c["router_outputs"],
                      top_k=c["num_experts_per_tok"]),
        layer_pattern=period(c), hybrid=hybrid)


def shapes(c: Dict) -> Dict:
    """leaf -> (shape, rule): a fan-in, "norm", "embed", or a leaf's own."""
    h, v = c["hidden_size"], c["vocab_size"]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    H, K = c["mamba_num_heads"], c["conv_kernel"]
    di = H * c["mamba_head_dim"]
    cd = di + 2 * c["n_groups"] * c["ssm_state_size"]
    E, El = c["router_outputs"], c["n_routed_experts"]
    lat, ie, ish = (c["moe_latent_size"], c["moe_intermediate_size"],
                    c["moe_shared_expert_intermediate_size"])
    pattern = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
    Lm, La, Le = (pattern.count(t) for t in "M*E")
    return {
        "embed": ((v, h), "embed"), "final_norm": ((h,), "norm"),
        "lm_head": ((h, v), h),
        "layers": {
            "mamba2": {"norm": ((Lm, h), "norm"),
                       "w_in": ((Lm, h, di + cd + H), h),
                       "conv_w": ((Lm, K, cd), K),
                       "conv_b": ((Lm, cd), "small"),
                       "dt_bias": ((Lm, H), "dt_bias"),
                       "A_log": ((Lm, H), "A_log"), "D": ((Lm, H), "ones"),
                       "gate_norm": ((Lm, di), "norm"),
                       "w_out": ((Lm, di, h), di)},
            "attention": {"norm": ((La, h), "norm"),
                          "wq": ((La, h, nh * hd), h),
                          "wk": ((La, h, nkv * hd), h),
                          "wv": ((La, h, nkv * hd), h),
                          "wo": ((La, nh * hd, h), nh * hd)},
            "experts": {"norm": ((Le, h), "norm"),
                        "router": ((Le, h, E), h),
                        "router_bias": ((Le, E), "router_bias"),
                        "w_down": ((Le, h, lat), h),
                        "w_up": ((Le, lat, h), lat),
                        "w1": ((Le, El, lat, ie), lat),
                        "w2": ((Le, El, ie, lat), ie),
                        "ws1": ((Le, h, ish), h), "ws2": ((Le, ish, h), ish),
                        "first_expert": ((Le,), "first_expert")}}}


F32_LEAVES = ("router", "router_bias", "dt_bias", "A_log", "D")


def weights(key: jax.Array, c: Dict, dtype=jnp.bfloat16) -> Dict:
    """The distributions the configuration file's ``assumed.weights``
    states. Call under ``jax.jit``. A stack of layers is drawn a layer at a
    time, so that the float32 draw of an expert stack (7 GB a leaf at once)
    is never whole."""
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=is_leaf)
    out = []
    for k, (path, (shape, kind)) in zip(
            jax.random.split(key, len(with_paths)), with_paths):
        name = path[-1].key
        to = jnp.float32 if name in F32_LEAVES else dtype

        def draw(kk, shape=shape, kind=kind, to=to):
            if kind == "ones":
                return jnp.ones(shape, to)
            if kind == "first_expert":
                return jnp.full(shape, c["first_expert_held"], jnp.int32)
            if kind == "A_log":
                return jnp.log(jax.random.uniform(kk, shape, jnp.float32,
                                                  1.0, 16.0))
            if kind == "dt_bias":
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    kk, shape, jnp.float32, math.log(c["time_step_min"]),
                    math.log(c["time_step_max"]))), c["time_step_floor"])
                return dt + jnp.log(-jnp.expm1(-dt))
            n = jax.random.normal(kk, shape, jnp.float32)
            if kind == "norm":
                return (1.0 + 0.1 * n).astype(to)
            scale = {"embed": 0.02, "small": 0.02, "router_bias": 0.01}.get(
                kind) or kind ** -0.5
            return (n * scale).astype(to)
        if "layers" in str(path[0]) and len(shape) > 2:
            out.append(jax.lax.map(lambda kk: draw(kk, shape=shape[1:]),
                                   jax.random.split(k, shape[0])))
        else:
            out.append(draw(k))
    return jax.tree.unflatten(treedef, out)
