"""``model_type: mellum`` for the serving driver ``drivers/serve_arch.py``:
the configuration file's keys as the program's ``LlamaConfig``, seeded
weights in the program's layout, and the plain reference to compare with.
A new architecture is one such module, named after its ``model_type``."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..reference import mellum as reference  # noqa: F401 — the driver's hook

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def period(c: Dict):
    """The shortest run of layer kinds that, repeated, gives the first
    ``num_hidden_layers`` entries of the published ``layer_types``."""
    kinds = [KINDS[t] for t in c["layer_types"][:c["num_hidden_layers"]]]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return tuple(kinds[:p])


def program_config(c: Dict, max_len: int, remat: bool = True):
    """bf16, every width as published; an expert's width is the program's
    ``intermediate_size`` (``intermediate_size`` of the file, 7168, is the
    dense width no layer of this model has)."""
    from paddle_tpu.models import llama
    from paddle_tpu.models.moe import MoEConfig
    if set(c["mlp_layer_types"][:c["num_hidden_layers"]]) != {"sparse"}:
        raise ValueError("mellum: every MLP of the published model is sparse")
    full = c["rope_parameters"]["full_attention"]
    slide = c["rope_parameters"]["sliding_attention"]
    if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
        raise ValueError("mellum: yarn on full layers, plain on sliding ones")
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["moe_intermediate_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        max_seq_len=max_len, rope_theta=float(full["rope_theta"]),
        rope_theta_sliding=float(slide["rope_theta"]),
        yarn=llama.YarnRope(
            factor=full["factor"],
            original_max_position_embeddings=full[
                "original_max_position_embeddings"],
            beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
            attention_factor=full.get("attention_factor")),
        rms_eps=c["rms_norm_eps"], dtype=jnp.bfloat16,
        tie_embeddings=c["tie_word_embeddings"], remat=remat,
        moe=MoEConfig(num_experts=c["num_experts"],
                      top_k=c["num_experts_per_tok"]),
        layer_pattern=period(c), sliding_window=c["sliding_window"])


def shapes(c: Dict) -> Dict:
    """leaf -> (shape, fan-in | "norm" | None for the embedding)."""
    h, i, v, L = (c["hidden_size"], c["moe_intermediate_size"],
                  c["vocab_size"], c["num_hidden_layers"])
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    E = c["num_experts"]
    return {"embed": ((v, h), None), "final_norm": ((h,), "norm"),
            "lm_head": ((h, v), h),
            "layers": {"wq": ((L, h, nh * hd), h), "wk": ((L, h, nkv * hd), h),
                       "wv": ((L, h, nkv * hd), h),
                       "wo": ((L, nh * hd, h), nh * hd),
                       "attn_norm": ((L, h), "norm"),
                       "mlp_norm": ((L, h), "norm"),
                       "moe_gate": ((L, h, E), h),
                       "moe_wg": ((L, E, h, i), h), "moe_wu": ((L, E, h, i), h),
                       "moe_wd": ((L, E, i, h), i)}}


def weights(key: jax.Array, c: Dict, dtype=jnp.bfloat16) -> Dict:
    """The distributions of ``chipbench/weights.py:make``: matrices
    N(0, 1/fan_in), the embedding N(0, 0.02^2), norm gains 1 + 0.1 N(0, 1);
    the router stays float32 as the program keeps it. Call under
    ``jax.jit``. A stack of layers is drawn a layer at a time, so that the
    float32 draw of the expert stacks (6 GB a leaf at once) is never whole."""
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=is_leaf)
    paths = [jax.tree_util.keystr(p) for p, _ in with_paths]
    leaves = [leaf for _, leaf in with_paths]
    out = []
    for k, path, (shape, kind) in zip(jax.random.split(key, len(leaves)),
                                      paths, leaves):
        to = jnp.float32 if "moe_gate" in path else dtype

        def draw(kk, shape=shape, kind=kind, to=to):
            n = jax.random.normal(kk, shape, jnp.float32)
            if kind == "norm":
                return (1.0 + 0.1 * n).astype(to)
            return (n * (0.02 if kind is None else kind ** -0.5)).astype(to)
        if "layers" in path and len(shape) > 2:
            L = shape[0]
            out.append(jax.lax.map(
                lambda kk: draw(kk, shape=shape[1:]), jax.random.split(k, L)))
        else:
            out.append(draw(k))
    return jax.tree.unflatten(treedef, out)
