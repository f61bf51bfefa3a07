"""``model_type: kimi_k2`` for the serving driver ``drivers/serve_arch.py``:
the configuration file's keys as the program's ``LlamaConfig`` (latent
attention in every layer, ``first_k_dense_replace`` leading dense layers,
then expert layers with sigmoid routing and a shared expert), seeded weights
in the program's layout (``dense_layers`` the leading layers' stack,
``layers`` the expert layers'; the expert stacks hold the experts this chip
holds), and the plain reference to compare with.

The file's ``n_routed_experts`` is the number of routed experts HELD HERE,
experts ``first_expert_held`` onwards; the router keeps ``router_outputs``
outputs and chooses ``num_experts_per_tok`` of them."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..reference import kimi_k2 as reference  # noqa: F401 — the driver's hook

F32_LEAVES = ("moe_gate", "moe_bias")


def program_config(c: Dict, max_len: int, remat: bool = True):
    """bf16, every width as published. A program without latent attention
    (the one before it was added) has no ``LatentConfig`` and fails here."""
    from paddle_tpu.models import llama
    from paddle_tpu.models.moe import MoEConfig
    rs = c["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("kimi_k2: rope_scaling is yarn")
    if (c["n_group"] != 1 or c["topk_group"] != 1 or c["moe_layer_freq"] != 1
            or c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"]
            or c["topk_method"] != "noaux_tc"):
        raise ValueError("kimi_k2: one routing group, every layer past the "
                         "leading ones an expert layer, sigmoid scores with "
                         "a selection bias, normalised")
    latent = llama.LatentConfig(
        q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
        nope_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
        v_dim=c["v_head_dim"],
        mscale=reference.mscale(rs["factor"], rs["mscale_all_dim"]))
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], max_seq_len=max_len,
        rope_theta=float(c["rope_theta"]),
        yarn=llama.YarnRope(
            factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            attention_factor=(reference.mscale(rs["factor"], rs["mscale"])
                              / reference.mscale(rs["factor"],
                                                 rs["mscale_all_dim"]))),
        rms_eps=c["rms_norm_eps"], dtype=jnp.bfloat16,
        tie_embeddings=c["tie_word_embeddings"], remat=remat,
        moe=MoEConfig(num_experts=c["router_outputs"],
                      top_k=c["num_experts_per_tok"], score="sigmoid",
                      routed_scale=float(c["routed_scaling_factor"]),
                      expert_size=c["moe_intermediate_size"],
                      shared_size=(c["moe_intermediate_size"]
                                   * c["n_shared_experts"])),
        layer_pattern=("latent",), latent=latent,
        dense_layers=c["first_k_dense_replace"])


def shapes(c: Dict) -> Dict:
    """leaf -> (shape, rule): a fan-in, "norm", "embed", or a leaf's own."""
    h, v, i = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    nh, qr, R = c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    E, El = c["router_outputs"], c["n_routed_experts"]
    ie = c["moe_intermediate_size"]
    sh = ie * c["n_shared_experts"]
    Ld = c["first_k_dense_replace"]
    Le = c["num_hidden_layers"] - Ld

    def attn(L):
        return {"attn_norm": ((L, h), "norm"), "wq_a": ((L, h, qr), h),
                "q_norm": ((L, qr), "norm"),
                "wq_b": ((L, qr, nh * (nope + rope)), qr),
                "wkv_a": ((L, h, R + rope), h), "kv_norm": ((L, R), "norm"),
                "wkv_b": ((L, R, nh * (nope + vd)), R),
                "wo": ((L, nh * vd, h), nh * vd),
                "mlp_norm": ((L, h), "norm")}
    out = {
        "embed": ((v, h), "embed"), "final_norm": ((h,), "norm"),
        "lm_head": ((h, v), h),
        "layers": {**attn(Le), "moe_gate": ((Le, h, E), h),
                   "moe_bias": ((Le, E), "router_bias"),
                   "moe_wg": ((Le, El, h, ie), h),
                   "moe_wu": ((Le, El, h, ie), h),
                   "moe_wd": ((Le, El, ie, h), ie),
                   "ws_g": ((Le, h, sh), h), "ws_u": ((Le, h, sh), h),
                   "ws_d": ((Le, sh, h), sh),
                   "first_expert": ((Le,), "first_expert")}}
    if Ld:
        out["dense_layers"] = {**attn(Ld), "wg": ((Ld, h, i), h),
                               "wu": ((Ld, h, i), h), "wd": ((Ld, i, h), i)}
    return out


def weights(key: jax.Array, c: Dict, dtype=jnp.bfloat16) -> Dict:
    """The distributions the configuration file's ``assumed.weights``
    states. Call under ``jax.jit``. A stack of layers is drawn a layer at a
    time, so that the float32 draw of an expert stack is never whole."""
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=is_leaf)
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
