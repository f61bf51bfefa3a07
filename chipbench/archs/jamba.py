"""``model_type: jamba`` for the serving driver ``drivers/serve_arch.py``:
the configuration file's keys as the program's ``LlamaConfig`` (a hybrid
model of single-part layers: each published decoder layer is its mixer,
Mamba-1 or attention, then its dense MLP), seeded weights in the program's
layout (a stack per part kind) and the plain reference to compare with.

Layer ``i`` attends iff ``i % attn_layer_period == attn_layer_offset``
(the published code's ``layers_block_type``); ``num_experts`` 1 makes every
feed-forward part the dense MLP."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..reference import jamba as reference  # noqa: F401 — the driver's hook


def mixers(c: Dict):
    """The mixer kind of each of the ``num_hidden_layers`` layers."""
    return tuple("attention" if i % c["attn_layer_period"]
                 == c["attn_layer_offset"] else "mamba1"
                 for i in range(c["num_hidden_layers"]))


def period(c: Dict):
    """The shortest run of part kinds (mixer, then MLP, a layer) that,
    repeated, gives the whole model."""
    parts = [p for kind in mixers(c) for p in (kind, "mlp")]
    for p in range(2, len(parts) + 1, 2):
        if len(parts) % p == 0 and parts == parts[:p] * (len(parts) // p):
            return tuple(parts[:p])


def _widths(c: Dict):
    h = c["hidden_size"]
    return (h, c["mamba_expand"] * h, c["mamba_d_state"], c["mamba_dt_rank"],
            c["mamba_d_conv"], h // c["num_attention_heads"])


def program_config(c: Dict, max_len: int, remat: bool = True):
    """bf16, every width as published. A program without Mamba-1 layers
    (the one before they were added) has no ``HybridConfig.channels`` and
    fails here."""
    from paddle_tpu.models import llama
    if c["num_experts"] != 1:
        raise ValueError("jamba: routed expert layers are not served here "
                         "(num_experts 1: a dense MLP in every layer)")
    if c["mamba_proj_bias"] or not c["mamba_conv_bias"]:
        raise ValueError("jamba: projections without bias, a convolution "
                         "with one")
    h, D, N, R, K, hd = _widths(c)
    hybrid = llama.HybridConfig(channels=D, dt_rank=R, ssm_state=N,
                                conv_kernel=K)
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=h,
        intermediate_size=c["intermediate_size"],
        num_layers=2 * c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=hd,
        max_seq_len=max_len, rms_eps=c["rms_norm_eps"], dtype=jnp.bfloat16,
        tie_embeddings=c["tie_word_embeddings"], remat=remat,
        layer_pattern=period(c), hybrid=hybrid)


def shapes(c: Dict) -> Dict:
    """leaf -> (shape, rule): a fan-in, "norm", "embed", or a leaf's own."""
    h, D, N, R, K, hd = _widths(c)
    v, i = c["vocab_size"], c["intermediate_size"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    kinds = mixers(c)
    Lm, La, L = kinds.count("mamba1"), kinds.count("attention"), len(kinds)
    out = {
        "embed": ((v, h), "embed"), "final_norm": ((h,), "norm"),
        "layers": {
            "mamba1": {"norm": ((Lm, h), "norm"),
                       "w_in": ((Lm, h, 2 * D), h),
                       "conv_w": ((Lm, K, D), K),
                       "conv_b": ((Lm, D), "small"),
                       "w_x": ((Lm, D, R + 2 * N), D),
                       "dt_norm": ((Lm, R), "norm"),
                       "b_norm": ((Lm, N), "norm"),
                       "c_norm": ((Lm, N), "norm"),
                       "w_dt": ((Lm, R, D), R),
                       "dt_bias": ((Lm, D), "dt_bias"),
                       "A_log": ((Lm, D, N), "A_log"),
                       "D": ((Lm, D), "ones"),
                       "w_out": ((Lm, D, h), D)},
            "attention": {"norm": ((La, h), "norm"),
                          "wq": ((La, h, nh * hd), h),
                          "wk": ((La, h, nkv * hd), h),
                          "wv": ((La, h, nkv * hd), h),
                          "wo": ((La, nh * hd, h), nh * hd)},
            "mlp": {"norm": ((L, h), "norm"), "wg": ((L, h, i), h),
                    "wu": ((L, h, i), h), "wd": ((L, i, h), i)}}}
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((h, v), h)
    return out


F32_LEAVES = ("dt_bias", "A_log", "D")


def weights(key: jax.Array, c: Dict, dtype=jnp.bfloat16) -> Dict:
    """The distributions the configuration file's ``assumed.weights``
    states. Call under ``jax.jit``. A stack of layers is drawn a layer at a
    time, so that no float32 draw of a whole stack is live at once."""
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=is_leaf)
    out = []
    for k, (path, (shape, kind)) in zip(
            jax.random.split(key, len(with_paths)), with_paths):
        to = jnp.float32 if path[-1].key in F32_LEAVES else dtype

        def draw(kk, shape=shape, kind=kind, to=to):
            if kind == "ones":
                return jnp.ones(shape, to)
            n = jax.random.normal(kk, shape, jnp.float32)
            if kind == "A_log":
                # log(1 .. N) along the states for every channel, jittered
                return jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
                               ) + 0.1 * n
            if kind == "dt_bias":
                dt = jax.random.uniform(kk, shape, jnp.float32, 1e-3, 0.1)
                return dt + jnp.log(-jnp.expm1(-dt))
            if kind == "norm":
                return (1.0 + 0.1 * n).astype(to)
            scale = {"embed": 0.02, "small": 0.02}.get(kind) or kind ** -0.5
            return (n * scale).astype(to)
        if "layers" in str(path[0]) and len(shape) > 1:
            out.append(jax.lax.map(lambda kk: draw(kk, shape=shape[1:]),
                                   jax.random.split(k, shape[0])))
        else:
            out.append(draw(k))
    return jax.tree.unflatten(treedef, out)
