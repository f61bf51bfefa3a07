"""Weights and token batches from ``--seed``: made on the device in one
jitted call, in the type they are served or trained in. The tree has the
layout of the program's ``llama.init_params`` (stacked layers), which is the
interface the system under test is handed; the values are the benchmark's."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A key for any whole-number seed, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def shapes(c: Dict) -> Dict:
    h, i, v, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = int(c.get("head_dim") or h // nh)
    tree = {"embed": ((v, h), None), "final_norm": ((h,), "norm"),
            "layers": {"wq": ((L, h, nh * hd), h), "wk": ((L, h, nkv * hd), h),
                       "wv": ((L, h, nkv * hd), h), "wo": ((L, nh * hd, h), nh * hd),
                       "attn_norm": ((L, h), "norm"), "mlp_norm": ((L, h), "norm"),
                       "wg": ((L, h, i), h), "wu": ((L, h, i), h),
                       "wd": ((L, i, h), i)}}
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = ((h, v), h)
    return tree


def make(key: jax.Array, c: Dict, dtype=jnp.bfloat16) -> Dict:
    """Matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm gains
    1 + 0.1 N(0, 1); call under ``jax.jit``."""
    spec = shapes(c)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        n = jax.random.normal(k, shape, jnp.float32)
        if kind == "norm":
            w = 1.0 + 0.1 * n
        elif kind is None:
            w = 0.02 * n
        else:
            w = n / (kind ** 0.5)
        out.append(w.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def batch(key: jax.Array, step, rows: int, seq_len: int, vocab: int):
    """The token batch of one training step: rows that all differ, from the
    seed and the step's number; call under ``jax.jit``."""
    return jax.random.randint(jax.random.fold_in(key, step), (rows, seq_len),
                              0, vocab, jnp.int32)
