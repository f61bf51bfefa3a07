"""GPT-2 family, TPU-first (second flagship architecture).

Capability target: the reference's GPT stack (reference: fleet examples +
python/paddle/nn/layer/transformer.py TransformerDecoderLayer;
fused kernels fused_attention_kernel.cu / fused_feedforward_kernel.cu).

Same functional design as llama.py: stacked layers + lax.scan, GSPMD
param specs over ("fsdp","tp"), Pallas flash attention. Architectural
differences from Llama: learned position embeddings, pre-LayerNorm (with
bias), GELU MLP, fused qkv, tied lm head by default.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .llama import _attention, _ce


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304            # 50257 padded to a multiple of 128
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ln_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def hd(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def gpt2_124m(**kw) -> "GPTConfig":
        return GPTConfig(**kw)

    @staticmethod
    def gpt2_medium(**kw) -> "GPTConfig":
        return GPTConfig(hidden_size=1024, intermediate_size=4096,
                         num_layers=24, num_heads=16, **kw)

    @staticmethod
    def gpt2_large(**kw) -> "GPTConfig":
        return GPTConfig(hidden_size=1280, intermediate_size=5120,
                         num_layers=36, num_heads=20, **kw)

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_layers=2, num_heads=4, max_seq_len=128,
                 dtype=jnp.float32, remat=False)
        d.update(kw)
        return GPTConfig(**d)

    def num_params(self) -> int:
        h, i, L = self.hidden_size, self.intermediate_size, self.num_layers
        per_layer = (3 * h * h + 3 * h          # qkv + bias
                     + h * h + h                # proj + bias
                     + 2 * h * i + i + h        # mlp + biases
                     + 4 * h)                   # 2 LN scale+bias
        return (L * per_layer + self.vocab_size * h
                + self.max_seq_len * h + 2 * h)


def init_params(key: jax.Array, cfg: GPTConfig) -> Dict[str, Any]:
    h, i, L, v = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    k = jax.random.split(key, 6)
    std = 0.02

    def norm(kk, shape):
        return (jax.random.normal(kk, shape, jnp.float32) * std).astype(
            cfg.dtype)

    return {
        "wte": norm(k[0], (v, h)),
        "wpe": norm(k[1], (cfg.max_seq_len, h)),
        "final_ln_g": jnp.ones((h,), cfg.dtype),
        "final_ln_b": jnp.zeros((h,), cfg.dtype),
        "layers": {
            "wqkv": norm(k[2], (L, h, 3 * h)),
            "bqkv": jnp.zeros((L, 3 * h), cfg.dtype),
            "wo": norm(k[3], (L, h, h)) / math.sqrt(2 * L),
            "bo": jnp.zeros((L, h), cfg.dtype),
            "w1": norm(k[4], (L, h, i)),
            "b1": jnp.zeros((L, i), cfg.dtype),
            "w2": norm(k[5], (L, i, h)) / math.sqrt(2 * L),
            "b2": jnp.zeros((L, h), cfg.dtype),
            "ln1_g": jnp.ones((L, h), cfg.dtype),
            "ln1_b": jnp.zeros((L, h), cfg.dtype),
            "ln2_g": jnp.ones((L, h), cfg.dtype),
            "ln2_b": jnp.zeros((L, h), cfg.dtype),
        },
    }


def param_specs(cfg: GPTConfig) -> Dict[str, Any]:
    return {
        "wte": P("fsdp", "tp"),
        "wpe": P(None, None),
        "final_ln_g": P(None),
        "final_ln_b": P(None),
        "layers": {
            "wqkv": P(None, "fsdp", "tp"),
            "bqkv": P(None, "tp"),
            "wo": P(None, "tp", "fsdp"),
            "bo": P(None, None),
            "w1": P(None, "fsdp", "tp"),
            "b1": P(None, "tp"),
            "w2": P(None, "tp", "fsdp"),
            "b2": P(None, None),
            "ln1_g": P(None, None), "ln1_b": P(None, None),
            "ln2_g": P(None, None), "ln2_b": P(None, None),
        },
    }


def _ln(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _block(x, lp, cfg: GPTConfig, mesh_axes):
    B, S, H = x.shape
    nh, hd = cfg.num_heads, cfg.hd

    from jax.sharding import NamedSharding

    def sp(t):
        if mesh_axes is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh_axes["mesh"],
                             P(mesh_axes["data"], mesh_axes["tp"], None)))

    h1 = _ln(x, lp["ln1_g"], lp["ln1_b"], cfg.ln_eps)
    qkv = h1 @ lp["wqkv"] + lp["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nh, hd)
    v = v.reshape(B, S, nh, hd)
    o = _attention(q, k, v, causal=True).reshape(B, S, H)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "attn_out")
    x = sp(x + (o @ lp["wo"] + lp["bo"]))

    h2 = _ln(x, lp["ln2_g"], lp["ln2_b"], cfg.ln_eps)
    ff = jax.nn.gelu((h2 @ lp["w1"] + lp["b1"]).astype(jnp.float32)
                     ).astype(x.dtype) @ lp["w2"] + lp["b2"]
    return sp(x + ff)


def _trunk(params, tokens, cfg: GPTConfig, mesh_axes=None):
    B, S = tokens.shape
    x = (jnp.take(params["wte"], tokens, axis=0)
         + params["wpe"][None, :S]).astype(cfg.dtype)

    def block(carry, lp):
        return _block(carry, lp, cfg, mesh_axes)

    if cfg.remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable)

    def body(carry, lp):
        return block(carry, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _ln(x, params["final_ln_g"], params["final_ln_b"], cfg.ln_eps), \
        jnp.float32(0.0)


def forward(params, tokens, cfg: GPTConfig, mesh_axes=None,
            return_hidden=False):
    x, _ = _trunk(params, tokens, cfg, mesh_axes)
    if return_hidden:
        return x
    return (x @ params["wte"].T.astype(x.dtype)).astype(jnp.float32)


def loss_fn(params, tokens, cfg: GPTConfig, mesh_axes=None,
            seq_chunk: Optional[int] = None) -> jax.Array:
    h, aux = _trunk(params, tokens, cfg, mesh_axes)
    head = params["wte"].T.astype(h.dtype)
    B, S, H = h.shape
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = jnp.concatenate(
        [jnp.ones((B, S - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1)
    denom = jnp.float32(B * (S - 1))
    if seq_chunk is not None and S % seq_chunk != 0:
        raise ValueError(f"seq_chunk={seq_chunk} must divide seq_len={S}")
    if seq_chunk is None:
        ce = _ce((h @ head).astype(jnp.float32), labels)
        return jnp.sum(ce * mask) / denom + aux
    nc = S // seq_chunk
    hc = jnp.moveaxis(h.reshape(B, nc, seq_chunk, H), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, seq_chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, nc, seq_chunk), 1, 0)

    def body(acc, xs):
        hh, ll, mm = xs
        ce = _ce((hh @ head).astype(jnp.float32), ll)
        return acc + jnp.sum(ce * mm), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, lc, mc))
    return total / denom + aux
