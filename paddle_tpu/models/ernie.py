"""ERNIE/BERT-style bidirectional encoder with MLM pretraining, TPU-first.

Capability target: the reference's flagship NLP encoder lineage (ERNIE) —
post-LN transformer encoder, learned position + segment embeddings,
masked-language-model head tied to the word embedding, pooler + NSP head
(reference architecture surface: python/paddle/nn/layer/transformer.py
TransformerEncoder; the ERNIE models themselves live out-of-tree in
PaddleNLP).

TPU-native design mirrors ``models/llama.py``: stacked (L, ...) parameter
leaves scanned with ``lax.scan``, GSPMD dp/fsdp/tp sharding declared in
:func:`param_specs`, optional Megatron-SP activation constraints, remat,
and a chunked-vocab MLM cross-entropy so the fp32 logits tensor never
materializes. Plugs into the shared train step via
``train.make_train_step(cfg, model=ernie)``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .llama import _ce
from .gpt import _ln


@dataclasses.dataclass(frozen=True)
class ErnieConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 512
    type_vocab_size: int = 2
    ln_eps: float = 1e-12
    dtype: Any = jnp.float32
    remat: bool = False
    # MLM objective: deterministic pseudo-random masking (stateless —
    # the mask derives from a fixed PRNG key + the token values, so the
    # loss is a pure function of (params, tokens))
    mlm_prob: float = 0.15
    mlm_seed: int = 0

    @property
    def hd(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def mask_token_id(self) -> int:
        return self.vocab_size - 1      # by convention here; documented

    @staticmethod
    def tiny(**kw) -> "ErnieConfig":
        kw.setdefault("vocab_size", 312)   # divisible for fsdp sharding
        kw.setdefault("hidden_size", 32)
        kw.setdefault("intermediate_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("max_seq_len", 64)
        return ErnieConfig(**kw)

    def num_params(self) -> int:
        h, i, L = self.hidden_size, self.intermediate_size, self.num_layers
        per_layer = (4 * h * h + 4 * h) + (2 * h * i + i + h) + 4 * h
        emb = (self.vocab_size + self.max_seq_len
               + self.type_vocab_size) * h + 2 * h
        heads = (h * h + h + 2 * h + self.vocab_size) + (h * h + h) \
            + (2 * h + 2)
        return L * per_layer + emb + heads


# ---------------- init ----------------
def init_params(key: jax.Array, cfg: ErnieConfig) -> Dict[str, Any]:
    h, i, v, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_layers)
    k = jax.random.split(key, 12)
    std = 0.02

    def norm(kk, shape, fan_in=None):
        s = std if fan_in is None else 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(kk, shape, jnp.float32) * s).astype(
            cfg.dtype)

    def zeros(shape):
        return jnp.zeros(shape, cfg.dtype)

    def ones(shape):
        return jnp.ones(shape, cfg.dtype)

    layers = {
        "wq": norm(k[1], (L, h, h), fan_in=h), "bq": zeros((L, h)),
        "wk": norm(k[2], (L, h, h), fan_in=h), "bk": zeros((L, h)),
        "wv": norm(k[3], (L, h, h), fan_in=h), "bv": zeros((L, h)),
        "wo": norm(k[4], (L, h, h), fan_in=h), "bo": zeros((L, h)),
        "attn_ln_g": ones((L, h)), "attn_ln_b": zeros((L, h)),
        "w1": norm(k[5], (L, h, i), fan_in=h), "b1": zeros((L, i)),
        "w2": norm(k[6], (L, i, h), fan_in=i), "b2": zeros((L, h)),
        "ffn_ln_g": ones((L, h)), "ffn_ln_b": zeros((L, h)),
    }
    return {
        "word_embed": norm(k[0], (v, h)),
        "pos_embed": norm(k[7], (cfg.max_seq_len, h)),
        "seg_embed": norm(k[8], (cfg.type_vocab_size, h)),
        "emb_ln_g": ones((h,)), "emb_ln_b": zeros((h,)),
        "layers": layers,
        # MLM transform + decoder bias (decoder weight tied to word_embed)
        "mlm_w": norm(k[9], (h, h), fan_in=h), "mlm_b": zeros((h,)),
        "mlm_ln_g": ones((h,)), "mlm_ln_b": zeros((h,)),
        "mlm_bias": jnp.zeros((v,), jnp.float32),
        # pooler + NSP head (reference BERT/ERNIE heads)
        "pool_w": norm(k[10], (h, h), fan_in=h), "pool_b": zeros((h,)),
        "nsp_w": norm(k[11], (h, 2), fan_in=h), "nsp_b": zeros((2,)),
    }


def param_specs(cfg: ErnieConfig) -> Dict[str, Any]:
    """dp/fsdp/tp shardings, Megatron conventions: qkv/w1 column-split
    over tp (biases follow), wo/w2 row-split; embeddings vocab-sharded
    over fsdp."""
    layers = {
        "wq": P(None, "fsdp", "tp"), "bq": P(None, "tp"),
        "wk": P(None, "fsdp", "tp"), "bk": P(None, "tp"),
        "wv": P(None, "fsdp", "tp"), "bv": P(None, "tp"),
        "wo": P(None, "tp", "fsdp"), "bo": P(None, None),
        "attn_ln_g": P(None, None), "attn_ln_b": P(None, None),
        "w1": P(None, "fsdp", "tp"), "b1": P(None, "tp"),
        "w2": P(None, "tp", "fsdp"), "b2": P(None, None),
        "ffn_ln_g": P(None, None), "ffn_ln_b": P(None, None),
    }
    return {
        "word_embed": P("fsdp", "tp"),
        "pos_embed": P(None, None),
        "seg_embed": P(None, None),
        "emb_ln_g": P(None), "emb_ln_b": P(None),
        "layers": layers,
        "mlm_w": P("fsdp", "tp"), "mlm_b": P("tp"),
        "mlm_ln_g": P(None), "mlm_ln_b": P(None),
        "mlm_bias": P("fsdp"),
        "pool_w": P("fsdp", "tp"), "pool_b": P("tp"),
        "nsp_w": P("fsdp", None), "nsp_b": P(None),
    }


# ---------------- building blocks ----------------
def _block(x, lp, attn_bias, cfg: ErnieConfig, mesh_axes):
    B, S, H = x.shape
    nh, hd = cfg.num_heads, cfg.hd

    def sp(t):
        if mesh_axes is None:
            return t
        from jax.sharding import NamedSharding
        return lax.with_sharding_constraint(
            t, NamedSharding(mesh_axes["mesh"],
                             P(mesh_axes["data"], mesh_axes["tp"], None)))

    q = (x @ lp["wq"] + lp["bq"]).reshape(B, S, nh, hd)
    k = (x @ lp["wk"] + lp["bk"]).reshape(B, S, nh, hd)
    v = (x @ lp["wv"] + lp["bv"]).reshape(B, S, nh, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if attn_bias is not None:
        s = s + attn_bias                   # (B,1,1,S) -1e30 at pads
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H)
    x = _ln(x + (o @ lp["wo"] + lp["bo"]), lp["attn_ln_g"],
            lp["attn_ln_b"], cfg.ln_eps)
    f = jax.nn.gelu((x @ lp["w1"] + lp["b1"]).astype(jnp.float32),
                    approximate=False).astype(x.dtype) @ lp["w2"] + lp["b2"]
    return sp(_ln(x + f, lp["ffn_ln_g"], lp["ffn_ln_b"], cfg.ln_eps))


def forward(params, tokens, cfg: ErnieConfig, mesh_axes=None,
            segment_ids=None, attention_mask=None):
    """-> (B, S, H) encoder output (bidirectional).

    attention_mask: optional (B, S), 1 = real token, 0 = padding (pads
    are masked out of every attention; outputs at real positions then
    match the unpadded encode).
    """
    B, S = tokens.shape
    x = jnp.take(params["word_embed"], tokens, axis=0)
    x = x + params["pos_embed"][:S][None]
    seg = (segment_ids if segment_ids is not None
           else jnp.zeros((B, S), jnp.int32))
    x = x + jnp.take(params["seg_embed"], seg, axis=0)
    x = _ln(x.astype(cfg.dtype), params["emb_ln_g"], params["emb_ln_b"],
            cfg.ln_eps)
    bias = None
    if attention_mask is not None:
        bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0,
                         -1e30).astype(jnp.float32)

    def block(carry, lp):
        return _block(carry, lp, bias, cfg, mesh_axes), None

    if cfg.remat:
        inner = block

        def block(carry, lp):  # noqa: F811 — remat wrapper
            return jax.checkpoint(
                lambda c, l: inner(c, l),
                policy=jax.checkpoint_policies.nothing_saveable)(carry, lp)

    x, _ = lax.scan(block, x, params["layers"])
    return x


def pooled_output(params, h, cfg: ErnieConfig):
    """[CLS] pooler: tanh(W·h₀) (reference BertPooler)."""
    return jnp.tanh((h[:, 0] @ params["pool_w"] + params["pool_b"])
                    .astype(jnp.float32))


def nsp_logits(params, pooled) -> jax.Array:
    """Next-sentence-prediction head over the pooled [CLS] output
    (reference BertPretrainingHeads); also the fine-tuning classifier
    seat."""
    return (pooled @ params["nsp_w"].astype(pooled.dtype)
            + params["nsp_b"].astype(pooled.dtype))


def _mlm_mask(tokens, cfg: ErnieConfig):
    """Pseudo-random MLM positions, stateless: the key folds in the batch
    CONTENT, so different batches mask different positions while the loss
    stays a pure function of (params, tokens)."""
    k = jax.random.fold_in(jax.random.key(cfg.mlm_seed),
                           jnp.sum(tokens.astype(jnp.uint32)))
    return jax.random.uniform(k, tokens.shape) < cfg.mlm_prob


def loss_fn(params, tokens, cfg: ErnieConfig, mesh_axes=None,
            seq_chunk: Optional[int] = None) -> jax.Array:
    """Masked-LM cross-entropy over the masked positions (mean).

    Masked inputs are replaced with ``cfg.mask_token_id``; the decoder is
    tied to the word embedding (+ output bias). ``seq_chunk`` chunks the
    fp32 logits over positions like the Llama loss.
    """
    B, S = tokens.shape
    mask = _mlm_mask(tokens, cfg)
    inp = jnp.where(mask, jnp.int32(cfg.mask_token_id), tokens)
    h = forward(params, inp, cfg, mesh_axes)
    t = _ln((h @ params["mlm_w"] + params["mlm_b"]),
            params["mlm_ln_g"], params["mlm_ln_b"], cfg.ln_eps)
    head = params["word_embed"].T.astype(t.dtype)
    w = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1.0)

    if seq_chunk is None:
        logits = (t @ head).astype(jnp.float32) + params["mlm_bias"]
        return jnp.sum(_ce(logits, tokens) * w) / denom
    if S % seq_chunk != 0:
        raise ValueError(f"seq_chunk={seq_chunk} must divide seq={S}")
    nc = S // seq_chunk
    tc = jnp.moveaxis(t.reshape(B, nc, seq_chunk, -1), 1, 0)
    lc = jnp.moveaxis(tokens.reshape(B, nc, seq_chunk), 1, 0)
    wc = jnp.moveaxis(w.reshape(B, nc, seq_chunk), 1, 0)

    def body(acc, xs):
        tch, lch, wch = xs
        logits = (tch @ head).astype(jnp.float32) + params["mlm_bias"]
        return acc + jnp.sum(_ce(logits, lch) * wch), None

    total, _ = lax.scan(body, jnp.float32(0.0), (tc, lc, wc))
    return total / denom
