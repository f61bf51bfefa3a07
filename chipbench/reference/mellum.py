"""The plain reference for ``model_type: mellum`` (Mellum2-12B-A2.5B): a
decoder whose layers alternate sliding-window and full attention, each with
its own rotary parameters, and whose every MLP is 64 routed experts of which
a token uses 8. Straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision: no cache, no kernel, no batching, no grouping. It reads the
configuration file's own keys and imports nothing from the program.

For layer l of type t in ``layer_types`` (the first ``num_hidden_layers``
entries of the published list):

- ``n = RMSNorm(x; g_attn)``; ``q = n Wq``, ``k = n Wk``, ``v = n Wv``, no bias.
- Rotary, rotate-half layout, on q and k, by ``rope_parameters[t]``:
  ``inv_freq_i = theta^(-2i/d)``; under ``rope_type: yarn`` the linear ramp
  between the two correction dimensions (``beta_fast`` and ``beta_slow``
  rotations over ``original_max_position_embeddings``) blends ``inv_freq_i``
  (below) and ``inv_freq_i / factor`` (above), and cos and sin are both
  multiplied by ``attention_factor``.
- ``s_ij = q_i k_j / sqrt(d)`` kept where ``j <= i`` and, in a sliding layer,
  ``i - j < sliding_window`` (the window counts the token itself); softmax;
  ``h = x + (softmax(s) v) Wo``.
- ``m = RMSNorm(h; g_mlp)``; ``p = softmax(m Wr)`` over all experts; the
  ``num_experts_per_tok`` largest p, ties to the lower index;
  ``w_e = p_e / sum of the selected p`` (``norm_topk_prob``);
  ``y = h + sum_e w_e Wd_e(silu(Wg_e m) * Wu_e m)``. No shared expert, no
  capacity, no dropped token.
- After the last layer RMSNorm, then the untied head.

Departures from a textbook transcription, each for memory at the published
widths on one chip and none changing a number: the layers run under
``lax.scan`` with every layer's weights cast to float32 inside the step (one
layer's experts are 1.6 GB in float32, all twelve would not fit beside the
bfloat16 originals), so the layer type enters as data (a window that is the
whole sequence in a full layer, that layer's own frequencies and scale);
attention is taken over blocks of ``q_block`` queries, so that a 10k-token
score matrix is never whole; the experts are a loop (``lax.scan``) over all
of them, each applied to every token and masked by whether the token chose
it, which is the sum above with zeros written out. The MTP head that the
model card lists is not in the published config and is not here.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_types(c: Dict):
    return list(c["layer_types"])[:c["num_hidden_layers"]]


def rotary(c: Dict, kind: str):
    """(inv_freq (d/2,), scale) of one layer type, in numpy float64."""
    rp = c["rope_parameters"][kind]
    d, theta = c["head_dim"], float(rp["rope_theta"])
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rp["rope_type"] == "default":
        return inv, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(rp["rope_type"])
    orig, factor = rp["original_max_position_embeddings"], rp["factor"]

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = inv * (1 - ramp) + inv / factor * ramp
    scale = rp.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


def _rope(x, pos, inv, scale):
    """x (S, H, d), rotate-half, positions ``pos`` (S,)."""
    f = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = (jnp.cos(f) * scale)[:, None, :], (jnp.sin(f) * scale)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, q_block: int):
    """q (S, nh, d), k and v (S, nkv, d): causal softmax attention in which
    query i sees the keys with ``i - j < window``."""
    S, nh, d = q.shape
    rep = nh // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    qb = min(q_block, S)
    pad = -S % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, nh, d)
    j = jnp.arange(S)[None, :]

    def block(_, xs):
        qi, i0 = xs
        i = i0 + jnp.arange(qb)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / math.sqrt(d)
        s = jnp.where((j <= i) & (i - j < window), s, -1e30)
        p = jax.nn.softmax(s, -1)
        return None, jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    _, o = jax.lax.scan(block, None, (qp, jnp.arange(qp.shape[0]) * qb))
    return o.reshape(-1, nh, d)[:S]


def experts(m, gate, wg, wu, wd, top_k: int):
    """m (S, h) -> the routed experts' weighted sum (S, h)."""
    p = jax.nn.softmax(_mm(m, gate), -1)                       # (S, E)
    order = jnp.argsort(-p, axis=-1, stable=True)[:, :top_k]   # ties: lower
    chosen = jnp.zeros(p.shape, bool).at[
        jnp.arange(p.shape[0])[:, None], order].set(True)
    w = jnp.where(chosen, p, 0.0)
    w = w / jnp.sum(w, -1, keepdims=True)

    def one(acc, xs):
        g, u, d, we = xs
        y = _mm(jax.nn.silu(_mm(m, g.astype(F32))) * _mm(m, u.astype(F32)),
                d.astype(F32))
        return acc + we[:, None] * y, None
    acc, _ = jax.lax.scan(one, jnp.zeros_like(m), (wg, wu, wd, w.T))
    return acc


def hidden(params: Dict, tokens, c: Dict, q_block: int = 512):
    """tokens (S,) -> final-norm hidden states (S, h), float32. ``params``
    is the tree the system under test is handed (stacked layers; the
    experts stay in their stored type until their turn in the loop)."""
    S = tokens.shape[0]
    nh, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps = c["rms_norm_eps"]
    kinds = layer_types(c)
    rot = {t: rotary(c, t) for t in set(kinds)}
    per_layer = {
        "inv": jnp.asarray(np.stack([rot[t][0] for t in kinds]), F32),
        "scale": jnp.asarray([rot[t][1] for t in kinds], F32),
        # a full layer's window is the whole sequence
        "window": jnp.asarray([c["sliding_window"]
                               if t == "sliding_attention" else S + 1
                               for t in kinds], jnp.int32)}
    pos = jnp.arange(S)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

    def layer(x, xs):
        lp, pl = xs
        f = lambda name: lp[name].astype(F32)
        n = _rms(x, f("attn_norm"), eps)
        q = _rope(_mm(n, f("wq")).reshape(S, nh, d), pos, pl["inv"], pl["scale"])
        k = _rope(_mm(n, f("wk")).reshape(S, nkv, d), pos, pl["inv"], pl["scale"])
        v = _mm(n, f("wv")).reshape(S, nkv, d)
        a = attention(q, k, v, pl["window"], q_block).reshape(S, nh * d)
        h = x + _mm(a, f("wo"))
        m = _rms(h, f("mlp_norm"), eps)
        y = experts(m, f("moe_gate"), lp["moe_wg"], lp["moe_wu"], lp["moe_wd"],
                    c["num_experts_per_tok"])
        return h + y, None
    x, _ = jax.lax.scan(layer, x, (params["layers"], per_layer))
    return _rms(x, params["final_norm"].astype(F32), eps)


def logits(params: Dict, rows, c: Dict):
    """hidden rows (n, h) -> logits (n, V); the head is untied."""
    if c["tie_word_embeddings"]:
        raise ValueError("mellum: the published head is untied")
    return _mm(rows, params["lm_head"].astype(F32))
