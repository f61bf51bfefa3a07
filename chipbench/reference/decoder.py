"""The plain reference: a decoder-only transformer (RMSNorm, rotary
positions in the rotate-half layout, grouped-query causal attention, SwiGLU,
tied or untied head) in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision, its next-token loss, and AdamW with clipping
by the global norm. No kernel, no cache, no batching tricks, and nothing
imported from the program. It reads the configuration file's own keys.

``quant="int8"`` is the training cells' control: the same code with both
operands of every matrix product, forward and backward, rounded to 8-bit
integers (by row or channel of the axis that is not summed over), the
nearest precision below the bfloat16 that the configurations state. It is
never the reference.

Memory: layers run under ``lax.scan`` with each layer recomputed in the
backward pass, the loss is taken in chunks of positions, and the training
step sums gradients over blocks of rows, so that the published sizes fit
beside nothing else on one chip.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _hd(c):
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def _fake_int8(x, axis):
    """Round to 127 levels of the largest magnitude along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _mm_int8(x, w):
    """x (..., k) @ w (k, n) with both operands in 8-bit integers:
    activations by row, weights by output channel."""
    return jnp.matmul(_fake_int8(x, -1), _fake_int8(w, 0), precision=HI)


def _mm_int8_fwd(x, w):
    return _mm_int8(x, w), (x, w)


def _mm_int8_bwd(res, g):
    # the backward pass's two products in 8-bit integers too: the incoming
    # gradient by row, the weights by input channel, activations by column
    x, w = res
    gq = _fake_int8(g, -1)
    dx = jnp.matmul(gq, _fake_int8(w, 1).T, precision=HI)
    x2, g2 = x.reshape(-1, x.shape[-1]), g.reshape(-1, g.shape[-1])
    dw = jnp.matmul(_fake_int8(x2, 0).T, _fake_int8(g2, 0), precision=HI)
    return dx, dw


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _ste_int8(x, axis):
    """Attention's operands rounded, the gradient passed straight through."""
    return x + jax.lax.stop_gradient(_fake_int8(x, axis) - x)


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32."""
    if quant == "int8":
        return _mm_int8(x, w)
    if quant is not None:
        raise ValueError(quant)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, S, H, hd), rotate-half."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    f = jnp.outer(jnp.arange(S, dtype=F32), inv)
    cos, sin = jnp.cos(f)[None, :, None, :], jnp.sin(f)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, c, quant):
    B, S, _ = x.shape
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], _hd(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope(_mm(h, lp["wq"], quant).reshape(B, S, nh, hd), theta)
    k = _rope(_mm(h, lp["wk"], quant).reshape(B, S, nkv, hd), theta)
    v = _mm(h, lp["wv"], quant).reshape(B, S, nkv, hd)
    k = jnp.repeat(k, nh // nkv, 2)
    v = jnp.repeat(v, nh // nkv, 2)
    if quant == "int8":
        q, k, v = (_ste_int8(t, -1) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / (hd ** 0.5)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI).reshape(B, S, nh * hd)
    x = x + _mm(o, lp["wo"], quant)
    h = _rms(x, lp["mlp_norm"], eps)
    ff = jax.nn.silu(_mm(h, lp["wg"], quant)) * _mm(h, lp["wu"], quant)
    return x + _mm(ff, lp["wd"], quant)


def hidden(params: Dict, tokens, c: Dict, quant: Optional[str] = None,
           remat: bool = False):
    """tokens (B, S) -> final-norm hidden states (B, S, h), float32."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    layer = functools.partial(_layer, c=c, quant=quant)
    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x, params["layers"])
    return _rms(x, params["final_norm"].astype(F32), c["rms_norm_eps"])


def head(params: Dict, c: Dict):
    w = params["embed"].T if c["tie_word_embeddings"] else params["lm_head"]
    return w.astype(F32)


def logits(params: Dict, rows, c: Dict, quant: Optional[str] = None):
    """hidden rows (n, h) -> logits (n, V)."""
    return _mm(rows, head(params, c), quant)


def loss(params: Dict, tokens, c: Dict, quant: Optional[str] = None,
         chunk: int = 512):
    """Mean next-token cross-entropy over B x (S-1) positions."""
    B, S = tokens.shape
    x = hidden(params, tokens, c, quant, remat=True)
    w = head(params, c)
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], 1)
    mask = jnp.arange(S)[None, :] < S - 1
    chunk = min(chunk, S)
    n = S // chunk

    @jax.checkpoint
    def piece(xc, lc, mc):
        lg = _mm(xc, w, quant)
        ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, lc[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(mc, ce, 0.0))

    def body(acc, xs):
        return acc + piece(*xs), None

    split = lambda a: jnp.moveaxis(a.reshape(B, n, chunk, *a.shape[2:]), 1, 0)
    total, _ = jax.lax.scan(body, F32(0.0), (split(x), split(labels),
                                             split(jnp.broadcast_to(mask, (B, S)))))
    return total / (B * (S - 1))


def loss_and_grads(params: Dict, tokens, c: Dict, quant: Optional[str] = None,
                   rows: int = 1):
    """Loss and float32 gradients of the whole batch's mean, summed over
    blocks of ``rows`` sequences."""
    B = tokens.shape[0]
    blocks = tokens.reshape(B // rows, rows, tokens.shape[1])
    vg = jax.value_and_grad(lambda p, t: loss(p, t, c, quant))

    def body(acc, t):
        lv, g = vg(params, t)
        return (acc[0] + lv, jax.tree.map(jnp.add, acc[1], g)), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    (lv, g), _ = jax.lax.scan(body, (F32(0.0), zero), blocks)
    k = B // rows
    return lv / k, jax.tree.map(lambda a: a / k, g)


def clip(grads, grad_clip: float):
    """The gradient as the optimizer gets it: scaled so that its global
    norm is at most ``grad_clip``."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, grad_clip / (gn + 1e-6))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(params, grads, m, v, step, o: Dict):
    """One AdamW update in float32; ``step`` counts from 0."""
    t = step + 1.0

    def one(p, g, m_, v_):
        m_ = o["b1"] * m_ + (1 - o["b1"]) * g
        v_ = o["b2"] * v_ + (1 - o["b2"]) * g * g
        mh, vh = m_ / (1 - o["b1"] ** t), v_ / (1 - o["b2"] ** t)
        p = p - o["lr"] * (mh / (jnp.sqrt(vh) + o["eps"]) + o["weight_decay"] * p)
        return p, m_, v_
    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t_: t_[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """The Euclidean norm of every leaf, by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in flat}
