"""The plain reference for ``model_type: afmoe`` (Trinity-Large-Preview): a
decoder whose layers mix sliding-window attention with rotary and full
attention without it, each gated and with QK-norm, under sandwich norms;
leading dense layers, then expert layers with sigmoid routing, a shared
expert and THIS CHIP'S SHARE of the routed experts and of the vocabulary.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
cache, no kernel, no batching, no grouping. It reads the configuration
file's own keys and imports nothing from the program.

The file runs layers ``first_layer_run .. + num_hidden_layers`` of the
published ``layer_types``; a layer below ``num_dense_layers`` is dense. With
eps ``rms_norm_eps`` and a gain on every norm:

- Input: ``x = E[token]``, times ``sqrt(hidden_size)`` under
  ``mup_enabled``.
- Layer, with ``u = RMSNorm_in(x)``: ``q = u W_q``, ``k = u W_k``, ``v = u
  W_v`` by head, no bias; under ``qk_norm`` each query and key head is
  RMS-normed over its lanes with one gain a layer; rotary (rotate-half,
  ``rope_theta``, unscaled) on ``q`` and ``k`` unless the layer's type is in
  ``nope_layer_types``; ``s = q_h . k_j(h) / sqrt(head_dim)``, causal, and
  in a sliding layer ``i - j < sliding_window``; softmax; ``o = concat_h(sum
  p v)``; under ``attn_output_gate`` ``o <- o * sigmoid(u W_gate)``
  elementwise; ``a = o W_o``; ``h = x + RMSNorm_post_attn(a)`` under
  ``sandwich_norms`` (else ``x + a``).
- ``w = RMSNorm_pre_mlp(h)``; ``x' = h + RMSNorm_post_mlp(FFN(w))``.
- Dense FFN: ``(silu(w W_g) * (w W_u)) W_d`` at ``intermediate_size``.
- Expert FFN: ``s = sigmoid(w W_r)`` over all ``router_outputs`` experts;
  the ``num_experts_per_tok`` largest of ``s + b``, ties to the lower index;
  weights ``route_scale * s / (sum of the chosen s + 1e-20)``; the routed sum
  over the chosen experts THAT ARE HELD HERE (``first_expert ..
  + num_experts``: what the others would add is left out, as on one chip of
  the deployment), each ``(silu(w W1) * (w W3)) W2``; plus one shared expert
  of the same form at ``moe_intermediate_size * num_shared_experts``, added
  once. :func:`ffn_partial` is this sum before ``RMSNorm_post_mlp``.
- After the last layer RMSNorm, then the untied head.

Departures from a textbook transcription, each for memory at the published
widths on one chip beside the bfloat16 weights and none changing a number: a
layer's weights are cast to float32 where they are used; attention is taken
over blocks of ``q_block`` queries and one key head's group of query heads
at a time, so that a 24k-token score matrix is never whole; the held experts
are a loop (``lax.scan``) over all of them, each taken out of the stack of
all layers' experts in its turn, applied to every token and weighed by
whether the token chose it (zero where not); the wide projections run over
blocks of ``t_block`` tokens, and the dense FFN over blocks of its inner
columns, summed.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
STACKS = ("moe_wg", "moe_wu", "moe_wd")


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _blocks(f, x, t_block: int):
    """``f`` over blocks of ``t_block`` rows of ``x``: the same numbers as
    ``f(x)`` for an ``f`` that treats rows alike."""
    S = x.shape[0]
    tb = min(t_block, S)
    pad = -S % tb
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, tb, x.shape[1])
    out = jax.lax.map(f, xp)
    return out.reshape(-1, out.shape[-1])[:S]


def layer_types(c: Dict):
    first = c["first_layer_run"]
    return list(c["layer_types"])[first:first + c["num_hidden_layers"]]


def lead_layers(c: Dict) -> int:
    """How many of the layers run are dense."""
    return min(max(c["num_dense_layers"] - c["first_layer_run"], 0),
               c["num_hidden_layers"])


def _rotate(x, cos, sin):
    """x (S, H, d), rotate-half; cos and sin (S, d/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    co, si = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * co - x2 * si, x2 * co + x1 * si], -1)


def attention(u, lp, c: Dict, kind: str, cos, sin, q_block: int,
              t_block: int):
    """u (S, h) -> the attention's output ``a`` (S, h), before the post
    norm; one key head's group of query heads at a time."""
    S = u.shape[0]
    nh, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    rep = nh // nkv
    eps = c["rms_norm_eps"]
    f = lambda name: lp[name].astype(F32)
    window = c["sliding_window"] if kind == "sliding_attention" else S + 1
    rope = kind not in c["nope_layer_types"]
    qb = min(q_block, S)
    pad = -S % qb
    j = jnp.arange(S)[None, :]

    def proj(name, width):
        return _blocks(lambda b: _mm(b, f(name)), u, t_block).reshape(
            S, -1, width)

    def heads(g):
        # key head g and its ``rep`` query heads
        cut = lambda w, n: jax.lax.dynamic_slice_in_dim(
            w.reshape(w.shape[0], -1, d), g * n, n, axis=1).astype(F32)
        q = jnp.einsum("sh,hnd->snd", u, cut(lp["wq"], rep), precision=HI)
        k = jnp.einsum("sh,hnd->snd", u, cut(lp["wk"], 1), precision=HI)
        v = jnp.einsum("sh,hnd->snd", u, cut(lp["wv"], 1), precision=HI)
        if c["qk_norm"]:
            q, k = _rms(q, f("q_norm"), eps), _rms(k, f("k_norm"), eps)
        if rope:
            q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, rep, d)

        def block(_, xs):
            qi, i0 = xs
            i = i0 + jnp.arange(qb)[:, None]
            s = jnp.einsum("qnd,kd->nqk", qi, k[:, 0], precision=HI) \
                / math.sqrt(d)
            s = jnp.where((j <= i) & (i - j < window), s, -1e30)
            p = jax.nn.softmax(s, -1)
            return None, jnp.einsum("nqk,kd->qnd", p, v[:, 0], precision=HI)
        _, o = jax.lax.scan(block, None, (qp, jnp.arange(qp.shape[0]) * qb))
        return o.reshape(-1, rep, d)[:S]

    o = jax.lax.map(heads, jnp.arange(nkv))            # (nkv, S, rep, d)
    o = jnp.moveaxis(o, 0, 1).reshape(S, nh * d)
    if c["attn_output_gate"]:
        o = o * jax.nn.sigmoid(_blocks(lambda b: _mm(b, f("wgate")), u,
                                       t_block))
    return _blocks(lambda b: _mm(b, f("wo")), o, t_block)


def _swiglu(u, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(u, wg)) * _mm(u, wu), wd)


def _dense(u, lp, t_block: int, i_block: int = 3072):
    """The dense FFN, ``i_block`` of its inner columns at a time: the sum
    over column blocks of ``(silu(u W_g[:, b]) * (u W_u[:, b])) W_d[b]``."""
    wg, wu, wd = lp["wg"], lp["wu"], lp["wd"]
    ib = min(i_block, wg.shape[1])
    if wg.shape[1] % ib:
        raise ValueError("the dense width is a whole number of blocks")

    def one(acc, b):
        cols = lambda w: jax.lax.dynamic_slice_in_dim(
            w, b * ib, ib, axis=1).astype(F32)
        rows = jax.lax.dynamic_slice_in_dim(wd, b * ib, ib, 0).astype(F32)
        return acc + _blocks(
            lambda x: _swiglu(x, cols(wg), cols(wu), rows), u, t_block), None
    return jax.lax.scan(one, jnp.zeros_like(u),
                        jnp.arange(wg.shape[1] // ib))[0]


def route(u, lp, c: Dict):
    """The router over all ``router_outputs`` experts: (weights (S, E),
    zero where not chosen)."""
    s = jax.nn.sigmoid(_mm(u, lp["moe_gate"].astype(F32)))       # (S, E)
    k = c["num_experts_per_tok"]
    order = jnp.argsort(-(s + lp["moe_bias"].astype(F32)), axis=-1,
                        stable=True)[:, :k]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    w = jnp.where(chosen, s, 0.0)
    return c["route_scale"] * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)


def ffn_partial(u, lp, stacks, layer: int, c: Dict, t_block: int):
    """u (S, h) -> the routed experts held here and the shared expert,
    before ``RMSNorm_post_mlp``. ``stacks``: the three expert stacks of ALL
    expert layers ``(L, E_l, ...)``; one expert of layer ``layer`` is taken
    out at a time."""
    f = lambda name: lp[name].astype(F32)
    w = route(u, lp, c)
    El = stacks[0].shape[1]
    held = jax.lax.dynamic_slice_in_dim(w, lp["first_expert"], El, axis=1)
    flat = [a.reshape((-1,) + a.shape[2:]) for a in stacks]

    def one(acc, xs):
        e, we = xs
        take = lambda a: jax.lax.dynamic_index_in_dim(
            a, layer * El + e, 0, keepdims=False).astype(F32)
        out = _blocks(lambda b: _swiglu(b, *(take(a) for a in flat)), u,
                      t_block)
        return acc + we[:, None] * out, None
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(El), held.T))
    return routed + _blocks(
        lambda b: _swiglu(b, f("ws_g"), f("ws_u"), f("ws_d")), u, t_block)


def hidden(params: Dict, tokens, c: Dict, q_block: int = 512,
           t_block: int = 2048):
    """tokens (S,) -> final-norm hidden states (S, h), float32. ``params``
    is the tree the system under test is handed: ``dense_layers`` the
    leading layers' stack, ``layers`` the expert layers', its expert stacks
    holding this chip's experts."""
    eps = c["rms_norm_eps"]
    S = tokens.shape[0]
    d = c["head_dim"]
    inv = float(c["rope_theta"]) ** (-np.arange(0, d, 2, dtype=np.float64)
                                     / d)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos, sin = jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if c["mup_enabled"]:
        x = x * math.sqrt(c["hidden_size"])
    lead = lead_layers(c)
    stacks = tuple(params["layers"][n] for n in STACKS)
    small = {n: a for n, a in params["layers"].items() if n not in STACKS}
    post = ((lambda y, lp, name: _rms(y, lp[name].astype(F32), eps))
            if c["sandwich_norms"] else (lambda y, lp, name: y))
    for i, kind in enumerate(layer_types(c)):
        dense = i < lead
        lp = jax.tree.map(lambda a: a[i if dense else i - lead],
                          params["dense_layers"] if dense else small)
        a = attention(_rms(x, lp["attn_norm"].astype(F32), eps), lp, c, kind,
                      cos, sin, q_block, t_block)
        x = x + post(a, lp, "post_attn_norm")
        w = _rms(x, lp["mlp_norm"].astype(F32), eps)
        y = (_dense(w, lp, t_block) if dense
             else ffn_partial(w, lp, stacks, i - lead, c, t_block))
        x = x + post(y, lp, "post_mlp_norm")
    return _rms(x, params["final_norm"].astype(F32), eps)


def logits(params: Dict, rows, c: Dict):
    """hidden rows (n, h) -> logits (n, V) over this chip's slice of the
    vocabulary; the head is untied."""
    if c["tie_word_embeddings"]:
        raise ValueError("afmoe: the published head is untied")
    return _mm(rows, params["lm_head"].astype(F32))
