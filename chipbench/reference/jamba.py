"""The plain reference for ``model_type: jamba`` (AI21-Jamba2-3B): Mamba-1
and attention mixers, each followed by a dense MLP, the whole model.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
cache, no kernel, no batching, no chunked scan. It reads the configuration
file's own keys and imports nothing from the program.

Layer ``i`` is ``x <- x + mixer(RMSNorm(x)); x <- x + MLP(RMSNorm(x))``,
each norm its own (eps ``rms_norm_eps``); the mixer attends iff ``i %
attn_layer_period == attn_layer_offset``, else it is Mamba-1:

- Mamba-1, ``D = mamba_expand * hidden`` channels, ``N = mamba_d_state``:
  ``[x | z] = u W_in``; ``x <- silu(b + sum_j w_j x_{t-K+1+j})``,
  depthwise, causal, zeros before the first token; ``[d | B | C] = x
  W_x`` (widths ``mamba_dt_rank``, N, N), each through its own RMSNorm;
  ``dt = softplus(d W_dt + b_dt)``, ``A = -exp(A_log)`` (D, N); a
  ``lax.scan`` over the tokens: ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t)
  B_t^T``, ``y_t = S_t C_t + D x_t``; ``f = (y silu(z)) W_out``.
- attention: ``q = u Wq``, ``k = u Wk``, ``v = u Wv`` without bias and
  without any positional embedding; causal softmax at ``1/sqrt(head
  dim)``, the one key/value head shared by all query heads; ``f = o Wo``.
- MLP: ``f = (silu(u W_gate) * u W_up) W_down`` (``num_experts`` 1).

Then a final RMSNorm and the tied head, ``logits = x E^T``.

Departures from the published code, each for memory or time at the
published widths on one chip beside the bfloat16 weights, and none
changing a number: a layer's weights are cast to float32 where they are
used; every step is float32 where the published code runs the model's
dtype (bfloat16) outside the recurrence; the recurrence's state is kept
``(N, D)``, transposed from the published ``(D, N)``, so that the scan's
carry is lane-dense; the scan emits ``y_t`` and never holds the states of
all tokens; attention is taken over blocks of ``q_block`` queries, so
that a 12k-token score matrix is never whole; the wide projections run
over blocks of ``t_block`` tokens.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


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


def is_attention(c: Dict, i: int) -> bool:
    return i % c["attn_layer_period"] == c["attn_layer_offset"]


def mamba(u, lp, c: Dict, t_block: int):
    """u (S, h) -> the Mamba-1 mixer's output (S, h)."""
    S = u.shape[0]
    D = c["mamba_expand"] * c["hidden_size"]
    N, R, K = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    eps = c["rms_norm_eps"]
    f = lambda name: lp[name].astype(F32)
    w_in = lp["w_in"]
    x, z = (_blocks(lambda b, lo=lo: _mm(b, w_in[:, lo:lo + D].astype(F32)),
                    u, t_block) for lo in (0, D))
    seq = jnp.concatenate([jnp.zeros((K - 1, D), F32), x])
    cw = f("conv_w")
    x = jax.nn.silu(f("conv_b") + sum(cw[j] * seq[j:j + S] for j in range(K)))
    dbc = _blocks(lambda b: _mm(b, f("w_x")), x, t_block)
    d = _rms(dbc[:, :R], f("dt_norm"), eps)
    B = _rms(dbc[:, R:R + N], f("b_norm"), eps)
    C = _rms(dbc[:, R + N:], f("c_norm"), eps)
    dt = jax.nn.softplus(_blocks(lambda b: _mm(b, f("w_dt")), d, t_block)
                         + f("dt_bias"))                      # (S, D)
    A = -jnp.exp(f("A_log")).T                                # (N, D)

    def step(state, xs):                                      # (N, D)
        dt_t, x_t, b_t, c_t = xs
        state = (jnp.exp(dt_t[None, :] * A) * state
                 + (dt_t * x_t)[None, :] * b_t[:, None])
        return state, jnp.sum(state * c_t[:, None], 0)
    _, y = jax.lax.scan(step, jnp.zeros((N, D), F32), (dt, x, B, C))
    y = (y + f("D") * x) * jax.nn.silu(z)
    return _blocks(lambda b: _mm(b, f("w_out")), y, t_block)


def attention(u, lp, c: Dict, q_block: int):
    """u (S, h) -> causal softmax attention's output (S, h), no positions."""
    S = u.shape[0]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // nh
    f = lambda name: lp[name].astype(F32)
    q = _mm(u, f("wq")).reshape(S, nh, d)
    k = jnp.repeat(_mm(u, f("wk")).reshape(S, nkv, d), nh // nkv, 1)
    v = jnp.repeat(_mm(u, f("wv")).reshape(S, nkv, d), nh // nkv, 1)
    qb = min(q_block, S)
    pad = -S % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, nh, d)
    j = jnp.arange(S)[None, :]

    def block(_, xs):
        qi, i0 = xs
        i = i0 + jnp.arange(qb)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(j <= i, s, -1e30), -1)
        return None, jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    _, o = jax.lax.scan(block, None, (qp, jnp.arange(qp.shape[0]) * qb))
    return _mm(o.reshape(-1, nh * d)[:S], f("wo"))


def mlp(u, lp, t_block: int):
    """u (S, h) -> the dense SwiGLU MLP's output (S, h)."""
    f = lambda name: lp[name].astype(F32)

    def one(b):
        return _mm(jax.nn.silu(_mm(b, f("wg"))) * _mm(b, f("wu")), f("wd"))
    return _blocks(one, u, t_block)


def hidden(params: Dict, tokens, c: Dict, q_block: int = 256,
           t_block: int = 1024):
    """tokens (S,) -> final-norm hidden states (S, h), float32. ``params``
    is the tree the system under test is handed: a stack a part kind
    (``mamba1``, ``attention``, ``mlp``) in model order."""
    eps = c["rms_norm_eps"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    seen = {"mamba1": 0, "attention": 0}
    for i in range(c["num_hidden_layers"]):
        name = "attention" if is_attention(c, i) else "mamba1"
        lp = jax.tree.map(lambda a: a[seen[name]], params["layers"][name])
        seen[name] += 1
        u = _rms(x, lp["norm"].astype(F32), eps)
        x = x + (attention(u, lp, c, q_block) if name == "attention"
                 else mamba(u, lp, c, t_block))
        lp = jax.tree.map(lambda a: a[i], params["layers"]["mlp"])
        x = x + mlp(_rms(x, lp["norm"].astype(F32), eps), lp, t_block)
    return _rms(x, params["final_norm"].astype(F32), eps)


def logits(params: Dict, rows, c: Dict):
    """hidden rows (n, h) -> logits (n, V); the head is the embedding."""
    head = (params["embed"].T if c["tie_word_embeddings"]
            else params["lm_head"])
    return _mm(rows, head.astype(F32))
