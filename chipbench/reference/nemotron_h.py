"""The plain reference for ``model_type: nemotron_h`` (NVIDIA-Nemotron-3-
Super-120B-A12B): Mamba-2 layers, attention layers without rotary embedding
and LatentMoE layers, one part a layer, with THIS CHIP'S SHARE of the routed
experts and of the vocabulary. Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision: no cache, no kernel, no batching, no grouping,
no chunked scan. It reads the configuration file's own keys and imports
nothing from the program.

Every layer is ``x <- x + f(RMSNorm(x; eps))``; the layers run are the first
``num_hidden_layers`` letters of ``hybrid_override_pattern``:

- ``M``: ``[z | xBC | dt] = u W_in``; ``xBC_t <- silu(b + sum_j w_j
  xBC_{t-K+1+j})``, depthwise, causal, zeros before the first token;
  ``xBC -> x (H, P), B (G, N), C (G, N)``, head h using group ``h // (H/G)``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; a ``lax.scan`` over
  the tokens: ``S_t = exp(dt A) S_{t-1} + dt x_t B_t^T`` a head, ``y_t =
  S_t C_t + D x_t``; the gated norm, gate first: ``y <- RMSNorm_groups(y
  silu(z)) g`` with the mean square over each of the G groups of channels;
  ``f = y W_out``.
- ``*``: ``q = u Wq``, ``k = u Wk``, ``v = u Wv`` without bias and WITHOUT
  rotary embedding; causal softmax at ``1/sqrt(head_dim)``; ``f = o Wo``.
- ``E``: ``s = sigmoid(u W_r)`` over all ``router_outputs`` experts; the
  ``num_experts_per_tok`` largest of ``s + bias``, ties to the lower index;
  ``w_e = routed_scaling_factor s_e / sum of the chosen s``; ``l = u
  W_down``; ``r = sum_e w_e relu(l W1_e)^2 W2_e`` over the chosen experts
  THAT ARE HELD HERE (``first_expert .. + n_routed_experts``: what the
  others would add is left out, as on one chip of the deployment);
  ``f = r W_up + relu(u Ws1)^2 Ws2`` (the shared expert, at full width).

Departures from a textbook transcription, each for memory at the published
widths on one chip beside the bfloat16 weights and none changing a number: a
layer's weights are cast to float32 where they are used; attention is taken
over blocks of ``q_block`` queries, so that a 12k-token score matrix is never
whole; the held experts are a loop (``lax.scan``) over all of them, each
taken out of the stack of all layers' experts in its turn, applied to every
token and masked by whether the token chose it; the recurrence's step cuts
its token's x, B and C out of the row and spreads the groups to heads there; the wide
projections (``W_in``, the shared expert) run over blocks of ``t_block``
tokens. The MTP module is not part of the next-token forward pass and is not
here.
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


def mamba(u, lp, c: Dict, t_block: int):
    """u (S, h) -> the mixer's output (S, h)."""
    S = u.shape[0]
    H, P, G, N, K = (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                     c["ssm_state_size"], c["conv_kernel"])
    di, gn = H * P, G * N
    f = lambda name: lp[name].astype(F32)
    # [z | xBC | dt] = u W_in, a column range of W_in at a time
    w_in = lp["w_in"]
    z, xbc, dt = (
        _blocks(lambda b, lo=lo, hi=hi: _mm(b, w_in[:, lo:hi].astype(F32)),
                u, t_block)
        for lo, hi in ((0, di), (di, 2 * di + 2 * gn),
                       (2 * di + 2 * gn, w_in.shape[1])))
    seq = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    cw = f("conv_w")
    xbc = jax.nn.silu(f("conv_b") + sum(cw[j] * seq[j:j + S]
                                         for j in range(K)))
    dt = jax.nn.softplus(dt + f("dt_bias"))                  # (S, H)
    A, D = -jnp.exp(f("A_log")), f("D")

    def step(state, xs):                                     # (H, P, N)
        row, dt_t = xs                                       # (cd,), (H,)
        x_t = row[:di].reshape(H, P)
        b_t = jnp.repeat(row[di:di + gn].reshape(G, N), H // G, axis=0)
        c_t = jnp.repeat(row[di + gn:].reshape(G, N), H // G, axis=0)
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y_t = jnp.sum(state * c_t[:, None, :], -1) + D[:, None] * x_t
        return state, y_t.reshape(di)
    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xbc, dt))
    v = (y * jax.nn.silu(z)).reshape(S, G, di // G)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + c["norm_eps"])
    return _blocks(lambda b: _mm(b, f("w_out")),
                   v.reshape(S, di) * f("gate_norm"), t_block)


def attention(u, lp, c: Dict, q_block: int):
    """u (S, h) -> causal softmax attention's output (S, h), no rotary."""
    S = u.shape[0]
    nh, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
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


def experts(u, lp, stacks, layer: int, c: Dict, t_block: int):
    """u (S, h) -> the routed experts held here and the shared expert.
    ``stacks``: the two expert stacks of ALL layers ``(L, E_l, ...)``; one
    expert of layer ``layer`` is taken out at a time."""
    f = lambda name: lp[name].astype(F32)
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(u, f("router")))                    # (S, E)
    order = jnp.argsort(-(s + f("router_bias")), axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    w = jnp.where(chosen, s, 0.0)
    w = c["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True)
    El = stacks[0].shape[1]
    held = jax.lax.dynamic_slice_in_dim(w, lp["first_expert"], El, axis=1)
    w1, w2 = (a.reshape((-1,) + a.shape[2:]) for a in stacks)
    lat = _mm(u, f("w_down"))

    def one(acc, xs):
        e, we = xs
        take = lambda a: jax.lax.dynamic_index_in_dim(
            a, layer * El + e, 0, keepdims=False).astype(F32)
        r = jnp.maximum(_mm(lat, take(w1)), 0.0)
        return acc + we[:, None] * _mm(r * r, take(w2)), None
    routed, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                             (jnp.arange(El), held.T))

    def shared(b):
        r = jnp.maximum(_mm(b, f("ws1")), 0.0)
        return _mm(r * r, f("ws2"))
    return _mm(routed, f("w_up")) + _blocks(shared, u, t_block)


def hidden(params: Dict, tokens, c: Dict, q_block: int = 256,
           t_block: int = 1024):
    """tokens (S,) -> final-norm hidden states (S, h), float32. ``params``
    is the tree the system under test is handed: a stack a layer kind, the
    expert stacks holding this chip's experts."""
    eps = c["norm_eps"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    e_all = params["layers"]["experts"]
    stacks = (e_all["w1"], e_all["w2"])
    small = {n: a for n, a in e_all.items() if n not in ("w1", "w2")}
    seen = dict.fromkeys("M*E", 0)
    for letter in c["hybrid_override_pattern"][:c["num_hidden_layers"]]:
        i = seen[letter]
        seen[letter] += 1
        if letter == "E":
            lp = jax.tree.map(lambda a: a[i], small)
            f = experts(_rms(x, lp["norm"].astype(F32), eps), lp, stacks, i,
                        c, t_block)
        else:
            name, fn = (("mamba2", mamba) if letter == "M"
                        else ("attention", attention))
            lp = jax.tree.map(lambda a: a[i], params["layers"][name])
            f = fn(_rms(x, lp["norm"].astype(F32), eps), lp, c,
                   t_block if letter == "M" else q_block)
        x = x + f
    return _rms(x, params["final_norm"].astype(F32), eps)


def logits(params: Dict, rows, c: Dict):
    """hidden rows (n, h) -> logits (n, V) over this chip's slice of the
    vocabulary; the head is untied."""
    if c["tie_word_embeddings"]:
        raise ValueError("nemotron_h: the published head is untied")
    return _mm(rows, params["lm_head"].astype(F32))
