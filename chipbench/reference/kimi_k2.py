"""The plain reference for ``model_type: kimi_k2`` (Kimi-K2-Instruct, the
DeepSeek-V3 decoder at other numbers): latent attention (MLA) in every
layer, a leading dense layer, then expert layers with sigmoid routing, a
shared expert and THIS CHIP'S SHARE of the routed experts and of the
vocabulary. Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, the EXPANDED form of attention only (keys and values by head are
made from the latents): no cache, no kernel, no batching, no grouping, and
no absorbed projection, so the program's absorbed decode and chunk paths
are checked against the other formulation. It reads the configuration
file's own keys and imports nothing from the program.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``,
eps ``rms_norm_eps``; a final RMSNorm; an untied head. With ``u`` the normed
input:

- Attention: ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb``, a head ``[q^nope
  (qk_nope_head_dim) | q^rope (qk_rope_head_dim)]``; ``[c | k_r] = u
  W_kva``, ``c <- RMSNorm(c)``, ``k_r`` one rotary key for all heads;
  ``[k_h^nope | v_h] = c W_kvb`` a head. Rotary on ``q^rope`` and ``k_r``:
  ``inv_freq_i = theta^(-2i/d)`` over ``d = qk_rope_head_dim``; under
  ``rope_scaling.type: yarn`` the linear ramp between the two correction
  dimensions (``beta_fast`` and ``beta_slow`` rotations over
  ``original_max_position_embeddings``) blends ``inv_freq_i`` (below) and
  ``inv_freq_i / factor`` (above); cos and sin are multiplied by ``m(mscale)
  / m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``. The pairing is
  the rotate-half one (the configuration file's ``assumed.rope``). ``s_h =
  (q_h^nope . k_h^nope + q_h^rope . k_r) * (nope + rope)^-0.5 *
  m(mscale_all_dim)^2``; causal softmax; ``o_h = sum p v_h``; out
  ``concat_h(o_h) W_o``; no bias anywhere.
- Dense FFN (layers below ``first_k_dense_replace``): ``(silu(u W_g) * (u
  W_u)) W_d`` at ``intermediate_size``.
- Expert layer (the others): ``s = sigmoid(u W_r)`` over all
  ``router_outputs`` experts; the ``num_experts_per_tok`` largest of ``s +
  b``, ties to the lower index; ``w_e = routed_scaling_factor s_e / (sum of
  the chosen s + 1e-20)``; ``routed = sum_e w_e (silu(u W1_e) * (u W3_e))
  W2_e`` over the chosen experts THAT ARE HELD HERE (``first_expert .. +
  n_routed_experts``: what the others would add is left out, as on one chip
  of the deployment); plus one shared expert of the same form at
  ``moe_intermediate_size * n_shared_experts``, added once.

Departures from a textbook transcription, each for memory at the published
widths on one chip beside the bfloat16 weights and none changing a number: a
layer's weights are cast to float32 where they are used; attention is taken
over blocks of ``q_block`` queries, so that a 14k-token score matrix is never
whole, and over ``h_block`` heads at a time; the held experts are a loop
(``lax.scan``) over all of them, each taken out of the stack of all layers'
experts in its turn, applied to every token and weighed by whether the token
chose it (zero where not); the wide projections run over blocks of
``t_block`` tokens, and the dense layer's FFN over blocks of its inner
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


def mscale(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(c: Dict) -> float:
    rs = c.get("rope_scaling")
    m = mscale(rs["factor"], rs["mscale_all_dim"]) if rs else 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def rotary(c: Dict):
    """(inv_freq (d/2,), what multiplies cos and sin), in numpy float64."""
    d, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = c.get("rope_scaling")
    if not rs:
        return inv, 1.0
    if rs["type"] != "yarn":
        raise ValueError(rs["type"])
    orig, factor = rs["original_max_position_embeddings"], rs["factor"]

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    return inv, (mscale(factor, rs["mscale"])
                 / mscale(factor, rs["mscale_all_dim"]))


def _rotate(x, cos, sin):
    """x (S, ..., d), rotate-half; cos and sin (S, d/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    co, si = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * co - x2 * si, x2 * co + x1 * si], -1)


def attention(u, lp, c: Dict, cos, sin, q_block: int, t_block: int,
              h_block: int = 16):
    """u (S, h) -> latent attention's output (S, h), the expanded form; the
    heads ``h_block`` at a time."""
    S = u.shape[0]
    nh, R = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    eps = c["rms_norm_eps"]
    f = lambda name: lp[name].astype(F32)
    cq = _rms(_mm(u, f("wq_a")), f("q_norm"), eps)
    kv = _mm(u, f("wkv_a"))
    lat = _rms(kv[:, :R], f("kv_norm"), eps)
    k_r = _rotate(kv[:, R:], cos, sin)                      # (S, rope)
    scale = softmax_scale(c)
    hb = min(h_block, nh)
    qb = min(q_block, S)
    pad = -S % qb
    j = jnp.arange(S)[None, :]
    w_q = lp["wq_b"].reshape(-1, nh, nope + rope)
    w_kv = lp["wkv_b"].reshape(R, nh, nope + vd)

    def heads(g):
        cut = lambda w: jax.lax.dynamic_slice_in_dim(
            w, g * hb, hb, axis=1).astype(F32).reshape(w.shape[0], -1)
        wq, wkv = cut(w_q), cut(w_kv)
        q = _blocks(lambda b: _mm(b, wq), cq, t_block).reshape(
            S, hb, nope + rope)
        q = jnp.concatenate([q[..., :nope],
                             _rotate(q[..., nope:], cos, sin)], -1)
        kvb = _blocks(lambda b: _mm(b, wkv), lat, t_block).reshape(
            S, hb, nope + vd)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            k_r[:, None, :], (S, hb, rope))], -1)
        v = kvb[..., nope:]
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, qb, hb, nope + rope)

        def block(_, xs):
            qi, i0 = xs
            i = i0 + jnp.arange(qb)[:, None]
            s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
            p = jax.nn.softmax(jnp.where(j <= i, s, -1e30), -1)
            return None, jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
        _, o = jax.lax.scan(block, None, (qp, jnp.arange(qp.shape[0]) * qb))
        return o.reshape(-1, hb * vd)[:S]

    o = jax.lax.map(heads, jnp.arange(nh // hb))            # (groups, S, .)
    o = jnp.swapaxes(o, 0, 1).reshape(S, nh * vd)
    return _blocks(lambda b: _mm(b, f("wo")), o, t_block)


def _swiglu(u, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(u, wg)) * _mm(u, wu), wd)


def _dense(u, lp, t_block: int, i_block: int = 2304):
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


def experts(u, lp, stacks, layer: int, c: Dict, t_block: int):
    """u (S, h) -> the routed experts held here and the shared expert.
    ``stacks``: the three expert stacks of ALL expert layers ``(L, E_l,
    ...)``; one expert of layer ``layer`` is taken out at a time."""
    f = lambda name: lp[name].astype(F32)
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(u, f("moe_gate")))                  # (S, E)
    order = jnp.argsort(-(s + f("moe_bias")), axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    w = jnp.where(chosen, s, 0.0)
    w = c["routed_scaling_factor"] * w / (jnp.sum(w, -1, keepdims=True)
                                          + 1e-20)
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


def hidden(params: Dict, tokens, c: Dict, q_block: int = 128,
           t_block: int = 1024):
    """tokens (S,) -> final-norm hidden states (S, h), float32. ``params``
    is the tree the system under test is handed: ``dense_layers`` the
    leading layers' stack, ``layers`` the expert layers', its expert stacks
    holding this chip's experts."""
    eps = c["rms_norm_eps"]
    S = tokens.shape[0]
    inv, mult = rotary(c)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * mult, F32)
    sin = jnp.asarray(np.sin(ang) * mult, F32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    lead = c["first_k_dense_replace"]
    stacks = tuple(params["layers"][n] for n in STACKS)
    small = {n: a for n, a in params["layers"].items() if n not in STACKS}
    for i in range(c["num_hidden_layers"]):
        dense = i < lead
        lp = jax.tree.map(lambda a: a[i if dense else i - lead],
                          params["dense_layers"] if dense else small)
        x = x + attention(_rms(x, lp["attn_norm"].astype(F32), eps), lp, c,
                          cos, sin, q_block, t_block)
        u = _rms(x, lp["mlp_norm"].astype(F32), eps)
        if dense:
            x = x + _dense(u, lp, t_block)
        else:
            x = x + experts(u, lp, stacks, i - lead, c, t_block)
    return _rms(x, params["final_norm"].astype(F32), eps)


def logits(params: Dict, rows, c: Dict):
    """hidden rows (n, h) -> logits (n, V) over this chip's slice of the
    vocabulary; the head is untied."""
    if c["tie_word_embeddings"]:
        raise ValueError("kimi_k2: the published head is untied")
    return _mm(rows, params["lm_head"].astype(F32))
