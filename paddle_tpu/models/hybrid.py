"""A hybrid model's layers: Mamba-2 or Mamba-1 mixers, attention without
rotary embedding, and expert or dense MLP feed-forward layers, one part a
layer.

``LlamaConfig.hybrid`` (``llama.HybridConfig``) says the model is of this
family; its ``layer_pattern`` names each layer of a period ``mamba2`` or
``mamba1``, ``attention``, ``experts`` or ``mlp``. Every layer is ``x <- x
+ f(RMSNorm(x))`` with ONE ``f``, so the kinds have different weights and
the parameters are a stack per kind, ``params["layers"][kind]`` with leaves
``(layers of the kind, ...)`` in model order; the expert stacks ``w1`` /
``w2`` hold the experts THIS process holds, ``first_expert`` says which. A
decoder layer of mixer and MLP (Jamba's) is two such layers.

What a layer keeps between calls (``llama.CACHE_OF_KIND``): an attention
layer its paged keys and values, in the one KV pool the cache manager
already runs; a state-space layer a row of recurrent state ``ssm`` in
float32 and the causal convolution's last ``conv_kernel - 1`` columns
``conv``, addressed by the row's slot (:func:`state_shapes`); an expert or
MLP layer nothing.

Three forwards share the layer bodies: :func:`trunk` (no cache, whole
sequences; the tests' plain path), :func:`forward_chunk` (one row's prefill
chunk from its state, inside ``generate.paged_prefill_chunk``) and
:func:`decode_forward` (one token a row, ``generate.paged_decode_forward``).

The Mamba-2 mixer, a token ``t`` of a row (``W_in``: h -> [z | xBC | dt]):
``xBC_t <- silu(b + sum_j w_j xBC_{t-K+1+j})`` depthwise and causal; ``xBC
-> x (H, P), B (G, N), C (G, N)``, head ``h`` using group ``h // (H/G)``; in
float32 ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``, ``S_t =
exp(dt A) S_{t-1} + dt x_t B_t^T``, ``y_t = S_t C_t + D x_t``; then the
gated norm, gate first, the mean square over each group's channels:
``y <- RMSNorm_groups(y silu(z)) g``; out ``y W_out``. A prefill chunk runs
the chunked form (sub-chunks of ``chunk_size``: quadratic inside, the state
handed on between); decode runs ``ops/pallas/ssm.py:ssm_state_update`` over
the pool in place.

The Mamba-1 mixer (Jamba's), ``D`` channels and ``N`` states: ``[x | z] =
u W_in``; ``x <- silu(b + sum_j w_j x_{t-K+1+j})`` depthwise and causal;
``[d | B | C] = x W_x``, each through its own RMSNorm; in float32 ``dt =
softplus(d W_dt + dt_bias)`` (D,), ``A = -exp(A_log)`` (D, N), ``S_t[n, d] =
exp(dt[d] A[d, n]) S_{t-1}[n, d] + dt[d] x_t[d] B_t[n]``, ``y_t = sum_n S_t
C_t + D x_t``; out ``(y silu(z)) W_out``. The state is ``(N, D)``, channels
last, the convolution's tail flat. A prefill chunk and a decode step both
run ``ops/pallas/mamba1.py`` (a scan token by token; the one-token
convolution step and state update over the pools in place).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import llama
from .llama import LlamaConfig, rms_norm
from ..ops.pallas.mamba1 import (mamba1_chunk_scan, mamba1_conv_step,
                                 mamba1_state_update)
from ..ops.pallas.ssm import ssm_state_update

_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def refuse(**on):
    """What a hybrid model's serving programs do not take, by name."""
    for name, value in on.items():
        if value:
            raise ValueError(
                f"{name} is not supported on a config with state-space "
                f"layers (one chip's programs, no adapters, no fused "
                f"kernels)")


# ---------------- parameters ----------------
def param_shapes(cfg: LlamaConfig, experts_held=None) -> Dict:
    """leaf -> (shape, how it is drawn): a fan-in (N(0, 1/fan_in)), "norm"
    (ones), "embed" (N(0, 0.02^2)), or the name of a state-space leaf's
    own rule (:func:`init_params`). ``experts_held``: the expert axis of
    the stacks (all the router's experts when None). Only the period's
    kinds have stacks; a tied head is the embedding."""
    hy, h, v = cfg.hybrid, cfg.hidden_size, cfg.vocab_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    def mamba2(L):
        di, cd, H = hy.d_inner, hy.conv_dim, hy.ssm_heads
        return {"norm": ((L, h), "norm"),
                "w_in": ((L, h, di + cd + H), h),
                "conv_w": ((L, hy.conv_kernel, cd), hy.conv_kernel),
                "conv_b": ((L, cd), "zeros"),
                "dt_bias": ((L, H), "dt_bias"),
                "A_log": ((L, H), "A_log"), "D": ((L, H), "ones"),
                "gate_norm": ((L, di), "norm"),
                "w_out": ((L, di, h), di)}

    def mamba1(L):
        D, N, R = hy.channels, hy.ssm_state, hy.dt_rank
        return {"norm": ((L, h), "norm"),
                "w_in": ((L, h, 2 * D), h),
                "conv_w": ((L, hy.conv_kernel, D), hy.conv_kernel),
                "conv_b": ((L, D), "zeros"),
                "w_x": ((L, D, R + 2 * N), D),
                "dt_norm": ((L, R), "norm"), "b_norm": ((L, N), "norm"),
                "c_norm": ((L, N), "norm"),
                "w_dt": ((L, R, D), R), "dt_bias": ((L, D), "dt_bias"),
                "A_log": ((L, D, N), "A_log"), "D": ((L, D), "ones"),
                "w_out": ((L, D, h), D)}

    def attention(L):
        return {"norm": ((L, h), "norm"),
                "wq": ((L, h, nh * hd), h), "wk": ((L, h, nkv * hd), h),
                "wv": ((L, h, nkv * hd), h), "wo": ((L, nh * hd, h), nh * hd)}

    def experts(L):
        E = cfg.moe.num_experts
        El = E if experts_held is None else experts_held
        lat, ie, ish = hy.latent_size, hy.expert_size, hy.shared_size
        return {"norm": ((L, h), "norm"),
                "router": ((L, h, E), h),
                "router_bias": ((L, E), "router_bias"),
                "w_down": ((L, h, lat), h), "w_up": ((L, lat, h), lat),
                "w1": ((L, El, lat, ie), lat), "w2": ((L, El, ie, lat), ie),
                "ws1": ((L, h, ish), h), "ws2": ((L, ish, h), ish),
                "first_expert": ((L,), "first_expert")}

    def mlp(L):
        i = cfg.intermediate_size
        return {"norm": ((L, h), "norm"), "wg": ((L, h, i), h),
                "wu": ((L, h, i), h), "wd": ((L, i, h), i)}

    kinds = {"mamba2": mamba2, "mamba1": mamba1, "attention": attention,
             "experts": experts, "mlp": mlp}
    return {"embed": ((v, h), "embed"), "final_norm": ((h,), "norm"),
            **({} if cfg.tie_embeddings else {"lm_head": ((h, v), h)}),
            "layers": {k: f(cfg.kind_layers(k)) for k, f in kinds.items()
                       if k in cfg.period}}


def state_shapes(cfg: LlamaConfig):
    """What a row keeps a state-space layer: ``(ssm, conv)`` shapes. Mamba-2
    ``(head_dim, state, heads)``, heads last, and the convolution's tail
    ``(conv_kernel - 1, conv_dim)``; Mamba-1 ``(state, channels)``,
    channels last (``ops/pallas/ssm.py`` and ``mamba1.py`` have the
    reasons), and its tail flat, ``((conv_kernel - 1) * channels,)``, tap
    after tap. The Mamba-1 pools are written in a decode step by kernels
    that alias them (``mamba1_conv_step``, ``mamba1_state_update``) and in
    a chunk program by the chunk's one-row update (:func:`forward_chunk`);
    a new kind of state keeps to the same rule: rows laid as whole tiles,
    and in each program one writer that takes the pool as it is stored."""
    hy = cfg.hybrid
    if cfg.state_kind == "mamba1":
        # laid (K - 1, channels) in bf16, the 3 taps pad a tile of 16
        # sublanes and XLA:TPU re-lays the whole pool twice in every layer
        # of a decode step to cut and write a batch of rows (PERF.md §5)
        return ((hy.ssm_state, hy.channels),
                ((hy.conv_kernel - 1) * hy.channels,))
    return ((hy.ssm_head_dim, hy.ssm_state, hy.ssm_heads),
            (hy.conv_kernel - 1, hy.conv_dim))


#: leaves kept in float32 whatever the model's dtype
F32_LEAVES = ("router", "router_bias", "dt_bias", "A_log", "D")


def draw_leaf(key, shape, kind, dtype, first_expert: int = 0):
    """One leaf of :func:`param_shapes` by its rule. ``A_log = log U(1,
    16)``; ``dt_bias`` the inverse softplus of ``U(1e-3, 0.1)`` floored at
    1e-4 (the published initialisation's ``time_step_min/max/floor``); the
    router's selection bias N(0, 0.01^2)."""
    if kind in ("norm", "ones"):
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "first_expert":
        return jnp.full(shape, first_expert, jnp.int32)
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(0.1))), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "router_bias":
        return 0.01 * n
    return (n * (0.02 if kind == "embed" else kind ** -0.5)).astype(dtype)


def init_params(key: jax.Array, cfg: LlamaConfig, experts_held=None,
                first_expert: int = 0) -> Dict:
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg, experts_held), is_leaf=is_leaf)
    out = []
    for k, (path, (shape, kind)) in zip(
            jax.random.split(key, len(with_paths)), with_paths):
        name = path[-1].key
        out.append(draw_leaf(
            k, shape, kind,
            jnp.float32 if name in F32_LEAVES else cfg.dtype, first_expert))
    return jax.tree.unflatten(treedef, out)


# ---------------- the Mamba-2 mixer ----------------
def _split_in(zxbcdt, hy):
    di, cd = hy.d_inner, hy.conv_dim
    return (zxbcdt[..., :di], zxbcdt[..., di:di + cd],
            zxbcdt[..., di + cd:])


def _split_xbc(xbc, hy):
    """Post-convolution ``xBC`` (..., conv_dim) -> float32 x (..., H, P),
    B and C (..., G, N)."""
    di, gn = hy.d_inner, hy.ssm_groups * hy.ssm_state
    lead = xbc.shape[:-1]
    f = xbc.astype(jnp.float32)
    return (f[..., :di].reshape(lead + (hy.ssm_heads, hy.ssm_head_dim)),
            f[..., di:di + gn].reshape(lead + (hy.ssm_groups, hy.ssm_state)),
            f[..., di + gn:].reshape(lead + (hy.ssm_groups, hy.ssm_state)))


def _gated_norm(y, z, g, hy, eps, dtype):
    """``RMSNorm_groups(y * silu(z)) * g``: y float32 (..., d_inner), the
    mean square over each of the groups' channels."""
    v = y * jax.nn.silu(z.astype(jnp.float32))
    vg = v.reshape(v.shape[:-1] + (hy.ssm_groups, -1))
    vg = vg * lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + eps)
    return vg.reshape(v.shape).astype(dtype) * g


def ssm_chunk_scan(x, dt, A, Bm, Cm, state, sub: int):
    """The chunked form of the recurrence over T tokens of one row, in
    float32. x (T, H, P); dt (T, H), 0 where a token is padding (it then
    neither decays nor feeds the state); A (H,); Bm, Cm (T, G, N); state
    (P, N, H), the pool's layout. Sub-chunks of ``sub`` tokens: inside
    one, ``y_t = sum_{s<=t} exp(a_t - a_s) (C_t . B_s) dt_s x_s`` with ``a``
    the running sum of ``dt A``; between, the state is handed on. Returns
    ``(y (T, H, P), state)``."""
    T, H, P = x.shape
    G = Bm.shape[1]
    rep = H // G
    Q = min(sub, T)
    pad = -T % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                         for a in (x, dt, Bm, Cm))
    nq = (T + pad) // Q
    cut = lambda a: a.reshape((nq, Q) + a.shape[1:])
    mask = jnp.tril(jnp.ones((Q, Q), bool))

    def step(S, inp):                                   # S (H, P, N)
        xq, dtq, Bq, Cq = inp
        cum = jnp.cumsum(dtq * A, axis=0)               # (Q, H), <= 0
        cb = jnp.repeat(jnp.einsum("tgn,sgn->gts", Cq, Bq, precision=_HI),
                        rep, axis=0)                    # (H, t, s)
        seg = cum.T[:, :, None] - cum.T[:, None, :]
        w = cb * jnp.exp(jnp.where(mask, seg, -jnp.inf)) * dtq.T[:, None, :]
        y = jnp.einsum("hts,shp->thp", w, xq, precision=_HI)
        Ch = jnp.repeat(Cq, rep, axis=1)                # (Q, H, N)
        y = y + (jnp.einsum("thn,hpn->thp", Ch, S, precision=_HI)
                 * jnp.exp(cum)[:, :, None])
        ws = jnp.exp(cum[-1][None] - cum) * dtq         # (Q, H)
        S = (S * jnp.exp(cum[-1])[:, None, None]
             + jnp.einsum("shp,shn->hpn", xq * ws[:, :, None],
                          jnp.repeat(Bq, rep, axis=1), precision=_HI))
        return S, y

    S, y = lax.scan(step, jnp.transpose(state.astype(jnp.float32), (2, 0, 1)),
                    tuple(cut(a) for a in (x, dt, Bm, Cm)))
    return (y.reshape((T + pad, H, P))[:T],
            jnp.transpose(S, (1, 2, 0)).astype(state.dtype))


def _mamba_seq(u, lp, cfg: LlamaConfig, state, tail, n_valid=None):
    """The mixer over T tokens of ONE row: ``u`` (T, h) the normed input,
    ``state`` (P, N, H) and ``tail`` (K - 1, conv_dim) what the row
    brought; ``n_valid`` (traced) the tokens that are not right-padding.
    Returns ``(out (T, h), state, tail)``."""
    from .generate import _w
    hy, dt_ = cfg.hybrid, u.dtype
    T, K = u.shape[0], hy.conv_kernel
    z, xbc, dt = _split_in(u @ _w(lp, "w_in", dt_), hy)
    seq = jnp.concatenate([tail.astype(dt_), xbc], axis=0)
    cw = lp["conv_w"].astype(jnp.float32)
    conv = lp["conv_b"].astype(jnp.float32) + sum(
        cw[j] * seq[j:j + T].astype(jnp.float32) for j in range(K))
    x, Bm, Cm = _split_xbc(jax.nn.silu(conv).astype(dt_), hy)
    n = T if n_valid is None else n_valid
    # the last K - 1 columns that came in, padding not counted
    tail = lax.dynamic_slice_in_dim(seq, n, K - 1, 0).astype(tail.dtype)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    dtv = jnp.where(jnp.arange(T)[:, None] < n, dtv, 0.0)
    with jax.named_scope("ssm_chunk_scan"):
        y, state = ssm_chunk_scan(x, dtv, -jnp.exp(lp["A_log"]), Bm, Cm,
                                  state, hy.chunk_size)
    y = y + lp["D"][None, :, None] * x
    y = _gated_norm(y.reshape(T, -1), z, lp["gate_norm"], hy, cfg.rms_eps,
                    dt_)
    return y @ _w(lp, "w_out", dt_), state, tail


def _mamba_decode(u, lp, cfg: LlamaConfig, ssm, conv, base, active,
                  use_kernel=None):
    """One token of every row: ``u`` (B, h); ``ssm`` / ``conv`` the pools
    of ALL state layers as ``(layers * slots, ...)``, this layer's rows
    from ``base`` on, row b in slot b. Rows not ``active`` keep their
    state and tail. Returns ``(out (B, h), ssm, conv)``."""
    from .generate import _w
    hy, dt_ = cfg.hybrid, u.dtype
    B = u.shape[0]
    rep = hy.ssm_heads // hy.ssm_groups
    z, xbc, dt = _split_in(u @ _w(lp, "w_in", dt_), hy)
    tail = lax.dynamic_slice_in_dim(conv, base, B, 0)     # (B, K - 1, cd)
    win = jnp.concatenate([tail.astype(dt_), xbc[:, None]], axis=1)
    c = lp["conv_b"].astype(jnp.float32) + jnp.sum(
        lp["conv_w"].astype(jnp.float32)[None] * win.astype(jnp.float32),
        axis=1)
    conv = lax.dynamic_update_slice_in_dim(
        conv, jnp.where(active[:, None, None], win[:, 1:].astype(conv.dtype),
                        tail), base, 0)
    x, Bm, Cm = _split_xbc(jax.nn.silu(c).astype(dt_), hy)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])  # (B, H)
    heads = lambda a: jnp.repeat(jnp.swapaxes(a, 1, 2), rep, axis=2)
    y, ssm = ssm_state_update(
        ssm, base, jnp.swapaxes(x * dtv[:, :, None], 1, 2),
        jnp.exp(dtv * -jnp.exp(lp["A_log"])), heads(Bm), heads(Cm), active,
        use_kernel=use_kernel)
    y = jnp.swapaxes(y, 1, 2) + lp["D"][None, :, None] * x     # (B, H, P)
    y = _gated_norm(y.reshape(B, -1), z, lp["gate_norm"], hy, cfg.rms_eps,
                    dt_)
    return y @ _w(lp, "w_out", dt_), ssm, conv


# ---------------- the Mamba-1 mixer ----------------
def _mamba1_in(u, lp, cfg: LlamaConfig):
    """``[x | z] = u W_in``, x first."""
    from .generate import _w
    xz = u @ _w(lp, "w_in", u.dtype)
    D = cfg.hybrid.channels
    return xz[..., :D], xz[..., D:]


def _mamba1_dbc(xc, lp, cfg: LlamaConfig):
    """The post-convolution ``x`` (..., D) -> float32 ``dt`` (..., D), B
    and C (..., N): ``x W_x`` cut into ``[d | B | C]``, each through its
    own RMSNorm, and ``dt = softplus(d W_dt + dt_bias)``."""
    from .generate import _w
    hy, eps = cfg.hybrid, cfg.rms_eps
    R, N = hy.dt_rank, hy.ssm_state
    dbc = xc @ _w(lp, "w_x", xc.dtype)
    d = rms_norm(dbc[..., :R], lp["dt_norm"], eps)
    b = rms_norm(dbc[..., R:R + N], lp["b_norm"], eps)
    c = rms_norm(dbc[..., R + N:], lp["c_norm"], eps)
    dt = jax.nn.softplus(jnp.matmul(d, _w(lp, "w_dt", d.dtype),
                                    preferred_element_type=F32)
                         + lp["dt_bias"])
    return dt, b.astype(F32), c.astype(F32)


def _mamba1_out(y, xc, z, lp, dtype):
    """``((y + D x) silu(z)) W_out``: y float32 (..., D)."""
    from .generate import _w
    y = (y + lp["D"] * xc.astype(F32)) * jax.nn.silu(z.astype(F32))
    return y.astype(dtype) @ _w(lp, "w_out", dtype)


def _decay(lp):
    """``A = -exp(A_log)`` as the state lies: (N, D)."""
    return -jnp.exp(lp["A_log"]).T


def _mamba1_seq(u, lp, cfg: LlamaConfig, state, tail, n_valid=None,
                use_kernel=None):
    """The Mamba-1 mixer over T tokens of ONE row: ``u`` (T, h) the normed
    input, ``state`` (N, D) and ``tail`` ((K - 1) * D,) what the row
    brought; ``n_valid`` (traced) the tokens that are not right-padding.
    Returns ``(out (T, h), state, tail)``."""
    dt_ = u.dtype
    T, K = u.shape[0], cfg.hybrid.conv_kernel
    x, z = _mamba1_in(u, lp, cfg)
    seq = jnp.concatenate([tail.reshape(K - 1, -1).astype(dt_), x], axis=0)
    cw = lp["conv_w"].astype(F32)
    conv = lp["conv_b"].astype(F32) + sum(
        cw[j] * seq[j:j + T].astype(F32) for j in range(K))
    xc = jax.nn.silu(conv).astype(dt_)
    n = T if n_valid is None else n_valid
    # the last K - 1 columns that came in, padding not counted
    tail = lax.dynamic_slice_in_dim(seq, n, K - 1, 0).astype(
        tail.dtype).reshape(tail.shape)
    dt, Bm, Cm = _mamba1_dbc(xc, lp, cfg)
    dt = jnp.where(jnp.arange(T)[:, None] < n, dt, 0.0)
    y, state = mamba1_chunk_scan(dt, xc.astype(F32), _decay(lp), Bm, Cm,
                                 state, use_kernel=use_kernel)
    return _mamba1_out(y, xc, z, lp, dt_), state, tail


def _mamba1_decode(u, lp, cfg: LlamaConfig, ssm, conv, base, active,
                   use_kernel=None):
    """One token of every row: ``u`` (B, h); ``ssm`` / ``conv`` the pools
    of ALL state layers as ``(layers * slots, ...)``, this layer's rows
    from ``base`` on, row b in slot b. Rows not ``active`` keep their
    state and tail. Each pool has one writer here, a kernel that aliases
    it to its result (``ops/pallas/mamba1.py``: the convolution step the
    flat tails, the state update the state), so the pools stay in the
    layout they are stored in. Returns ``(out (B, h), ssm, conv)``."""
    dt_ = u.dtype
    x, z = _mamba1_in(u, lp, cfg)
    c, conv = mamba1_conv_step(conv, base, x, lp["conv_w"], lp["conv_b"],
                               active, use_kernel=use_kernel)
    xc = jax.nn.silu(c).astype(dt_)
    dt, Bm, Cm = _mamba1_dbc(xc, lp, cfg)
    y, ssm = mamba1_state_update(ssm, base, dt, xc.astype(F32), _decay(lp),
                                 Bm, Cm, active, use_kernel=use_kernel)
    return _mamba1_out(y, xc, z, lp, dt_), ssm, conv


def _mlp(u, lp):
    """The dense MLP layer: ``(silu(u W_gate) * u W_up) W_down``."""
    from .generate import _swiglu
    return _swiglu(u, lp, "wg", "wu", "wd")


# ---------------- walking the layers ----------------
def _layer(stack, idx):
    """Layer ``idx`` (a number or a traced index) of one kind's stack."""
    if isinstance(idx, int):
        return jax.tree.map(lambda a: a[idx], stack)
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), stack)


def _over_periods(cfg: LlamaConfig, body, carry):
    """``body(carry, i)`` for every period ``i`` in turn. One period is
    called as it is, with ``i`` the number 0: the layers' stacks are then
    cut at fixed places. More are a ``lax.scan`` whose carry holds whatever
    the layers hand on (the pools, whole)."""
    n = cfg.num_layers // len(cfg.period)
    if n == 1:
        return body(carry, 0)
    return lax.scan(lambda c, i: (body(c, i), None), carry,
                    jnp.arange(n, dtype=jnp.int32))[0]


def _positions(cfg: LlamaConfig):
    """For each layer of a period: (kind, its place among the period's
    layers of that kind, how many of the kind a period has)."""
    period = cfg.period
    return [(kind, period[:j].count(kind), period.count(kind))
            for j, kind in enumerate(period)]


def _expert_stacks(params):
    """The expert layers' small leaves and their two expert stacks (None
    without expert layers)."""
    e = params["layers"].get("experts")
    if e is None:
        return None, None
    lp = {n: a for n, a in e.items() if n not in ("w1", "w2")}
    return lp, (e["w1"], e["w2"])


def _head(params, x, cfg):
    from .generate import _w
    head = (params["embed"].T.astype(x.dtype) if cfg.tie_embeddings
            else _w(params, "lm_head", x.dtype))
    return (x @ head).astype(jnp.float32)


def _qkv(u, lp, cfg: LlamaConfig, nkv: int):
    """The attention layer's three projections of ``u`` (B, T, h), by
    heads; no rotary embedding is applied to them."""
    from .generate import _project_heads, _w
    return (_project_heads(u, _w(lp, "wq", u.dtype), cfg.num_heads),
            _project_heads(u, _w(lp, "wk", u.dtype), nkv),
            _project_heads(u, _w(lp, "wv", u.dtype), nkv))


#: a KV cache's arrays; the scales only on the int8 tier
KV_NAMES = ("k", "v", "ks", "vs")


def trunk(params, tokens, cfg: LlamaConfig):
    """No cache: tokens (B, S) -> the final norm's hidden states (B, S,
    h). Every row starts from zero state."""
    from .generate import _latent_moe_ffn, _w
    B, S = tokens.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    e_lp, experts = _expert_stacks(params)
    s_shape, t_shape = state_shapes(cfg)
    state0 = jnp.zeros(s_shape, jnp.float32)
    tail0 = jnp.zeros(t_shape, cfg.dtype)

    def body(x, i):
        for kind, at, count in _positions(cfg):
            idx = i * count + at
            if kind == "mlp":
                lp = _layer(params["layers"]["mlp"], idx)
                f = _mlp(rms_norm(x, lp["norm"], cfg.rms_eps), lp)
            elif kind == "mamba1":
                lp = _layer(params["layers"]["mamba1"], idx)
                f = jax.vmap(lambda u: _mamba1_seq(
                    u, lp, cfg, state0, tail0)[0])(
                    rms_norm(x, lp["norm"], cfg.rms_eps))
            elif kind == "experts":
                lp = _layer(e_lp, idx)
                f, _ = _latent_moe_ffn(
                    rms_norm(x, lp["norm"], cfg.rms_eps), lp, cfg, experts,
                    idx)
            elif kind == "mamba2":
                lp = _layer(params["layers"]["mamba2"], idx)
                f = jax.vmap(lambda u: _mamba_seq(
                    u, lp, cfg, state0, tail0)[0])(
                    rms_norm(x, lp["norm"], cfg.rms_eps))
            else:
                lp = _layer(params["layers"]["attention"], idx)
                u = rms_norm(x, lp["norm"], cfg.rms_eps)
                q, k, v = _qkv(u, lp, cfg, nkv)
                f = llama._attention(q, k, v, causal=True).reshape(
                    B, S, nh * hd) @ _w(lp, "wo", x.dtype)
            x = x + f
        return x

    x = _over_periods(cfg, body, x)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward_chunk(params, tokens, dense: Dict, state: Dict, slot, pos: int,
                  cfg: LlamaConfig, *, kstart, ctx_len, chunk_len,
                  use_kernel=None):
    """One row's prefill chunk. tokens (1, C) right-padded past
    ``chunk_len``; ``dense`` the attention layers' temp cache ``(layers,
    1, W, nkv, hd)`` with the row's context right-aligned below ``pos``
    (``generate.paged_prefill_chunk``); ``state`` the pools ``ssm`` /
    ``conv``, the row's in ``slot``. At ``ctx_len`` 0 the row starts from
    zeros. Returns ``(logits (1, V) at the last valid token, dense, the
    pools with the row's state after ``chunk_len`` tokens, the expert
    stats)``."""
    from .generate import (_attn_with_cache, _cache_write, _latent_moe_ffn,
                           _w)
    B, C = tokens.shape
    nh, nkv, hd = cfg.num_heads, dense["k"].shape[3], cfg.hd
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    e_lp, experts = _expert_stacks(params)
    valid = (jnp.arange(C, dtype=jnp.int32) < chunk_len)[None, :]
    fresh = ctx_len == 0

    def body(carry, i):
        x, dense, state, stats = carry
        for kind, at, count in _positions(cfg):
            idx = i * count + at
            if kind == "experts":
                lp = _layer(e_lp, idx)
                f, st = _latent_moe_ffn(
                    rms_norm(x, lp["norm"], cfg.rms_eps), lp, cfg, experts,
                    idx, valid=valid, use_kernel=use_kernel)
                stats = stats + st
            elif kind == "mlp":
                lp = _layer(params["layers"]["mlp"], idx)
                f = _mlp(rms_norm(x, lp["norm"], cfg.rms_eps), lp)
            elif kind in ("mamba2", "mamba1"):
                lp = _layer(params["layers"][kind], idx)
                # the row's state cut out of the pool where it lies; the
                # barrier keeps the Mamba-2 scan's re-layout of it (heads
                # first) from being hoisted above the cut, onto the whole
                # pool
                s0, t0 = lax.optimization_barrier(tuple(
                    lax.dynamic_slice(
                        state[n], (idx, slot) + (0,) * (state[n].ndim - 2),
                        (1, 1) + state[n].shape[2:])[0, 0]
                    for n in ("ssm", "conv")))
                u = rms_norm(x, lp["norm"], cfg.rms_eps)[0]
                s0, t0 = jnp.where(fresh, 0, s0), jnp.where(fresh, 0, t0)
                if kind == "mamba1":
                    f, s1, t1 = _mamba1_seq(u, lp, cfg, s0, t0,
                                            n_valid=chunk_len,
                                            use_kernel=use_kernel)
                else:
                    f, s1, t1 = _mamba_seq(u, lp, cfg, s0, t0,
                                           n_valid=chunk_len)
                f = f[None]
                state = {
                    n: lax.dynamic_update_slice(
                        state[n], new[None, None].astype(state[n].dtype),
                        (idx, slot) + (0,) * new.ndim)
                    for n, new in (("ssm", s1), ("conv", t1))}
            else:
                lp = _layer(params["layers"]["attention"], idx)
                u = rms_norm(x, lp["norm"], cfg.rms_eps)
                q, k, v = _qkv(u, lp, cfg, nkv)
                mine = [None if n not in dense else lax.dynamic_index_in_dim(
                    dense[n], idx, 0, False) for n in KV_NAMES]
                new = _cache_write(*mine, k, v, pos)
                o = _attn_with_cache(q, new[0], new[1], pos + C, nh,
                                     use_kernel=use_kernel, kstart=kstart,
                                     k_rows=new[2], v_rows=new[3])
                f = o.reshape(B, C, nh * hd) @ _w(lp, "wo", x.dtype)
                dense = {n: lax.dynamic_update_index_in_dim(
                    dense[n], a, idx, 0)
                    for n, a in zip(KV_NAMES, new) if a is not None}
            x = x + f
        return x, dense, state, stats

    x, dense, state, stats = _over_periods(
        cfg, body, (x, dense, state, jnp.zeros((4,), jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    x = lax.dynamic_slice_in_dim(
        x, jnp.clip(chunk_len - 1, 0, C - 1), 1, axis=1)
    return _head(params, x[:, -1], cfg), dense, state, stats


def decode_forward(params, tokens, paged: Dict, block_tables, lengths,
                   cfg: LlamaConfig, *, active=None, use_kernel=None):
    """One decode step over the ragged batch (the contract of
    ``generate.paged_decode_forward``): row b's state is slot b of the
    state pool. The KV pool and the state pools ride through the layers
    whole, as ``(layers * pages, ...)`` and ``(layers * slots, ...)``, each
    layer writing its rows where they lie; donated, they are updated in
    place. Returns ``(logits (B, V), pools, expert stats)``."""
    from .generate import _latent_moe_ffn, _paged_kv_attend, _w
    B = tokens.shape[0]
    page = paged["k"].shape[2]
    nh, nkv, hd = cfg.num_heads, paged["k"].shape[3], cfg.hd
    if active is None:
        active = jnp.ones((B,), bool)
    lengths = jnp.asarray(lengths, jnp.int32)
    slots = paged["ssm"].shape[1]
    if B != slots:
        raise ValueError(f"decode_forward: {B} rows over a state pool of "
                         f"{slots} slots (row b's state is slot b)")
    pages = paged["k"].shape[1]
    dst = jnp.where(active, block_tables[jnp.arange(B), lengths // page]
                    * page + lengths % page, 0)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)  # (B, h)
    e_lp, experts = _expert_stacks(params)
    # K/V pools as pages (layers * P, page, ...), the int8 tier's scales
    # as the lane rows the kernel reads; state as (layers * slots, ...)
    flat = {n: (paged[n].reshape((-1, 1, page * nkv)) if n in ("ks", "vs")
                else paged[n].reshape((-1,) + paged[n].shape[2:]))
            for n in paged}

    def body(carry, i):
        x, held, stats = carry
        for kind, at, count in _positions(cfg):
            idx = i * count + at
            if kind == "experts":
                lp = _layer(e_lp, idx)
                f, st = _latent_moe_ffn(
                    rms_norm(x, lp["norm"], cfg.rms_eps)[:, None], lp, cfg,
                    experts, idx, valid=active[:, None],
                    use_kernel=use_kernel)
                f, stats = f[:, 0], stats + st
            elif kind == "mlp":
                lp = _layer(params["layers"]["mlp"], idx)
                f = _mlp(rms_norm(x, lp["norm"], cfg.rms_eps), lp)
            elif kind in ("mamba2", "mamba1"):
                lp = _layer(params["layers"][kind], idx)
                f, ssm, conv = (
                    _mamba1_decode if kind == "mamba1" else _mamba_decode)(
                    rms_norm(x, lp["norm"], cfg.rms_eps), lp, cfg,
                    held["ssm"], held["conv"], idx * slots, active,
                    use_kernel=use_kernel)
                held = {**held, "ssm": ssm, "conv": conv}
            else:
                lp = _layer(params["layers"]["attention"], idx)
                u = rms_norm(x, lp["norm"], cfg.rms_eps)
                q, k, v = _qkv(u[:, None], lp, cfg, nkv)
                base = idx * pages
                o, *new = _paged_kv_attend(
                    q, k, v, tuple(held.get(n) for n in KV_NAMES),
                    dst + base * page, block_tables + base, lengths, page,
                    use_kernel=use_kernel, dtype=cfg.dtype)
                held = {**held, **{n: a for n, a in zip(KV_NAMES, new)
                                   if a is not None}}
                f = o.reshape(B, nh * hd) @ _w(lp, "wo", x.dtype)
            x = x + f
        return x, held, stats

    x, held, stats = _over_periods(
        cfg, body, (x, flat, jnp.zeros((4,), jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return (_head(params, x, cfg),
            {n: a.reshape(paged[n].shape) for n, a in held.items()}, stats)
