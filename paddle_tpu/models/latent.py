"""A decoder of latent-attention layers (MLA; the ``deepseek_v3`` /
``kimi_k2`` family): attention + MLP pairs whose attention keeps ONE latent
a token in place of keys and values by head, with leading dense layers
before the expert layers.

``LlamaConfig.latent`` (``llama.LatentConfig``) says the model is of this
family; its period is ``("latent",)``. Every layer is ``h = x + Attn(
RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``. The first ``cfg.dense_layers``
layers have a dense SwiGLU at ``intermediate_size`` and are a stack of their
own, ``params["dense_layers"]``, run as a prologue; the others
(``params["layers"]``, scanned) have the expert layer ``generate._moe_ffn``
(routing rule, shared expert and the share of the experts held: its
docstring).

Attention, with ``u`` the normed input: ``c_q = RMSNorm(u W_qa)``, ``q =
c_q W_qb``, a head ``[q^nope | q^rope]``; ``[c | k_r] = u W_kva``, ``c <-
RMSNorm(c)``, ``k_r`` ONE rotary key shared by all heads; ``[k_h^nope |
v_h] = c W_kvb`` a head; rotary on ``q^rope`` and ``k_r`` (``rope_dim``
lanes, ``rope_theta`` under ``cfg.yarn``); ``s_h = (q_h^nope . k_h^nope +
q_h^rope . k_r) * scale`` (``LatentConfig.scale``), causal softmax in
float32, ``o_h = sum p v_h``, out ``concat_h(o_h) W_o``. What a token keeps
a layer is the row ``[c | k_r]`` (``LatentConfig.row`` numbers), in the
paged pool ``c`` (``generate.init_paged_cache``), which lays a row out in
whole 128-lane tiles (``LatentConfig.row_lanes``), zeros past the key.

Two forms of the same function. EXPANDED (:func:`trunk`, no cache: the
tests' plain path and ``llama.forward``): keys and values by head are made
from the latents and attention is the usual one. ABSORBED (decode and
chunk, over the pool): with ``W_kvb`` cut a head into ``W_UK,h`` and
``W_UV,h`` — slices of the matrix as stored, no second copy — ``q_h^lat =
q_h^nope W_UK,h^T`` is taken into the latent, ``s_h = (q_h^lat . c + q_h^rope
. k_r) * scale`` and ``o_h^lat = sum p c`` stay there, and ``o_h = o_h^lat
W_UV,h`` comes back out: all heads read the same cached rows, as key (all
lanes) and as value (the latent's). Decode is one kernel over all live rows
(``ops/pallas/paged_latent_attention.py``). A prefill chunk writes its own
rows into the pool, gathers the row's pages and attends over them in
``jnp``, blocked over heads so that the float32 scores of one block are
what is held (:func:`latent_chunk_attention`); absorbed too: at a few
hundred queries over some thousands of cached tokens the two forms cost
about the same arithmetic (expanding every cached token's keys and values
again a chunk against 3.4x wider dot products), and the absorbed one keeps
no expanded copy.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import llama
from .llama import LlamaConfig, rms_norm
from ..ops.pallas.paged_latent_attention import paged_latent_attention

#: the matrices ``generate.quantize_weights`` takes (expert stacks stay)
QUANT_LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wg", "wu", "wd",
                "ws_g", "ws_u", "ws_d")
#: leaves kept in float32 whatever the model's dtype
F32_LEAVES = ("moe_gate", "moe_bias")
# float32 scores a block of heads of the chunk's attention may hold
_SCORE_BYTES = 128 * 1024 * 1024


def refuse(**on):
    """What a latent-attention model's serving programs do not take."""
    for name, value in on.items():
        if value:
            raise ValueError(
                f"{name} is not supported on a config with latent "
                f"attention (one chip's programs over a pool of latents "
                f"with no head axis; no adapters, no fused kernels)")


# ---------------- parameters ----------------
def param_shapes(cfg: LlamaConfig, experts_held=None) -> Dict:
    """leaf -> (shape, how it is drawn): a fan-in (N(0, 1/fan_in)), "norm"
    (ones), "embed" (N(0, 0.02^2)), "router_bias" (N(0, 0.01^2)) or
    "first_expert". ``experts_held``: the expert axis of the stacks; None
    holds all the router's experts and leaves ``first_expert`` out."""
    la, h, v, i = (cfg.latent, cfg.hidden_size, cfg.vocab_size,
                   cfg.intermediate_size)
    nh = cfg.num_heads
    Ld = cfg.dense_layers
    Le = cfg.num_layers - Ld

    def attn(L):
        return {"attn_norm": ((L, h), "norm"),
                "wq_a": ((L, h, la.q_rank), h),
                "q_norm": ((L, la.q_rank), "norm"),
                "wq_b": ((L, la.q_rank, nh * la.qk_dim), la.q_rank),
                "wkv_a": ((L, h, la.row), h),
                "kv_norm": ((L, la.kv_rank), "norm"),
                "wkv_b": ((L, la.kv_rank, nh * (la.nope_dim + la.v_dim)),
                          la.kv_rank),
                "wo": ((L, nh * la.v_dim, h), nh * la.v_dim),
                "mlp_norm": ((L, h), "norm")}

    def dense(L):
        return {"wg": ((L, h, i), h), "wu": ((L, h, i), h),
                "wd": ((L, i, h), i)}

    out = {"embed": ((v, h), "embed"), "final_norm": ((h,), "norm")}
    if not cfg.tie_embeddings:
        out["lm_head"] = ((h, v), h)
    if Ld:
        out["dense_layers"] = {**attn(Ld), **dense(Ld)}
    moe = cfg.moe
    E = moe.num_experts
    El = E if experts_held is None else experts_held
    ie, sh = moe.expert_size or i, moe.shared_size
    layers = {**attn(Le), "moe_gate": ((Le, h, E), h),
              "moe_wg": ((Le, El, h, ie), h), "moe_wu": ((Le, El, h, ie), h),
              "moe_wd": ((Le, El, ie, h), ie)}
    if moe.score == "sigmoid":
        layers["moe_bias"] = ((Le, E), "router_bias")
    if sh:
        layers.update(ws_g=((Le, h, sh), h), ws_u=((Le, h, sh), h),
                      ws_d=((Le, sh, h), sh))
    if experts_held is not None:
        layers["first_expert"] = ((Le,), "first_expert")
    out["layers"] = layers
    return out


def init_params(key: jax.Array, cfg: LlamaConfig, experts_held=None,
                first_expert: int = 0) -> Dict:
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg, experts_held), is_leaf=is_leaf)
    out = []
    for k, (path, (shape, kind)) in zip(
            jax.random.split(key, len(with_paths)), with_paths):
        dtype = jnp.float32 if path[-1].key in F32_LEAVES else cfg.dtype
        if kind == "norm":
            out.append(jnp.ones(shape, dtype))
        elif kind == "first_expert":
            out.append(jnp.full(shape, first_expert, jnp.int32))
        else:
            n = jax.random.normal(k, shape, jnp.float32)
            scale = {"embed": 0.02, "router_bias": 0.01}.get(kind) \
                or kind ** -0.5
            out.append((n * scale).astype(dtype))
    return jax.tree.unflatten(treedef, out)


# ---------------- the attention layer's parts ----------------
def _rope(cfg: LlamaConfig, positions: int):
    return llama.rope_tables_by_kind(cfg, positions)["latent"]


def _project(u, lp, cfg: LlamaConfig, cos, sin, rpos):
    """The layer's projections of ``u`` (B, T, h) at rope positions ``rpos``
    (B, T): ``(q_nope (B, T, nh, nope), q_rope (B, T, nh, rope) rotated,
    rows (B, T, kv_rank + rope): the normed latent and the rotated key)``."""
    from .generate import _project_heads, _rope_rows, _w
    la, dt = cfg.latent, u.dtype
    cq = rms_norm(u @ _w(lp, "wq_a", dt), lp["q_norm"], cfg.rms_eps)
    q = _project_heads(cq, _w(lp, "wq_b", dt), cfg.num_heads)
    kv = u @ _w(lp, "wkv_a", dt)
    c = rms_norm(kv[..., :la.kv_rank], lp["kv_norm"], cfg.rms_eps)
    k_r = _rope_rows(kv[..., None, la.kv_rank:], cos, sin, rpos)[:, :, 0]
    return (q[..., :la.nope_dim],
            _rope_rows(q[..., la.nope_dim:], cos, sin, rpos),
            jnp.concatenate([c, k_r], axis=-1))


def _kvb(lp, cfg: LlamaConfig, dtype):
    """``W_kvb`` as ``(kv_rank, heads, nope + v)``: a head's ``W_UK`` is
    its first ``nope_dim`` columns and its ``W_UV`` the rest."""
    from .generate import _w
    la = cfg.latent
    return _w(lp, "wkv_b", dtype).reshape(la.kv_rank, cfg.num_heads,
                                          la.nope_dim + la.v_dim)


def _absorb(q_nope, q_rope, w_kvb, cfg: LlamaConfig):
    """The queries taken into the latent, as wide as the pool lays a row
    out: ``[q^nope W_UK^T | q^rope | zeros]``."""
    la = cfg.latent
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_kvb[..., :la.nope_dim])
    pad = jnp.zeros(q_rope.shape[:-1] + (la.row_lanes - la.row,),
                    q_rope.dtype)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def _unabsorb(o_lat, w_kvb, cfg: LlamaConfig):
    """The heads' results out of the latent through ``W_UV``, side by
    side: (B, T, nh, kv_rank) -> (B, T, nh * v)."""
    o = jnp.einsum("bthr,rhv->bthv", o_lat, w_kvb[..., cfg.latent.nope_dim:])
    return o.reshape(o.shape[:2] + (-1,))


def _write_rows(held: Dict, rows, dst, kv_rank: int) -> Dict:
    """Write the token rows ``rows`` (N, row) at the flat slots ``dst`` (N,)
    of the pool ``held`` (``c`` as pages ``(layers * P, page, row_lanes)``,
    zeros past ``row``; on the int8 tier with ``cs`` ``(layers * P, page,
    2)``: a token's latent and its key are scaled apart, each by its
    largest magnitude over 127)."""
    def put(pool, vals):
        vals = jnp.pad(vals, ((0, 0), (0, pool.shape[-1] - vals.shape[-1])))
        return pool.reshape((-1,) + pool.shape[2:]).at[dst].set(
            vals.astype(pool.dtype)).reshape(pool.shape)
    if "cs" not in held:
        return {"c": put(held["c"], rows)}
    f = rows.astype(jnp.float32)
    parts = (f[:, :kv_rank], f[:, kv_rank:])
    sc = [jnp.maximum(jnp.max(jnp.abs(p), axis=-1, keepdims=True) / 127.0,
                      1e-8) for p in parts]
    q = jnp.concatenate([jnp.clip(jnp.round(p / s), -127, 127)
                         for p, s in zip(parts, sc)], axis=-1)
    return {"c": put(held["c"], q),
            "cs": put(held["cs"], jnp.concatenate(sc, axis=-1))}


def latent_chunk_attention(q, ctx, ctx_len, scale: float, value_dim: int):
    """A prefill chunk's absorbed attention over its row's cached latents:
    q (C, H, D) the chunk's queries in the latent, query i at position
    ``ctx_len + i``; ctx (S, D) the row's cached rows by position, the
    chunk's own among them; query i sees positions ``<= ctx_len + i``.
    Returns (C, H, value_dim), still in the latent. Heads go a block at a
    time, so that the float32 scores held are one block's
    (``_SCORE_BYTES``), not all heads'."""
    C, H, D = q.shape
    S = ctx.shape[0]
    hb = max(1, min(H, _SCORE_BYTES // (4 * C * S)))
    while H % hb:
        hb -= 1
    mask = (jnp.arange(S, dtype=jnp.int32)[None, :]
            <= ctx_len + jnp.arange(C, dtype=jnp.int32)[:, None])
    values = ctx[:, :value_dim]

    def block(qb):                                      # (hb, C, D)
        s = jnp.einsum("hcd,kd->hck", qb, ctx,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hck,kr->hcr", p.astype(ctx.dtype), values)

    with jax.named_scope("latent_chunk_attention"):
        o = lax.map(block, jnp.swapaxes(q, 0, 1).reshape(H // hb, hb, C, D))
    return jnp.swapaxes(o.reshape(H, C, value_dim), 0, 1)


# ---------------- walking the layers ----------------
def _layers(params, cfg: LlamaConfig, x, held, attend, valid=None,
            use_kernel=None):
    """Every layer in order over ``x`` (B, T, h): the leading dense layers
    one by one, then a scan over the others. ``attend(u, lp, held, idx) ->
    (the heads' results (B, T, nh * v), held)`` is the form of attention the
    caller runs for layer ``idx``; ``held`` is what the layers hand on (the
    pool, whole; None without a cache). Returns ``(x, held, the expert
    layers' summed stats or None)``."""
    from .generate import _moe_ffn, _split_experts, _swiglu, _w
    scanned, experts = _split_experts(params["layers"])

    def one(x, held, lp, idx, expert_layer):
        u = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        o, held = attend(u, lp, held, idx)
        x = x + o @ _w(lp, "wo", x.dtype)
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        if expert_layer is None:
            return x + _swiglu(h2, lp, "wg", "wu", "wd"), held, None
        f, st = _moe_ffn(h2, lp, cfg, valid=valid, experts=experts,
                         layer=expert_layer, use_kernel=use_kernel)
        return x + f, held, st

    for j in range(cfg.dense_layers):
        x, held, _ = one(x, held, jax.tree.map(
            lambda a: a[j], params["dense_layers"]), j, None)

    def body(carry, xs):
        x, held, stats = carry
        lp, i = xs
        x, held, st = one(x, held, lp, cfg.dense_layers + i, i)
        return (x, held, stats + st), None

    zero = jnp.zeros((4 if "first_expert" in scanned else 3,), jnp.int32)
    (x, held, stats), _ = lax.scan(
        body, (x, held, zero),
        (scanned, jnp.arange(cfg.num_layers - cfg.dense_layers,
                             dtype=jnp.int32)))
    return x, held, stats


def _logits(params, x, cfg: LlamaConfig):
    """Final norm and head over rows ``x`` (..., h) -> float32 logits."""
    from .generate import _w
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = (params["embed"].T.astype(x.dtype) if cfg.tie_embeddings
            else _w(params, "lm_head", x.dtype))
    return (x @ head).astype(jnp.float32)


def _as_pages(paged: Dict) -> Dict:
    """The pool of all layers as one run of pages, ``(layers * P, ...)``."""
    return {n: a.reshape((-1,) + a.shape[2:]) for n, a in paged.items()}


def trunk(params, tokens, cfg: LlamaConfig):
    """No cache, the EXPANDED form: tokens (B, S) -> the final norm's
    hidden states (B, S, h)."""
    la = cfg.latent
    B, S = tokens.shape
    nh = cfg.num_heads
    cos, sin = _rope(cfg, S)
    rpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    causal = jnp.tril(jnp.ones((S, S), bool))

    def attend(u, lp, held, idx):
        q_nope, q_rope, rows = _project(u, lp, cfg, cos, sin, rpos)
        kv = jnp.einsum("bsr,rhd->bshd", rows[..., :la.kv_rank],
                        _kvb(lp, cfg, u.dtype))
        k = jnp.concatenate(
            [kv[..., :la.nope_dim], jnp.broadcast_to(
                rows[:, :, None, la.kv_rank:], (B, S, nh, la.rope_dim))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * la.scale
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(u.dtype),
                       kv[..., la.nope_dim:])
        return o.reshape(B, S, nh * la.v_dim), held

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    x, _, _ = _layers(params, cfg, x, None, attend)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward_chunk(params, tokens, paged: Dict, block_table, cfg: LlamaConfig,
                  *, ctx_cap: int, ctx_len, chunk_len, use_kernel=None):
    """One row's prefill chunk (the contract of ``generate.
    paged_prefill_chunk``): tokens (1, C) right-padded past ``chunk_len``,
    at positions ``ctx_len ..``; the chunk's rows go into the row's pages
    (padding to the trash page) and its queries attend over the first
    ``ctx_cap + C`` positions of the row's pages, their own rows among
    them. Returns ``(logits (1, V) at the last valid token, the pool, the
    expert stats)``."""
    la = cfg.latent
    B, C = tokens.shape
    P, page = paged["c"].shape[1:3]
    ppseq = block_table.shape[0]
    ext = ppseq * page
    ctx_len = jnp.asarray(ctx_len, jnp.int32).reshape(())
    chunk_len = jnp.asarray(chunk_len, jnp.int32).reshape(())
    pos = jnp.arange(C, dtype=jnp.int32)
    logical = jnp.clip(ctx_len + pos, 0, ext - 1)
    cos, sin = _rope(cfg, ext)
    dst = jnp.where(pos < chunk_len,
                    block_table[logical // page] * page + logical % page, 0)
    # the pages that hold the context and the chunk
    seen = block_table[:min(ppseq, -(-ctx_cap // page) + -(-C // page))]

    def attend(u, lp, held, idx):
        q_nope, q_rope, rows = _project(u, lp, cfg, cos, sin, logical[None])
        held = _write_rows(held, rows[0], dst + idx * P * page, la.kv_rank)
        ctx = jnp.take(held["c"], seen + idx * P, axis=0)
        ctx = ctx.reshape(-1, la.row_lanes)
        if "cs" in held:
            sc = jnp.take(held["cs"], seen + idx * P, axis=0).reshape(-1, 2)
            ctx = jnp.concatenate(
                [ctx[:, :la.kv_rank].astype(jnp.float32) * sc[:, :1],
                 ctx[:, la.kv_rank:].astype(jnp.float32) * sc[:, 1:]],
                axis=-1).astype(u.dtype)
        w_kvb = _kvb(lp, cfg, u.dtype)
        o = latent_chunk_attention(
            _absorb(q_nope, q_rope, w_kvb, cfg)[0], ctx, ctx_len, la.scale,
            la.kv_rank)
        return _unabsorb(o[None], w_kvb, cfg), held

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    x, held, stats = _layers(params, cfg, x, _as_pages(paged), attend,
                             valid=(pos < chunk_len)[None, :],
                             use_kernel=use_kernel)
    x = lax.dynamic_slice_in_dim(x, jnp.clip(chunk_len - 1, 0, C - 1), 1,
                                 axis=1)
    return (_logits(params, x[:, 0], cfg),
            {n: a.reshape(paged[n].shape) for n, a in held.items()}, stats)


def decode_forward(params, tokens, paged: Dict, block_tables, lengths,
                   cfg: LlamaConfig, *, active=None, use_kernel=None):
    """One decode step over the ragged batch (the contract of ``generate.
    paged_decode_forward``). The pool rides through the layers whole as
    ``(layers * pages, page, row_lanes)``: a layer writes its B rows where they
    lie and the kernel reads its pages by block tables moved up to the
    layer's first page; donated, the pool is updated in place. Returns
    ``(logits (B, V), the pool, the expert stats)``."""
    la = cfg.latent
    B = tokens.shape[0]
    P, page = paged["c"].shape[1:3]
    ext = block_tables.shape[1] * page
    if active is None:
        active = jnp.ones((B,), bool)
    lengths = jnp.asarray(lengths, jnp.int32)
    cos, sin = _rope(cfg, ext)
    # inactive rows dump into the trash page (page 0 of the layer)
    dst = jnp.where(active, block_tables[jnp.arange(B), lengths // page]
                    * page + lengths % page, 0)

    def attend(u, lp, held, idx):
        q_nope, q_rope, rows = _project(u, lp, cfg, cos, sin,
                                        lengths[:, None])
        held = _write_rows(held, rows[:, 0], dst + idx * P * page,
                           la.kv_rank)
        w_kvb = _kvb(lp, cfg, u.dtype)
        o = paged_latent_attention(
            _absorb(q_nope, q_rope, w_kvb, cfg)[:, 0], held["c"],
            block_tables + idx * P, lengths + 1, scale=la.scale,
            value_dim=la.kv_rank, scales=held.get("cs"),
            use_kernel=use_kernel)
        return _unabsorb(o[:, None], w_kvb, cfg), held

    x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cfg.dtype)
    x, held, stats = _layers(params, cfg, x, _as_pages(paged), attend,
                             valid=active[:, None], use_kernel=use_kernel)
    return (_logits(params, x[:, 0], cfg),
            {n: a.reshape(paged[n].shape) for n, a in held.items()}, stats)
