"""Autoregressive decoding with a static KV cache, TPU-first.

Capability target: the reference's serving stack (reference:
paddle/fluid/inference/api/analysis_predictor.cc + fused decode kernels
paddle/phi/kernels/fusion/masked_multihead_attention_kernel.cu,
block_multi_head_attention_kernel.cu).

TPU-native: ONE jitted program per phase — prefill writes the prompt's
K/V into a preallocated (L, B, S_max, H, D) cache (static shapes; no
dynamic growth), decode is a ``lax.scan`` over steps where each step does
a single-token forward against the cache with a length mask. Greedy or
temperature/top-k sampling via stateless PRNG.

Caches by layer kind. A config whose ``layer_pattern`` names sliding
layers keeps two sets of arrays, dense and paged alike: ``k``/``v``
(``ks``/``vs``) hold the FULL layers, ``k_w``/``v_w`` (``ks_w``/``vs_w``)
the SLIDING ones, each stacked over the layers of its kind in model
order — paged: ``(layers of the kind, pages, page, nkv, hd)``, two pools
with page ids and block tables of their own. A plain config has the one
set. Every forward scans over periods (``_layer_feed``); inside a period
each run of one layer kind (``_runs``) is one layer body, scanned when the
run has several layers; a period of one is the scan over layers (the
paged decode step carries the pools through it whole and in place).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import llama
from .llama import LlamaConfig, rope_tables, apply_rope, rms_norm
from ..observability import hooks as _obs


def _tp_allgather(x: jax.Array, axis_name: str, axis: int) -> jax.Array:
    """Tensor-parallel serving collective: tiled all-gather of a
    column-sharded activation along ``axis`` (exact — a concatenation
    in shard order, no reduction to reassociate, which is what keeps
    tp-sharded decode BIT-identical to single-chip). The byte counter
    fires at TRACE time, so like ``hooks.collective`` it counts the
    collectives in the compiled program (per-shard payload bytes)."""
    _obs.serving_tp_allgather(int(x.size) * jnp.dtype(x.dtype).itemsize)
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def _lora_delta(x, a_l, b_l, aslot, scale):
    """Per-row batched LoRA term (ISSUE 14): gather each row's packed
    low-rank factors from the adapter pool's per-layer arrays and add
    ``(x @ A_i) @ B_i · α/r``. ``x`` (B, T, in); ``a_l`` (S, in, r) /
    ``b_l`` (S, r, out) — this layer's slice of the pool; ``aslot``
    (B,) int32 pool-slot per row; ``scale`` (B,) the per-row α/r.
    Slot 0 holds exact zeros (the base model), so a base row's term is
    an exactly-zero add — the adapter_id=0 bit-identity gate. Under
    tensor parallel ``b_l`` arrives column-sharded on the same output
    axis as the base matrix, so each shard's delta columns use the
    full, identically ordered rank contraction (bit-identical by the
    ISSUE 7 column-split argument)."""
    a = jnp.take(a_l, aslot, axis=0).astype(x.dtype)      # (B, in, r)
    b = jnp.take(b_l, aslot, axis=0).astype(x.dtype)      # (B, r, out)
    t = jnp.einsum("bti,bir->btr", x, a)
    return jnp.einsum("btr,bro->bto", t, b) * scale[:, None, None]


def _adapter_prep(adapters, adapter_slots, cfg: LlamaConfig):
    """Shared per-forward adapter setup: the (B,) slot vector, the
    gathered per-row α/r scale, and the TRACE-time factor-gather byte
    counter (``serving_adapter_gather`` — fires once per compile, the
    serving_tp_allgather contract: it reports the per-step adapter
    bytes the compiled program gathers out of the pool)."""
    aslot = jnp.asarray(adapter_slots, jnp.int32).reshape(-1)
    asc = jnp.take(adapters["scale"], aslot).astype(cfg.dtype)
    B = aslot.shape[0]
    per_row = sum(int(adapters[n].shape[-1] * adapters[n].shape[-2])
                  for n in ("aq", "bq", "ao", "bo"))
    _obs.serving_adapter_gather(
        B * cfg.num_layers * per_row
        * jnp.dtype(adapters["aq"].dtype).itemsize)
    return aslot, asc


def _tp_heads(layers: Dict, cfg: LlamaConfig) -> Tuple[int, int]:
    """Per-SHARD (num_heads, num_kv_heads) from the local weight shards
    (inside shard_map the cfg still describes the GLOBAL model; the
    sliced wq/wk columns carry the local head counts)."""
    return (layers["wq"].shape[-1] // cfg.hd,
            layers["wk"].shape[-1] // cfg.hd)


#: suffix of a layer kind's cache arrays (see the module docstring)
KIND_SUFFIX = {"full": "", "sliding": "_w"}
#: a pool's arrays by their plain names: keys and values, or a config with
#: latent attention's ONE pool of latents (``c``, and ``cs`` on the int8
#: tier: :func:`init_paged_cache`), which stands where a ``full`` pool does
POOL_NAMES = ("k", "v", "ks", "vs", "c", "cs")


def _kind_arrays(cfg: LlamaConfig, make) -> Dict:
    """``make(kind, layers)`` -> the arrays of that kind of KV cache by
    their plain names; every kind the model asks for
    (:meth:`LlamaConfig.cache_layers`) in one dict, suffixed."""
    out = {}
    for kind, layers in cfg.cache_layers().items():
        if kind in KIND_SUFFIX:
            out.update({n + KIND_SUFFIX[kind]: a
                        for n, a in make(kind, layers).items()})
    return out


def _of_kind(arrays: Dict, kind: str) -> Dict:
    """The arrays of one layer kind under their plain names."""
    sfx = KIND_SUFFIX[kind]
    return {n: arrays[n + sfx] for n in POOL_NAMES if n + sfx in arrays}


def init_cache(cfg: LlamaConfig, batch: int, max_len: int,
               kv_dtype=None, num_kv_heads: Optional[int] = None,
               window_len: Optional[int] = None) -> Dict:
    """``kv_dtype="int8"``: int8 KV cache with PER-ROW dequant scales
    (each cached token row carries its own scale — self-calibrating, no
    calibration pass), halving KV HBM for long-context decode
    (reference: the cachekv-int8 tier of block_multihead_attention).
    ``num_kv_heads`` overrides the config's head count — the per-shard
    temp caches of the tensor-parallel chunk/verify programs hold only
    the shard's own kv heads. ``window_len``: the width of the SLIDING
    layers' arrays where it is not ``max_len`` (the chunk program's temp
    cache holds one window of context for them)."""
    nkv, hd = cfg.num_kv_heads, cfg.hd
    _refuse_latent(cfg, "init_cache (the dense cache of generate, "
                   "beam_search and the verify programs)")
    if num_kv_heads is not None:
        nkv = num_kv_heads
    if kv_dtype is not None and jnp.dtype(kv_dtype) != jnp.int8:
        raise ValueError(
            f"init_cache: kv_dtype={kv_dtype!r} is not supported — pass "
            f"None (model dtype) or 'int8' (quantized cache with per-row "
            f"scales); a silently full-precision cache would misreport "
            f"the serving configuration")

    def make(kind, L):
        S = window_len if kind == "sliding" and window_len else max_len
        if kv_dtype is not None:
            return {
                "k": jnp.zeros((L, batch, S, nkv, hd), jnp.int8),
                "v": jnp.zeros((L, batch, S, nkv, hd), jnp.int8),
                "ks": jnp.zeros((L, batch, S, nkv), jnp.float32),
                "vs": jnp.zeros((L, batch, S, nkv), jnp.float32),
            }
        return {
            "k": jnp.zeros((L, batch, S, nkv, hd), cfg.dtype),
            "v": jnp.zeros((L, batch, S, nkv, hd), cfg.dtype),
        }
    return _kind_arrays(cfg, make)


#: the arrays of the recurrent-state pool (``init_paged_cache``): not paged
STATE_ARRAYS = ("ssm", "conv")


def init_paged_cache(cfg: LlamaConfig, num_pages: int, page_size: int,
                     kv_dtype=None, tp: Optional[int] = None,
                     window_pages: Optional[int] = None,
                     state_slots: Optional[int] = None,
                     state_dtype=jnp.float32) -> Dict:
    """Paged KV cache: one global pool of fixed-size token pages per
    layer — ``(L, num_pages, page_size, nkv, hd)`` — indexed by
    per-request block tables instead of a dense ``(L, B, S_max, ...)``
    slab, so serving HBM is sized by tokens in flight (reference:
    block_multi_head_attention's block cache; see
    paddle_tpu/serving/paged_cache.py for the allocator).

    ``kv_dtype="int8"`` mirrors :func:`init_cache`'s per-row-scale int8
    tier: pages store int8 rows, ``ks``/``vs`` pools carry the per-row
    dequant scales.

    ``tp``: build the GLOBAL pool for a tensor-parallel serving mesh of
    that size — the head axis shards over tp (``nkv/tp`` heads per
    shard, same page ids everywhere so the host-side allocator / block
    tables / prefix trie stay replicated and untouched). Divisibility
    is validated LOUDLY (:func:`~paddle_tpu.models.llama.
    validate_serving_tp`): a silent mis-shard would split heads across
    chips. GQA with ``num_kv_heads < tp`` takes the replication path —
    the head extent expands to ``tp`` (each kv head repeated
    ``tp/num_kv_heads`` times, one per shard), so per-shard page bytes
    are ``1/num_kv_heads`` of the pool rather than ``1/tp``.

    A config with sliding layers gets TWO pools, one per layer kind
    (module docstring): the full layers' of ``num_pages`` pages and the
    sliding layers' of ``window_pages``, the second sized by the
    allocator for a window and a chunk a row
    (:class:`~paddle_tpu.serving.PagedKVCache`), not for ``max_len``.

    A config with state-space layers gets a THIRD kind of cache beside
    them, addressed by slot and not by page: ``ssm`` ``(state layers,
    state_slots, ...)`` the recurrence's state in ``state_dtype`` (Mamba-2
    ``(head_dim, state, heads)``, Mamba-1 ``(state, channels)``:
    ``hybrid.state_shapes``) and ``conv`` ``(state layers, state_slots,
    ...)`` the causal convolution's last ``conv_kernel - 1`` columns in
    the model's dtype (Mamba-1's flat). The KV pools hold layers only for
    the kinds that attend.

    A config with latent attention gets ONE pool, of latents: ``c``
    ``(layers, num_pages, page_size, LatentConfig.row_lanes)``, a token's
    normed latent and the one rotated key its heads share (``kv_rank +
    rope_dim`` numbers, then zeros to a whole 128-lane tile), with no head
    axis (``tp`` is refused by name: there is nothing to shard); on the
    int8 tier ``c`` is int8 and ``cs`` ``(..., page_size, 2)`` float32
    carries a token's two dequant scales, the latent's and the key's.
    Block tables, admission and the prefix trie deal in page ids and run
    it as they run a ``full`` pool."""
    nkv, hd = cfg.num_kv_heads, cfg.hd
    needs = cfg.cache_layers()
    if kv_dtype is not None and jnp.dtype(kv_dtype) != jnp.int8:
        raise ValueError(
            f"init_paged_cache: kv_dtype={kv_dtype!r} is not supported — "
            f"pass None (model dtype) or 'int8'")
    if "latent" in needs:
        if tp is not None:
            raise ValueError(
                "init_paged_cache: tp is not supported on a pool of "
                "latents: a latent has no head axis to shard")
        shape = (needs["latent"], num_pages, page_size,
                 cfg.latent.row_lanes)
        if kv_dtype is None:
            return {"c": jnp.zeros(shape, cfg.dtype)}
        return {"c": jnp.zeros(shape, jnp.int8),
                "cs": jnp.zeros(shape[:3] + (2,), jnp.float32)}
    if "sliding" in needs and window_pages is None:
        raise ValueError(
            "init_paged_cache: the config has sliding layers; "
            "window_pages must size their pool")
    if "full" not in needs:
        raise ValueError(
            "init_paged_cache: a period without a full layer is not "
            "served (block tables and admission follow the full pool)")
    if "state" in needs and state_slots is None:
        raise ValueError(
            "init_paged_cache: the config has state-space layers; "
            "state_slots must size their pool")
    if tp is not None:
        # validate_serving_mesh rather than validate_serving_tp: the
        # head contract is identical and MoE configs are legal on the
        # serving mesh (ISSUE 17 expert-parallel decode)
        nkv = llama.validate_serving_mesh(cfg, tp) * tp

    def make(kind, L):
        P = window_pages if kind == "sliding" else num_pages
        if kv_dtype is not None:
            return {
                "k": jnp.zeros((L, P, page_size, nkv, hd), jnp.int8),
                "v": jnp.zeros((L, P, page_size, nkv, hd), jnp.int8),
                "ks": jnp.zeros((L, P, page_size, nkv), jnp.float32),
                "vs": jnp.zeros((L, P, page_size, nkv), jnp.float32),
            }
        return {
            "k": jnp.zeros((L, P, page_size, nkv, hd), cfg.dtype),
            "v": jnp.zeros((L, P, page_size, nkv, hd), cfg.dtype),
        }
    out = _kind_arrays(cfg, make)
    if "state" in needs:
        from .hybrid import state_shapes
        L = needs["state"]
        ssm, conv = state_shapes(cfg)
        out["ssm"] = jnp.zeros((L, state_slots) + ssm, state_dtype)
        out["conv"] = jnp.zeros((L, state_slots) + conv, cfg.dtype)
    return out


def _take_pages(pool, table):
    """The pages ``table`` (n,) names, in order, as token rows: pool
    (L, P, page, nkv, ...) -> (L, n * page, nkv, ...). With 8 kv heads
    or more the plain gather moves nothing but the pages. With fewer, a
    position's ``(nkv, hd)`` rows do not fill one of XLA's (8, 128)
    tiles, and asked to gather such pages XLA first re-tiles the WHOLE
    pool (two copies of the pool a chunk, seen at 4 heads): there
    a page is taken as one slab of ``page * nkv`` rows, the view the
    paged kernel reads too, and a short table (a sliding layer's
    window) page by page, since XLA's gather of few slabs again starts
    by slicing the whole pool in halves."""
    L, P, page, nkv = pool.shape[:4]
    n = table.shape[0]
    if nkv >= 8:
        g = jnp.take(pool, table, axis=1)               # (L, n, pg, .)
    else:
        slabs = pool.reshape((L, P, page * nkv) + pool.shape[4:])
        if n <= 32:
            g = jnp.concatenate(
                [lax.dynamic_index_in_dim(slabs, table[i], 1)
                 for i in range(n)], axis=1)
        else:
            g = jnp.take(slabs, table, axis=1)
    return g.reshape((L, n * page) + pool.shape[3:])


def _scatter_rows(pool, dst, rows):
    """Write token rows into pool slots: pool (L, P, page, ...), dst
    (N,) flat slot ids (page*page_size + offset), rows (L, N, ...). One
    layer at a time: asked to scatter into all layers at once, XLA:TPU
    lays the slot axis outermost, and where a position's row is narrower
    than a tile (one key/value head) that is a copy of the whole pool in
    and out of every call (two of 1.6 GB a pool at a 49k-page pool of two
    layers)."""
    L, P, page = pool.shape[0], pool.shape[1], pool.shape[2]
    flat = pool.reshape((L, P * page) + pool.shape[3:])
    for l in range(L):
        flat = flat.at[l, dst].set(rows[l].astype(pool.dtype))
    return flat.reshape(pool.shape)


#: the expert stacks of a layer tree: kept out of the layer scan's slices
EXPERT_STACKS = ("moe_wg", "moe_wu", "moe_wd")


def _expert_apply(x_rows, item_row, le, stacks, layer, tp_axis=None,
                  use_kernel=None, absent=False):
    """Expert SwiGLU over routed items, grouped by expert.

    ``x_rows`` (R, H) token rows, ``item_row`` (n,) the row each item
    copies, ``le`` (n,) LOCAL expert ids into this shard's experts of
    layer ``layer``; ``stacks`` are the model's three expert stacks as
    they are stored, ``(L, E_l, H, i_cols)`` twice and ``(L, E_l, i,
    h_cols)``. The items are sorted by expert (a stable sort: within an
    expert they keep token order), the group sizes are the counts, and
    each projection is ONE grouped matmul
    (``ops/pallas/grouped_matmul.py``: a Pallas kernel on the chip,
    ``lax.ragged_dot`` off it) in place of a private gathered copy of
    three matrices an item: an expert with no item is never read, and
    one with items is read once. The call is handed the stacks of ALL
    layers and the layer's number, and reads the layer's experts out of
    the stack where they lie: a custom call's operand is a whole
    buffer, so a layer's slice (0.8 GB of experts at the published
    widths) would be copied out for it at every layer of every step.
    The outputs go back to item order. Under tp the stacks arrive
    column-sharded like the dense ``wg``/``wu``/``wd`` and the
    activations all-gather to full width before each contraction (the
    ISSUE 7 exact-concat argument).

    Two stacks in place of three are the two-matrix expert
    ``relu(x W1)^2 W2``. ``absent``: some items' experts are not held
    here; their ``le`` is ``E_l``, past every group, so that they sort
    behind the held items, count in no group, are multiplied by nothing
    and come back as zeros."""
    from ..ops.pallas.grouped_matmul import grouped_matmul
    n, dt = le.shape[0], x_rows.dtype
    El = stacks[0].shape[1]
    sizes = jnp.zeros((El,), jnp.int32).at[le].add(1)
    order = jnp.argsort(le)                     # stable: token order kept
    xs = jnp.take(x_rows, jnp.take(item_row, order), axis=0)
    mm = partial(grouped_matmul, sizes=sizes, layer=layer,
                 use_kernel=use_kernel)
    if len(stacks) == 2:
        # the plain two-matrix expert, relu squared between
        r = jnp.maximum(mm(xs, stacks[0]).astype(jnp.float32), 0.0)
        gu, wd = (r * r).astype(dt), stacks[1]
    else:
        wg, wu, wd = stacks
        g = jax.nn.silu(mm(xs, wg).astype(jnp.float32)).astype(dt)
        u = mm(xs, wu)
        gu = g * u
    if tp_axis is not None:
        gu = _tp_allgather(gu, tp_axis, 1)
    o = mm(gu, wd)
    if tp_axis is not None:
        o = _tp_allgather(o, tp_axis, 1)
    if absent:
        # rows past the last group belong to no expert held here: no
        # grouped matmul wrote them
        o = jnp.where((jnp.take(le, order) < El)[:, None], o, 0)
    inv = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    return jnp.take(o, inv, axis=0)


def _swiglu(x, lp, gate: str, up: str, down: str):
    """``(silu(x W_gate) * (x W_up)) W_down`` with the leaves of ``lp`` so
    named: a dense FFN or a shared expert."""
    dt = x.dtype
    g = jax.nn.silu((x @ _w(lp, gate, dt)).astype(jnp.float32)).astype(dt)
    return (g * (x @ _w(lp, up, dt))) @ _w(lp, down, dt)


def _route(xf, router, bias, k: int, score: str, scale: float):
    """The routing rule of an expert layer, in float32: xf (N, H) rows,
    ``router`` (H, E) -> ``(idx (N, k) chosen experts, w (N, k) their
    weights)``; ``lax.top_k`` breaks ties to the lower index.

    ``"softmax"``: the ``k`` largest router logits and a softmax over
    them, which IS the softmax over all experts, its k largest,
    renormalised (``norm_topk_prob``).

    ``"sigmoid"``: ``s = sigmoid(x W_r)``; the ``k`` largest of ``s +
    bias`` are chosen (the bias selects, it does not weigh); the weights
    are ``scale * s_chosen / sum(s_chosen)`` (``topk_method: noaux_tc``,
    ``routed_scaling_factor``). The product is float32 in fact: a TPU
    multiplies float32 operands as bfloat16 unless told otherwise, and a
    chosen expert's weight here does not fall off towards the k-th
    (sigmoid scores saturate), so an expert that flips in or out at the
    boundary moves the layer's output like any other."""
    if score == "softmax":
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        vals, idx = lax.top_k(logits, k)                    # (N, k)
        return idx, jax.nn.softmax(vals, axis=-1)
    if score != "sigmoid":
        raise ValueError(f"routing rule {score!r}: 'softmax' or 'sigmoid'")
    s = jax.nn.sigmoid(jnp.matmul(
        xf.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))                       # (N, E)
    _, idx = lax.top_k(s + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)               # (N, k)
    return idx, scale * chosen / jnp.sum(chosen, -1, keepdims=True)


def _held_items(idx, first_expert, El: int, valid):
    """The routed items of a layer that holds the ``El`` experts from
    ``first_expert`` on: idx (N, k) global expert ids -> ``(le (N*k,) local
    ids, ``El`` where the expert is held elsewhere, so that
    :func:`_expert_apply` sorts the item behind every group; item_row
    (N*k,) the row each item copies; stats)``, ``stats`` int32 ``[items
    computed here, held experts hit, largest load of a held expert, items
    routed to experts held elsewhere]`` over the rows ``valid`` (N,) marks
    (all when None)."""
    N, k = idx.shape
    le = idx.reshape(-1).astype(jnp.int32) - first_expert
    held = (le >= 0) & (le < El)
    le = jnp.where(held, le, El)
    item_row = jnp.arange(N * k, dtype=jnp.int32) // k
    live = (jnp.ones((N * k,), jnp.int32) if valid is None else
            jnp.repeat(valid.reshape(N).astype(jnp.int32), k))
    load = jnp.zeros((El,), jnp.int32).at[le].add(live)
    here = jnp.sum(live * held)
    return le, item_row, jnp.stack([here, jnp.sum(load > 0), jnp.max(load),
                                    jnp.sum(live) - here])


def _moe_ffn(x, lp, cfg: LlamaConfig, tp_axis=None, dp_axis=None,
             valid=None, experts=None, layer=0, use_kernel=None):
    """Serving MoE FFN: capacity-DROPLESS top-k routing and one grouped
    expert layer (:func:`_expert_apply`) for decode and chunk alike,
    expert-parallel over the dp axis (ISSUE 17), or with a SHARE of the
    experts held and no exchange.

    x: (B, T, H); lp carries this layer's ``moe_gate`` (H, E) fp32
    router (replicated — every shard routes identically, the
    bit-identity precondition) and expert stacks ``moe_wg``/``moe_wu``/
    ``moe_wd`` — FULL E on a single chip, E/dp experts per shard under
    expert parallelism (their column axis tp-sharded either way). A
    forward that scans over layers hands the stacks of ALL layers as
    ``experts`` and says which ``layer`` this is (:func:`_expert_apply`
    has the reason); ``lp`` then carries the router alone.
    Returns ``(y, stats)``; ``stats`` is int32 ``[routed items, experts
    hit, largest expert load]`` of this layer over the rows ``valid``
    (B, T) marks (all of them when None): what the engine's ``moe_*``
    counters sum.

    Routing (:func:`_route`) by ``cfg.moe.score``: a softmax over the
    chosen logits, or sigmoid scores with the selection bias ``moe_bias``
    (E,) and ``cfg.moe.routed_scale``. The combine ``y = sum_j w_j *
    out_j`` runs over the top-k slots IN SLOT ORDER in fp32 — the same
    fixed-order sum on every path. No capacity, no dropped token: the
    groups are as long as the routing makes them.

    A share of the experts: ``lp`` with ``first_expert`` says that the
    stacks hold the ``E_l`` experts from that one on (a property of the
    parameters, not of the config). Every token is still routed over all
    E experts, as on every chip of the deployment; the items whose expert
    is not held here are dropped before the sort and contribute zero (the
    other chips' shares would add theirs), with no ``dp`` exchange and
    nothing in its place, and ``stats`` is :func:`_held_items`'s four.

    ``cfg.moe.shared_size``: a shared three-matrix expert ``ws_g`` /
    ``ws_u`` / ``ws_d`` on every token, every chip's own, added once.

    Dispatch (dp > 1): the N*k routed items scatter into per-owner send
    buffers of capacity N*k each — dropless BY CONSTRUCTION (a worst
    case where one owner receives every item still fits), unlike the
    train-side ``moe.router`` whose capacity_factor DROPS overflow —
    then one ``lax.all_to_all`` ships tokens to their experts' owners
    and a second ships the outputs back. Unfilled capacity slots
    compute FFN(0) on expert 0 and are never read back. Serving decode
    batches are small, so the quadratic rank assignment and the
    padded capacity are noise next to the expert matmuls."""
    B, T, H = x.shape
    moe = cfg.moe
    k = moe.top_k
    if experts is None:
        experts = tuple(lp[n][None] for n in EXPERT_STACKS)
    E = lp["moe_gate"].shape[-1]
    El = experts[0].shape[1]                            # local experts
    N = B * T
    xf = x.reshape(N, H)
    idx, w = _route(xf, lp["moe_gate"], lp.get("moe_bias"), k, moe.score,
                    moe.routed_scale)
    n = N * k
    share = "first_expert" in lp
    if share:
        if dp_axis is not None:
            raise ValueError("_moe_ffn: a share of the experts (lp has "
                             "first_expert) takes no dp exchange")
        items_e, item_row, stats = _held_items(idx, lp["first_expert"], El,
                                               valid)
    else:
        items_e = idx.reshape(-1).astype(jnp.int32)     # global ids
        item_row = jnp.arange(n, dtype=jnp.int32) // k
        live = (jnp.ones((n,), jnp.int32) if valid is None else
                jnp.repeat(valid.reshape(N).astype(jnp.int32), k))
        load = jnp.zeros((E,), jnp.int32).at[items_e].add(live)
        stats = jnp.stack([jnp.sum(live), jnp.sum(load > 0), jnp.max(load)])
    if dp_axis is not None and El != E:
        # expert-parallel dispatch: owner shard + local id from the
        # LOCAL stack shape (dp = E/El — no collective needed), rank
        # within owner via pairwise comparison cumsum
        dp = E // El
        owner = items_e // El
        le = items_e % El
        ar = jnp.arange(n, dtype=jnp.int32)
        pos = jnp.sum((owner[None, :] == owner[:, None])
                      & (ar[None, :] < ar[:, None]),
                      axis=1).astype(jnp.int32)
        sx = jnp.zeros((dp, n, H), x.dtype).at[owner, pos].set(
            jnp.take(xf, item_row, axis=0))
        se = jnp.zeros((dp, n), jnp.int32).at[owner, pos].set(le)
        # trace-time all-to-all accounting (the serving_tp_allgather
        # contract — fires once per compile per layer): token payload
        # there + outputs back, plus the local-id plane
        _obs.serving_moe_dispatch(
            2 * int(sx.size) * jnp.dtype(sx.dtype).itemsize
            + int(se.size) * 4, n)
        rx = lax.all_to_all(sx, dp_axis, split_axis=0, concat_axis=0)
        re = lax.all_to_all(se, dp_axis, split_axis=0, concat_axis=0)
        out = _expert_apply(rx.reshape(dp * n, H),
                            jnp.arange(dp * n, dtype=jnp.int32),
                            re.reshape(dp * n), experts, layer,
                            tp_axis=tp_axis, use_kernel=use_kernel)
        back = lax.all_to_all(out.reshape(dp, n, H), dp_axis,
                              split_axis=0, concat_axis=0)
        items_out = back[owner, pos]                    # (N*k, H)
    else:
        items_out = _expert_apply(xf, item_row, items_e, experts, layer,
                                  tp_axis=tp_axis, use_kernel=use_kernel,
                                  absent=share)
    y = jnp.sum(items_out.reshape(N, k, H).astype(jnp.float32)
                * w[:, :, None], axis=1).astype(x.dtype)
    if moe.shared_size:
        y = y + _swiglu(xf, lp, "ws_g", "ws_u", "ws_d")
    return y.reshape(B, T, H), stats


def _latent_moe_ffn(x, lp, cfg: LlamaConfig, experts, layer, valid=None,
                    use_kernel=None):
    """The hybrid model's expert layer (LatentMoE) with a SHARE of the
    routed experts held here; the whole feed-forward part of one layer.

    x: (B, T, H). ``lp`` carries the layer's ``router`` (H, E) and
    ``router_bias`` (E,) in float32 over ALL E experts, the latent
    projections ``w_down`` (H, l) / ``w_up`` (l, H), the shared expert
    ``ws1`` / ``ws2`` at full width, and ``first_expert``; ``experts`` are
    the two stacks ``(L, E_l, l, i)`` / ``(L, E_l, i, l)`` of the E_l
    experts ``first_expert .. first_expert + E_l`` of every layer, read in
    place by :func:`_expert_apply`.

    Routing is :func:`_route`'s sigmoid rule and the share
    :func:`_held_items`'s, as in :func:`_moe_ffn`: every token is routed
    over all E experts, as on every chip of the deployment; the items whose
    expert is not held here are dropped before the sort and contribute
    zero: the other chips' shares would add theirs through the same
    ``w_up``, which is linear and has no bias. No ``dp`` exchange, and
    nothing stands in for the absent chips. The shared expert is every
    chip's own and is added once.

    Returns ``(y, stats)``; ``stats`` is int32 ``[items computed here,
    held experts hit, largest load of a held expert, items routed to
    experts held elsewhere]`` over the rows ``valid`` marks."""
    B, T, H = x.shape
    k, dt = cfg.moe.top_k, x.dtype
    El = experts[0].shape[1]
    N = B * T
    xf = x.reshape(N, H)
    idx, w = _route(xf, lp["router"], lp["router_bias"], k, "sigmoid",
                    cfg.hybrid.routed_scale)
    le, item_row, stats = _held_items(idx, lp["first_expert"], El, valid)
    lat = xf @ _w(lp, "w_down", dt)                             # (N, l)
    items = _expert_apply(lat, item_row, le, experts, layer,
                          use_kernel=use_kernel, absent=True)
    r = jnp.sum(items.reshape(N, k, -1).astype(jnp.float32)
                * w[:, :, None], axis=1).astype(dt)
    hs = jnp.maximum((xf @ _w(lp, "ws1", dt)).astype(jnp.float32), 0.0)
    y = r @ _w(lp, "w_up", dt) + (hs * hs).astype(dt) @ _w(lp, "ws2", dt)
    return y.reshape(B, T, H), stats


def _runs(period):
    """A period as its runs of one layer kind: ``[(kind, first layer of the
    run, layers in it), ...]``."""
    runs = []
    for j, kind in enumerate(period):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, j, 1])
    return [tuple(r) for r in runs]


def _by_period(a, n: int, plen: int):
    """A kind's stacked arrays ``(layers of the kind, ...)`` as ``(periods,
    of the kind a period, ...)`` for a scan over periods; a period of one
    layer is the array itself."""
    return a if plen == 1 else a.reshape((a.shape[0] // n, n) + a.shape[1:])


def _layer_feed(tree, plen: int):
    """How a scan over periods reaches a stacked ``(L, ...)`` tree of layer
    arrays: ``(what to put among the scan's xs, take)`` with
    ``take(slice, i, j)`` the arrays of layer ``j`` of period ``i``
    (weights, adapters; paged pools ride in the carry instead). A
    period of one is the scan's own slice. With several layers a period
    the stack stays outside the scan and a layer is cut out of it by its
    number, which is what the scan does itself; a period's slice cut
    again by position would be copied twice."""
    if plen == 1 or tree is None:
        return tree, lambda sl, i, j: sl
    return None, lambda sl, i, j: jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i * plen + j, 0,
                                           keepdims=False), tree)


def _no_stats(layers: Dict):
    """What a layer tree's ``_moe_ffn`` stats start from: four counts where
    it holds a share of the experts (:func:`_held_items`), else three."""
    return jnp.zeros((4 if "first_expert" in layers else 3,), jnp.int32)


def _split_experts(layers: Dict):
    """A layer tree as (what the layer scan slices, the three expert
    stacks whole or None): :func:`_expert_apply` reads a layer's experts
    in place."""
    if EXPERT_STACKS[0] not in layers:
        return layers, None
    return ({n: a for n, a in layers.items() if n not in EXPERT_STACKS},
            tuple(layers[n] for n in EXPERT_STACKS))


def _refuse_latent(cfg: LlamaConfig, what: str):
    """The programs that keep keys and values by head."""
    if cfg.latent is not None:
        raise ValueError(
            f"{what}: the config has latent attention (a pool of latents "
            f"with no head axis); only paged_prefill_chunk and "
            f"paged_decode_forward serve it, and llama.forward runs it "
            f"without a cache")


def _refuse_parts(cfg: LlamaConfig, **on):
    """What a K/V decoder with more than the plain layer's parts
    (:meth:`LlamaConfig.kv_parts`) is not served with: one chip's programs,
    unfused, with no adapters."""
    parts = cfg.kv_parts()
    for name, value in on.items():
        if value and parts:
            raise ValueError(
                f"{name} is not supported on a config with "
                f"{', '.join(parts)} (one chip's unfused programs, no "
                f"adapters)")


def _refuse_sliding(cfg: LlamaConfig, what: str):
    """The programs that know one pool and one block table a row."""
    _refuse_latent(cfg, what)
    if cfg.hybrid is not None:
        raise ValueError(
            f"{what}: the config has state-space layers (a recurrent "
            f"state a row beside its pages); only paged_prefill_chunk and "
            f"paged_decode_forward serve it")
    if "sliding" in cfg.period:
        raise ValueError(
            f"{what}: the config has sliding-window layers (two pools, "
            f"two block tables a row); only paged_prefill_chunk and "
            f"paged_decode_forward serve it")


def paged_prefill_insert(params, prompt: jax.Array, paged: Dict,
                         block_table: jax.Array, cfg: LlamaConfig,
                         prompt_len=None):
    """Prefill ONE request and scatter its KV into the paged pools.

    prompt:      (1, S) int32 — continuous batching admits one request
                 at a time into a free slot
    paged:       :func:`init_paged_cache` pools (int8 tier included)
    block_table: (ppseq,) int32 page ids for this request, in logical
                 order; entries beyond the allocated pages may point at
                 the trash page (their scattered rows are zeros)
    prompt_len:  optional TRACED scalar — the true prompt length when
                 ``prompt`` is LEFT-padded to a bucketed width (the
                 engine pads to page multiples so a long-lived server
                 compiles one prefill program per page count, not per
                 distinct prompt length). Decode parity is preserved
                 exactly: left-padded prefill is row-identical to the
                 unpadded one (the ragged-``generate`` guarantee) and
                 the scatter shifts rows so page slot ``s`` holds
                 logical token ``s``.
    returns (last-token logits (1, V), updated pools).

    The prefill itself runs the DENSE path (:func:`_forward_cached`)
    over a temporary cache sized to the PROMPT's width ``S`` (not the
    slot's full ``max_len`` extent — per-admission cost scales with the
    prompt, the serving hot path's bill), then scatters those ``S``
    rows into the request's pages. Page slots past the prompt keep
    whatever a previous tenant left: decode masks ``kpos <= length``
    and overwrites each position before any mask exposes it, so stale
    rows are never visible."""
    B, S = prompt.shape
    if B != 1:
        raise ValueError(
            f"paged_prefill_insert: one request at a time (got batch "
            f"{B}); continuous batching admits requests individually")
    _refuse_sliding(cfg, "paged_prefill_insert")
    page = paged["k"].shape[2]
    ext = block_table.shape[0] * page          # the slot's full extent
    if S > ext:
        raise ValueError(
            f"prompt of {S} tokens exceeds the block table's "
            f"{ext}-token extent")
    quant = "ks" in paged
    dense = init_cache(cfg, 1, S, kv_dtype="int8" if quant else None)
    if prompt_len is None:
        logits, dense = _forward_cached(params, prompt, dense, 0, cfg,
                                        S)
        src = None
    else:
        pad = S - jnp.asarray(prompt_len, jnp.int32).reshape(())
        kstart = jnp.clip(pad, 0, S - 1)[None]                  # (1,)
        rpos = jnp.clip(jnp.arange(S, dtype=jnp.int32)[None, :]
                        - kstart[:, None], 0, None)
        logits, dense = _forward_cached(params, prompt, dense, 0, cfg,
                                        S, rpos=rpos, kstart=kstart)
        # logical token s lives at padded cache row pad + s; rows past
        # the prompt clip to the last row (finite garbage, overwritten
        # by decode steps before any attention mask exposes them)
        src = jnp.clip(pad + jnp.arange(S, dtype=jnp.int32), 0, S - 1)
    pos = jnp.arange(S, dtype=jnp.int32)
    dst = block_table[pos // page] * page + pos % page
    out = {}
    for name in paged:
        rows = dense[name][:, 0]
        if src is not None:
            rows = jnp.take(rows, src, axis=1)
        out[name] = _scatter_rows(paged[name], dst, rows)
    return logits, out


def paged_prefill_chunk(params, tokens: jax.Array, paged: Dict,
                        block_table: jax.Array, cfg: LlamaConfig, *,
                        ctx_cap: int, ctx_len, chunk_len, tp_axis=None,
                        dp_axis=None, fused=None, use_kernel=None,
                        adapters=None, adapter_slot=None,
                        window_table=None, with_stats=False,
                        state_slot=None):
    """Prefill ONE chunk of a request's prompt against the KV already in
    its pages — the chunked-prefill / prefix-cache continuation program
    (one compile per static ``(ctx_cap, C)`` pair; the engine buckets
    ``ctx_cap`` to power-of-two page counts and ``C`` to page multiples,
    bounding a long-lived server's compile count independent of prompt
    or shared-prefix lengths).

    tokens:      (1, C) int32 chunk, RIGHT-padded past ``chunk_len``
    paged:       :func:`init_paged_cache` pools (int8 tier included)
    block_table: (ppseq,) int32 — the slot's page ids, logical order
    ctx_cap:     STATIC page multiple >= ctx_len (``ceil(ctx/page) *
                 page``) — the gathered-context width / compile key
    ctx_len:     TRACED true token count already in the slot's pages
                 (shared prefix + previous chunks; any value, so
                 copy-on-write partial-page shares need no realignment)
    chunk_len:   TRACED valid tokens in this chunk
    returns (logits (1, V) at the chunk's LAST VALID token, updated
    pools).

    Layout (:func:`_chunk_over_pages`): a layer writes the chunk's keys
    and values into the slot's pages at logical positions ``ctx_len +
    i`` (pad rows route to the trash page), and its queries attend over
    the slot's pages through the block table
    (:func:`~paddle_tpu.ops.pallas.paged_chunk_attention.
    kv_chunk_attention`: on the chip a kernel that reads the pages to
    the row's true length, elsewhere its twin over the first ``ctx_cap +
    C`` positions); the pools ride whole in the layer scan's carry. Under
    ``fused`` and for state-space layers the slot's first ``ctx_len``
    cached rows are instead gathered from its pages and RIGHT-ALIGNED into
    a ``(1, ctx_cap + C)`` dense temp cache (garbage below masked via the
    same ``kstart``/``rpos`` machinery as left-padded ragged prompts),
    the chunk forwards at temp positions ``[ctx_cap, ctx_cap + C)`` with
    logical rope positions ``ctx_len + i``, and the new rows scatter into
    the slot's pages. Either way chunk rows see exactly the KV a
    monolithic prefill's rows ``[ctx_len, ctx_len + chunk)`` would see —
    cached rows are bit-identical and masked columns contribute exact
    zeros — so chunked + prefix-shared prefill stays TOKEN-IDENTICAL to
    the dense path.

    This one program serves THREE consumers: chunked prefill of a fresh
    admission, the prefix-cache continuation (``ctx_len`` > 0 on the
    first chunk), and the SLO scheduler's preemption RESUME — a
    preempted request replays ``prompt + generated[:-1]`` through here
    to rebuild its evicted pages (decode then re-feeds the last sampled
    token), which is why resume is bit-identical to an uninterrupted
    run rather than approximately so (gated in tests/test_scheduler.py
    at fp and int8-KV).

    ``tp_axis``: run as one tensor-parallel shard (inside shard_map;
    see :func:`_block_infer`) — ``paged`` then holds the shard's own kv
    heads and the temp cache is sized from the pool, not the config.

    ``dp_axis`` (ISSUE 17): on the 2-D tp x dp mesh this one-request
    program runs fully dp-REPLICATED — every dp shard computes the
    identical chunk and scatters the identical rows into its pool
    replica, so no batch gathers are needed; the axis only feeds the
    MoE expert-parallel dispatch (:func:`_moe_ffn`), whose replicated
    inputs make the all-to-all redundant but exact.

    ``fused`` (ISSUE 11): the chunk's attention runs through the flash
    prefill kernel (``ops/pallas/serving_fused.flash_chunk_attention``)
    instead of the materialized-score jnp path — same ragged
    ``kstart``/``rpos`` masks, int8 dequant in VMEM.

    ``adapters`` / ``adapter_slot`` (ISSUE 14): the request's LoRA term
    — the one-request sibling of :func:`paged_decode_forward`'s per-row
    gather (``adapter_slot`` is this request's pool slot; q/o adapters
    leave the chunk's CACHED K/V adapter-agnostic by construction, so
    prefix sharing stays valid across tenants).

    ``window_table``: the slot's page ids in the SLIDING layers' pool,
    by the same logical page index (entries of pages that slid out of
    the window hold the trash page and are never read). Those layers
    read one window of context, not ``ctx_cap`` (the temp cache of the
    gathering path holds the rows ``[ctx_len - wcap, ctx_len)``), so a
    chunk deep in a long prompt costs them what a shallow one does.
    ``with_stats``: also return the summed ``_moe_ffn`` stats of the
    chunk's valid rows.

    ``state_slot``: the row's slot in the recurrent-state pool of a config
    with state-space layers. The chunk starts from the slot's state and
    convolution tail and writes both back; at ``ctx_len`` 0 it starts from
    zeros whatever the slot holds, which is how an admission's reset of
    its slot takes effect (``PagedKVCache``). The attention layers of such
    a config go through the same gather and scatter as any full layer."""
    B, C = tokens.shape
    if B != 1:
        raise ValueError(
            f"paged_prefill_chunk: one request at a time (got batch {B})")
    _refuse_parts(cfg, tp_axis=tp_axis, dp_axis=dp_axis, fused=fused,
                  adapters=adapters)
    if cfg.latent is not None:
        # the chunk's latents go straight into the pool and its queries
        # attend over the row's pages: no temp cache is gathered
        from . import latent as _latent
        _latent.refuse(tp_axis=tp_axis, dp_axis=dp_axis, fused=fused,
                       adapters=adapters)
        out = _latent.forward_chunk(
            params, tokens, paged, block_table, cfg, ctx_cap=ctx_cap,
            ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=use_kernel)
        return out if with_stats else out[:2]
    page = paged["k"].shape[2]
    if ctx_cap % page:
        raise ValueError(
            f"paged_prefill_chunk: ctx_cap={ctx_cap} must be a multiple "
            f"of the page size {page}")
    if cfg.hybrid is None and not fused:
        return _chunk_over_pages(
            params, tokens, paged, block_table, cfg, ctx_cap=ctx_cap,
            ctx_len=ctx_len, chunk_len=chunk_len, window_table=window_table,
            tp_axis=tp_axis, dp_axis=dp_axis, adapters=adapters,
            adapter_slot=adapter_slot, use_kernel=use_kernel,
            with_stats=with_stats)
    ext = block_table.shape[0] * page
    quant = "ks" in paged
    W = ctx_cap + C
    ctx_len = jnp.asarray(ctx_len, jnp.int32).reshape(())
    chunk_len = jnp.asarray(chunk_len, jnp.int32).reshape(())
    pad = ctx_cap - ctx_len                       # garbage rows below
    sliding = "sliding" in cfg.cache_layers()
    # context rows a sliding layer's first chunk query can see: one
    # window less itself, in whole pages, never more than the context
    wcap = (min(ctx_cap, -(-(cfg.sliding_window - 1) // page) * page)
            if sliding else 0)
    dense = init_cache(cfg, 1, W, kv_dtype="int8" if quant else None,
                       num_kv_heads=paged["k"].shape[3],
                       window_len=wcap + C)
    if ctx_cap:
        ppc = ctx_cap // page
        ctx_tbl = block_table[:ppc]
        srows = jnp.clip(jnp.arange(ctx_cap, dtype=jnp.int32) - pad,
                         0, ctx_cap - 1)
        for name in _of_kind(paged, "full"):
            g = _take_pages(paged[name], ctx_tbl)       # (L, ppc*pg, .)
            g = jnp.take(g, srows, axis=1)              # right-aligned
            dense[name] = dense[name].at[:, 0, :ctx_cap].set(
                g.astype(dense[name].dtype))
    if wcap:
        # temp row t holds logical position ctx_len - wcap + t; the
        # pages that cover them start at the window's first live page
        lo = ctx_len - wcap
        p0 = jnp.maximum(lo, 0) // page
        npg = wcap // page + 1
        win_tbl = jnp.take(window_table, jnp.clip(
            p0 + jnp.arange(npg, dtype=jnp.int32), 0,
            window_table.shape[0] - 1))
        wrows = jnp.clip(lo - p0 * page
                         + jnp.arange(wcap, dtype=jnp.int32),
                         0, npg * page - 1)
        for name in _of_kind(paged, "sliding"):
            name += KIND_SUFFIX["sliding"]
            g = jnp.take(_take_pages(paged[name], win_tbl), wrows, axis=1)
            dense[name] = dense[name].at[:, 0, :wcap].set(
                g.astype(dense[name].dtype))
    kstart = pad[None]                                  # (1,)
    rpos = (ctx_len + jnp.arange(C, dtype=jnp.int32))[None, :]
    pos = jnp.arange(C, dtype=jnp.int32)
    new = {}
    if cfg.hybrid is not None:
        from . import hybrid as _hybrid
        _hybrid.refuse(tp_axis=tp_axis, dp_axis=dp_axis, fused=fused,
                       adapters=adapters)
        logits, dense, state, stats = _hybrid.forward_chunk(
            params, tokens, dense,
            {n: paged[n] for n in STATE_ARRAYS}, state_slot, ctx_cap, cfg,
            kstart=kstart, ctx_len=ctx_len, chunk_len=chunk_len,
            use_kernel=use_kernel)
        new.update(state)
        out = (logits, dense) + ((stats,) if with_stats else ())
    else:
        out = _forward_cached(params, tokens, dense, ctx_cap, cfg,
                              W, use_kernel=use_kernel, rpos=rpos,
                              kstart=kstart, logits_at=chunk_len - 1,
                              tp_axis=tp_axis, dp_axis=dp_axis,
                              fused=bool(fused), adapters=adapters,
                              adapter_slots=adapter_slot, pos_w=wcap,
                              kstart_w=(jnp.maximum(wcap - ctx_len, 0)[None]
                                        if sliding else None),
                              moe_valid=(pos < chunk_len)[None, :],
                              with_stats=with_stats)
    logits, dense = out[0], out[1]
    logical = jnp.clip(ctx_len + pos, 0, ext - 1)
    for kind in (k for k in cfg.cache_layers() if k in KIND_SUFFIX):
        table = window_table if kind == "sliding" else block_table
        at = wcap if kind == "sliding" else ctx_cap
        dst = jnp.where(pos < chunk_len,
                        table[logical // page] * page + logical % page,
                        0)
        for name in _of_kind(paged, kind):
            name += KIND_SUFFIX[kind]
            rows = dense[name][:, 0, at:]               # (L, C, ...)
            new[name] = _scatter_rows(paged[name], dst, rows)
    return (logits, new) + tuple(out[2:])


def _chunk_over_pages(params, tokens, paged: Dict, block_table,
                      cfg: LlamaConfig, *, ctx_cap: int, ctx_len, chunk_len,
                      window_table, tp_axis, dp_axis, adapters, adapter_slot,
                      use_kernel, with_stats):
    """:func:`paged_prefill_chunk` for a K/V decoder, unfused: each layer
    writes the chunk's keys and values into the row's pages (the int8
    tier's rows and scales as :func:`_cache_write` makes them), then its
    queries attend over the row's pages, their own rows among them,
    through :func:`~paddle_tpu.ops.pallas.paged_chunk_attention.
    kv_chunk_attention` (the twin reaches ``ctx_cap + C`` positions, the
    kernel the row's true length). The pools ride whole in the layer
    scan's carry (:func:`_paged_layers`), donated and written in place;
    no temp cache is gathered."""
    from ..ops.pallas.paged_chunk_attention import kv_chunk_attention
    C = tokens.shape[1]
    page = paged["k"].shape[2]
    ppseq = block_table.shape[0]
    ext = ppseq * page
    ctx_len = jnp.asarray(ctx_len, jnp.int32).reshape(())
    chunk_len = jnp.asarray(chunk_len, jnp.int32).reshape(())
    pos = jnp.arange(C, dtype=jnp.int32)
    rpos = (ctx_len + pos)[None, :]
    logical = jnp.clip(ctx_len + pos, 0, ext - 1)
    kinds = tuple(dict.fromkeys(cfg.prologue + cfg.period))
    tables = {"full": block_table, "sliding": window_table}
    dsts = {kind: jnp.where(pos < chunk_len, tables[kind][logical // page]
                            * page + logical % page, 0) for kind in kinds}
    rope = llama.rope_tables_by_kind(cfg, ext)
    # the pages of the context's bucket and the chunk (the twin's reach)
    seen = min(ppseq, ctx_cap // page + -(-C // page))

    def write(pool, dst, rows):
        return pool.reshape((-1,) + pool.shape[2:]).at[dst].set(
            rows.astype(pool.dtype)).reshape(pool.shape)

    def attend(q, k, v, held, kind, dst, table):
        cos, sin = rope[kind]
        if cos is not None:
            q = _rope_rows(q, cos, sin, rpos)
            k = _rope_rows(k, cos, sin, rpos)
        if "ks" in held:
            (k, ks), (v, vs) = _rowq(k), _rowq(v)
            rows = {"k": k, "v": v, "ks": ks, "vs": vs}
        else:
            rows = {"k": k, "v": v}
        held = {n: write(held[n], dst, r[0]) for n, r in rows.items()}
        o = kv_chunk_attention(
            q[0], held["k"], held["v"], table, ctx_len,
            chunk_len, bucket_pages=seen, window=cfg.window_of(kind),
            ks_pages=held.get("ks"), vs_pages=held.get("vs"),
            use_kernel=use_kernel)
        return o, held

    pools = {kind: _of_kind(paged, kind) for kind in kinds}
    x, new, stats = _paged_layers(
        params, cfg, llama.embed_rows(params, tokens, cfg),
        {kind: {n: a.reshape((-1,) + a.shape[2:]) for n, a in d.items()}
         for kind, d in pools.items()},
        {kind: d["k"].shape[1] for kind, d in pools.items()}, dsts, tables,
        attend, valid=pos < chunk_len, use_kernel=use_kernel,
        tp_axis=tp_axis, dp_axis=dp_axis, adapters=adapters,
        adapter_slots=adapter_slot)
    new_paged = {n + KIND_SUFFIX[kind]: a.reshape(
        paged[n + KIND_SUFFIX[kind]].shape)
        for kind in kinds for n, a in new[kind].items()}
    x = lax.dynamic_slice_in_dim(x, jnp.clip(chunk_len - 1, 0, C - 1), 1,
                                 axis=1)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        head = params["embed"].T.astype(x.dtype)
        gather = False
    else:
        head = _w(params, "lm_head", x.dtype)
        gather = tp_axis is not None                # vocab-sharded
    logits = (x[:, -1] @ head).astype(jnp.float32)
    if gather:
        logits = _tp_allgather(logits, tp_axis, 1)
    return (logits, new_paged) + ((stats,) if with_stats else ())


def paged_verify_forward(params, tokens: jax.Array, paged: Dict,
                         block_tables: jax.Array, lengths: jax.Array,
                         cfg: LlamaConfig, *, ctx_cap: int, active=None,
                         use_kernel=None, tp_axis=None, dp_axis=None,
                         fused=None, adapters=None, adapter_slots=None,
                         tree_depth=None, tree_mask=None):
    """Batched speculative-decode VERIFY: score a ``T``-token chunk for
    EVERY speculating row against its paged KV in ONE forward — the
    batched generalization of :func:`paged_prefill_chunk` (which runs
    one request's chunk; here every row carries its own block table and
    context length).

    tokens:       (B, T) int32 — per row: ``[last_sampled_token,
                  draft_1, ..., draft_{T-1}]`` (rows proposing fewer
                  drafts right-pad; pad lanes are causally masked from
                  every earlier position, so their garbage never
                  reaches an accepted token's logits)
    block_tables: (B, ppseq) int32 page ids per slot
    lengths:      (B,) tokens already COMMITTED in each row's pages
                  (the chunk's KV lands at ``lengths + i``); must be
                  <= ``ctx_cap``
    ctx_cap:      STATIC page multiple >= max(lengths) — the gathered
                  context width / compile key (callers bucket it to
                  power-of-two page counts, same as the chunk program)
    active:       (B,) bool — inactive rows compute (static shapes) but
                  their KV writes route to the trash page
    returns (logits (B, T, V) f32 at EVERY chunk position, updated
    pools). ``argmax(logits[r, i])`` is the greedy next token given the
    row's context plus ``tokens[r, :i+1]`` — the verify target for
    draft ``i+1`` and the bonus token at the first rejection.

    Math is the chunk program's, vectorized over rows: per-row context
    gathered from pages and RIGHT-ALIGNED into a ``(B, ctx_cap + T)``
    dense temp cache (``kstart`` masks the pad rows below), the chunk
    forwards at temp positions ``[ctx_cap, ctx_cap + T)`` with logical
    rope positions ``lengths + i``, and the new rows scatter back into
    each row's pages. Cached rows are bit-identical and masked columns
    contribute exact zeros, so greedy acceptance against these logits
    is TOKEN-IDENTICAL to plain paged decode at fp and int8-KV (gated
    in tests/test_spec_decode.py). Rejected-tail rows need NO device
    rollback: the host simply doesn't advance ``lengths`` past the
    accepted prefix, the length mask keeps stale rows invisible, and
    sequential writes overwrite them before the mask ever reaches them
    (the same contract decode already relies on for retired tenants).

    ``dp_axis`` (ISSUE 17): run as one dp shard of the 2-D mesh — the
    batch args arrive SPLIT over dp (B is the per-shard rows), pools
    stay dp-replicated; this program has ONE gather site at the end:
    the new KV rows + destination slots all-gather across dp before
    the scatter (full-batch writes on every replica, single-chip row
    order) and the logits batch-gather to (B_total, T, V).

    TREE mode (ISSUE 20): with ``tree_depth`` (B, T) int32 per-node
    depths (root 0) and ``tree_mask`` (B, T, T) bool ancestor-or-self
    matrices, the T chunk lanes are token-TREE nodes instead of a
    linear draft: rope positions become ``lengths + depth`` and the
    ancestor matrix replaces the intra-chunk causal triangle (see
    :func:`_attn_with_cache`), so ``logits[r, i]`` scores node i
    against exactly its ROOT PATH — the whole tree verifies in this
    ONE forward. Same-depth nodes would collide at the same page slot,
    so tree mode does NOT scatter: it returns ``(logits, rows)`` where
    ``rows[name]`` is the (L, B, T, ...) per-node new KV (rope'd,
    int8-quantized — everything but placed); the host picks the
    accepted root path and :func:`paged_tree_commit` scatters exactly
    those nodes. Pools pass through untouched (the caller keeps its
    reference), so rejection needs no rollback at all."""
    B, T = tokens.shape
    _refuse_sliding(cfg, "paged_verify_forward")
    _refuse_parts(cfg, tp_axis=tp_axis, dp_axis=dp_axis, fused=fused,
                  adapters=adapters)
    tree = tree_depth is not None
    if tree and tree_mask is None:
        raise ValueError("paged_verify_forward: tree_depth requires "
                         "tree_mask (and vice versa)")
    page = paged["k"].shape[2]
    if ctx_cap % page:
        raise ValueError(
            f"paged_verify_forward: ctx_cap={ctx_cap} must be a "
            f"multiple of the page size {page}")
    ext = block_tables.shape[1] * page
    quant = "ks" in paged
    W = ctx_cap + T
    if active is None:
        active = jnp.ones((B,), bool)
    lengths = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, ctx_cap)
    pad = ctx_cap - lengths                              # (B,)
    dense = init_cache(cfg, B, W, kv_dtype="int8" if quant else None,
                       num_kv_heads=paged["k"].shape[3])
    if ctx_cap:
        ppc = ctx_cap // page
        ctx_tbl = block_tables[:, :ppc]                  # (B, ppc)
        srows = jnp.clip(jnp.arange(ctx_cap, dtype=jnp.int32)[None, :]
                         - pad[:, None], 0, ctx_cap - 1)  # (B, ctx_cap)
        for name in paged:
            g = jnp.take(paged[name], ctx_tbl, axis=1)   # (L,B,ppc,pg,.)
            g = g.reshape((g.shape[0], B, ppc * page) + g.shape[4:])
            idx = srows[None].reshape(
                (1, B, ctx_cap) + (1,) * (g.ndim - 3))
            g = jnp.take_along_axis(g, idx, axis=2)      # right-aligned
            dense[name] = dense[name].at[:, :, :ctx_cap].set(
                g.astype(dense[name].dtype))
    if tree:
        rpos = lengths[:, None] + jnp.asarray(tree_depth, jnp.int32)
    else:
        rpos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    logits, dense = _forward_cached(params, tokens, dense, ctx_cap, cfg,
                                    W, use_kernel=use_kernel, rpos=rpos,
                                    kstart=pad, logits_all=True,
                                    tp_axis=tp_axis, dp_axis=dp_axis,
                                    fused=bool(fused),
                                    adapters=adapters,
                                    adapter_slots=adapter_slots,
                                    tree_mask=(jnp.asarray(tree_mask, bool)
                                               if tree else None))
    if dp_axis is not None:
        logits = _tp_allgather(logits, dp_axis, 0)       # full batch
    if tree:
        # no scatter: same-depth nodes share a page slot, so placement
        # waits for the host's accepted root path (paged_tree_commit)
        rows = {name: dense[name][:, :, ctx_cap:] for name in paged}
        return logits, rows
    # scatter the T new rows of every row into its pages; inactive rows
    # and positions past the slot extent route to the trash page
    pos = rpos                                           # (B, T)
    ok = active[:, None] & (pos < ext)
    posc = jnp.clip(pos, 0, ext - 1)
    row = jnp.arange(B)[:, None]
    dst = jnp.where(ok, block_tables[row, posc // page] * page
                    + posc % page, 0)                    # (B, T)
    dst = dst.reshape(-1)
    if dp_axis is not None:
        dst = _tp_allgather(dst, dp_axis, 0)             # (B_total*T,)
    out = {}
    for name in paged:
        rows = dense[name][:, :, ctx_cap:]               # (L, B, T, ...)
        rows = rows.reshape((rows.shape[0], B * T) + rows.shape[3:])
        if dp_axis is not None:
            # full-batch rows in shard order — row b*T+t of the global
            # batch, matching the gathered dst exactly
            rows = _tp_allgather(rows, dp_axis, 1)
        out[name] = _scatter_rows(paged[name], dst, rows)
    return logits, out


def paged_tree_commit(paged: Dict, rows: Dict, block_tables: jax.Array,
                      lengths: jax.Array, path_nodes: jax.Array,
                      path_len: jax.Array, *, dp_axis=None):
    """Place the ACCEPTED root path of a tree verify into the paged
    pools — the deferred second half of
    :func:`paged_verify_forward`'s tree mode.

    rows:       per-node new KV from the tree verify — ``rows[name]``
                is (L, B, T, ...), node-indexed on axis 2
    path_nodes: (B, T) int32 node indices of each row's accepted root
                path in COMMIT ORDER (entry 0 is the tree root — its
                KV lands at position ``lengths``, exactly where the
                linear verify writes ``chunk[:, 0]``); entries past
                ``path_len`` are don't-care
    path_len:   (B,) int32 committed node count (= accepted + 1 with
                the bonus token's node never included — the bonus has
                no KV yet, its row decodes it next step; rows that
                committed nothing pass 0)

    Gathers each row's path nodes out of ``rows`` and scatters them at
    positions ``lengths + d`` — pure data movement (no model math), so
    the committed pool state is bit-identical to what a linear verify
    of the accepted path would have written. Unaccepted nodes are
    simply never placed: the tree path inherits the linear path's
    no-rollback contract for free. Under dp the destinations + rows
    all-gather before the scatter (pools stay replicated, same as the
    linear verify's single gather site)."""
    some = next(iter(rows.values()))
    B, T = some.shape[1], some.shape[2]
    page = paged["k"].shape[2]
    ext = block_tables.shape[1] * page
    path_nodes = jnp.clip(jnp.asarray(path_nodes, jnp.int32), 0, T - 1)
    path_len = jnp.asarray(path_len, jnp.int32)
    d = jnp.arange(T, dtype=jnp.int32)[None, :]          # (1, T)
    pos = jnp.asarray(lengths, jnp.int32)[:, None] + d   # (B, T)
    ok = (d < path_len[:, None]) & (pos < ext)
    posc = jnp.clip(pos, 0, ext - 1)
    row = jnp.arange(B)[:, None]
    dst = jnp.where(ok, block_tables[row, posc // page] * page
                    + posc % page, 0).reshape(-1)        # (B*T,)
    if dp_axis is not None:
        dst = _tp_allgather(dst, dp_axis, 0)
    out = {}
    for name in rows:
        r = rows[name]                                   # (L, B, T, ...)
        idx = path_nodes[None].reshape(
            (1, B, T) + (1,) * (r.ndim - 3))
        r = jnp.take_along_axis(r, idx, axis=2)          # path order
        r = r.reshape((r.shape[0], B * T) + r.shape[3:])
        if dp_axis is not None:
            r = _tp_allgather(r, dp_axis, 1)
        out[name] = _scatter_rows(paged[name], dst, r)
    return out


def make_draft_params(params, cfg: LlamaConfig, n_layers: int):
    """Truncated-layer, shared-embedding DRAFT model (ISSUE 20): the
    first ``n_layers`` decoder layers of the target plus its embedding
    / final norm / head, by REFERENCE — no copies, no extra weight
    memory beyond what jax may materialize for sliced layer stacks.
    Returns ``(draft_params, draft_cfg)`` ready for every paged program
    in this module (the draft model is just a smaller Llama). Sharded
    targets stay sharded: slicing the stacked (L, ...) layer arrays on
    axis 0 preserves each leaf's head/vocab partitioning, so the draft
    runs under the same tp mesh with the same param specs."""
    _refuse_latent(cfg, "make_draft_params (a truncated-layer draft model)")
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    if not (1 <= n_layers < L):
        raise ValueError(
            f"make_draft_params: n_layers must be in [1, {L}), got "
            f"{n_layers} (the draft must be a strict truncation)")
    draft = {k: v for k, v in params.items() if k != "layers"}
    draft["layers"] = jax.tree_util.tree_map(
        lambda a: a[:n_layers], params["layers"])
    return draft, dataclasses.replace(cfg, num_layers=n_layers)


def _paged_kv_attend(q, k, v, held, dst, table, lengths, page, *,
                     window=None, rope_row=None, use_kernel=None,
                     dp_axis=None, dtype=None):
    """The cache half of a decode layer: write the B new rows ``k`` / ``v``
    (B, 1, nkv, hd) at flat slots ``dst`` of the pools ``held`` (``(k, v,
    ks, vs)`` as pages ``(layers * P, page, ...)``, the scale pools None
    off the int8 tier) and attend ``q`` (B, 1, nh, hd) through ``table``
    over ``lengths + 1`` keys. ``rope_row``: the row's cos/sin for the
    fused kernel, which rotates ``q`` itself; None takes the unfused call.
    Returns ``(o (B, nh, hd), k, v, ks, vs)``."""
    from ..ops.pallas import paged_attention as _pa
    from ..ops.pallas import serving_fused as _sf
    kp, vp, ksp, vsp = held
    B, _, nh, hd = q.shape
    quant = ksp is not None

    def _pool_write(pool, rows):
        # dp shards scatter the FULL batch's rows (gathered in
        # shard order to match the full dst) into their pool
        # replica — identical writes on every replica, which is
        # what keeps the dp-replicated pools bit-identical
        if dp_axis is not None:
            rows = _tp_allgather(rows, dp_axis, 0)
        if pool.ndim == 3:
            # a scale pool as the kernel reads it (below): a scatter
            # would re-lay it whole, so write row after row in place
            return lax.fori_loop(
                0, rows.shape[0], lambda r, p: lax.dynamic_update_slice(
                    p, lax.dynamic_slice_in_dim(rows, r, 1)[None],
                    (dst[r] // page, 0, dst[r] % page * rows.shape[1])),
                pool)
        return pool.reshape((-1,) + pool.shape[2:]).at[dst].set(
            rows).reshape(pool.shape)

    if quant:
        sc = jnp.maximum(
            jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1) / 127.0,
            1e-8)
        kq = jnp.clip(jnp.round(k.astype(jnp.float32)
                                / sc[..., None]), -127, 127)
        vc = jnp.maximum(
            jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-1) / 127.0,
            1e-8)
        vq = jnp.clip(jnp.round(v.astype(jnp.float32)
                                / vc[..., None]), -127, 127)
        kp = _pool_write(kp, kq[:, 0].astype(jnp.int8))
        vp = _pool_write(vp, vq[:, 0].astype(jnp.int8))
        ksp = _pool_write(ksp, sc[:, 0].astype(jnp.float32))
        vsp = _pool_write(vsp, vc[:, 0].astype(jnp.float32))
        ksa, vsa = (a.reshape(kp.shape[:3]) for a in (ksp, vsp))
    else:
        ksa = vsa = None
        kp = _pool_write(kp, k[:, 0].astype(kp.dtype))
        vp = _pool_write(vp, v[:, 0].astype(vp.dtype))
    if rope_row is not None:
        # trace-time dispatch counter + bytes-saved estimate: the
        # rotated q's HBM write+read per layer (plus, on int8
        # tiers, the in-VMEM dequant the unfused reference pays as
        # an fp copy) — fires once per compile per layer, like
        # serving_tp_allgather
        _obs.serving_fused_dispatch(
            "decode_rope_attn",
            2 * B * nh * hd * jnp.dtype(dtype).itemsize)
        o = _sf.fused_paged_decode_attention(
            q[:, 0], *rope_row, kp, vp, table,
            lengths + 1, ks_pages=ksa, vs_pages=vsa,
            use_kernel=use_kernel)
    else:
        o = _pa.paged_attention(
            q[:, 0], kp, vp, table, lengths + 1,
            ks_pages=ksa, vs_pages=vsa, use_kernel=use_kernel,
            window=window)
    return o, kp, vp, ksp, vsp


def _paged_layers(params, cfg: LlamaConfig, x, pools: Dict, pool_pages,
                  dsts, tables, attend, *, valid, use_kernel=None,
                  tp_axis=None, dp_axis=None, adapters=None,
                  adapter_slots=None):
    """Every layer of a K/V decoder in order over ``x`` (B, T, hidden), the
    pools riding WHOLE in the layer scan's carry: ``pools`` {kind: {name:
    (layers of the kind * ``pool_pages[kind]``, page, ...)}} as ``attend``
    reads and writes them; no slice of a pool is copied out or back, and
    donated pools are written in place. ``dsts`` / ``tables`` by kind: the
    flat slots the new rows go to and the block tables, both counted from
    a kind's first layer; a layer whose pages start at ``base`` gets them
    moved up by ``base`` pages. ``attend(q, k, v, held, kind, dst, table)
    -> (o, held)`` is the cache half of a layer: ``q``,
    ``k``, ``v`` projected and QK-normed, not yet rotated; ``held`` the
    kind's pools; ``o`` the heads' results of the B x T rows in their
    order, ``(B * T, nh, hd)`` as a kernel gives them. ``valid`` (B * T,):
    the rows the ``_moe_ffn`` stats count. Inside a period each run of one kind
    (:func:`_runs`) is a scan of its own over one layer body; a plain
    decoder's period is one layer, and the scan over periods is its layer
    loop. Leading dense layers (``cfg.prologue``) run one by one before
    it, each on its kind's pool from the first layer on; the scanned
    layers' pools follow theirs. Returns ``(x, pools, the summed stats or
    None)``."""
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    if tp_axis is not None:
        nh, nkv = _tp_heads(params["layers"], cfg)
    aslot = asc = None
    if adapters is not None:
        aslot, asc = _adapter_prep(adapters, adapter_slots, cfg)
    period = cfg.period
    plen = len(period)
    pro = cfg.prologue
    # where in its kind's slice of a period a layer's pool sits
    slot_of = [period[:j].count(kind) for j, kind in enumerate(period)]

    def layer(xc, lp, kind, held, ad_l, at_layer, base):
        page = held["k"].shape[1]
        dst, table = dsts[kind] + base * page, tables[kind] + base
        h1 = rms_norm(xc, lp["attn_norm"], cfg.rms_eps)
        q = _project_heads(
            h1, _w(lp, "wq", xc.dtype), nh,
            None if ad_l is None
            else _lora_delta(h1, ad_l[0], ad_l[1], aslot, asc))
        k = _project_heads(h1, _w(lp, "wk", xc.dtype), nkv)
        v = _project_heads(h1, _w(lp, "wv", xc.dtype), nkv)
        if cfg.qk_norm:
            q, k = _qk_norm(q, k, lp, cfg)
        o, held = attend(q, k, v, held, kind, dst, table)
        if cfg.attn_gate:
            o = _gate(o.reshape(xc.shape[:2] + (nh, hd)), h1, lp, nh)
        o = o.reshape(xc.shape[:2] + (nh * hd,))
        if tp_axis is not None:
            o = _tp_allgather(o, tp_axis, 2)
        ow = o @ _w(lp, "wo", xc.dtype)
        if ad_l is not None:
            # the o-projection's adapter term: input is the (full-
            # width) attention output, B_o column-sharded with wo
            ow = ow + _lora_delta(o, ad_l[2], ad_l[3], aslot, asc)
        if tp_axis is not None:
            xo = xc + _tp_allgather(ow, tp_axis, 2)
        else:
            xo = xc + _post_norm(ow, lp, "post_attn_norm", cfg)
        h2 = rms_norm(xo, lp["mlp_norm"], cfg.rms_eps)
        stats = None
        if cfg.moe is not None and "wg" not in lp:
            # (a leading dense layer of an expert model has "wg")
            ff, stats = _moe_ffn(h2, lp, cfg, tp_axis=tp_axis,
                                 dp_axis=dp_axis,
                                 valid=valid.reshape(xc.shape[:2]),
                                 experts=experts, layer=at_layer,
                                 use_kernel=use_kernel)
            y = xo + _post_norm(ff, lp, "post_mlp_norm", cfg)
        else:
            g = jax.nn.silu((h2 @ _w(lp, "wg", xc.dtype)).astype(
                jnp.float32)).astype(xc.dtype)
            u = h2 @ _w(lp, "wu", xc.dtype)
            if tp_axis is not None:
                gu = _tp_allgather(g * u, tp_axis, 2)
                y = xo + _tp_allgather(gu @ _w(lp, "wd", xc.dtype),
                                       tp_axis, 2)
            else:
                y = xo + _post_norm((g * u) @ _w(lp, "wd", xc.dtype), lp,
                                    "post_mlp_norm", cfg)
        return y, held, stats

    scanned, experts = _split_experts(params["layers"])
    lps, take_lp = _layer_feed(scanned, plen)
    ads, take_ad = _layer_feed(
        None if adapters is None else tuple(
            adapters[n] for n in ("aq", "bq", "ao", "bo")), plen)
    index = jnp.arange(cfg.periods, dtype=jnp.int32)
    zero = _no_stats(scanned) if cfg.moe is not None else None

    def body(carry, xs):
        lps_i, ads_i, i = xs

        def one(carry, j, kind, first):
            # layer j of the period; ``first``: the period's first layer
            # of this run, at slot ``slot_of[first]`` of its kind, after
            # the prologue's layers of that kind
            xc, held, stats = carry
            base = ((i * period.count(kind) + slot_of[first]
                     + (j - first)) * pool_pages[kind])
            if pro.count(kind):
                base = base + pro.count(kind) * pool_pages[kind]
            xc, out, st = layer(
                xc, take_lp(lps_i, i, j), kind, held[kind],
                take_ad(ads_i, i, j), i * plen + j, base)
            return (xc, {**held, kind: out},
                    stats if st is None else stats + st)

        for kind, first, n in _runs(period):
            if n == 1:
                carry = one(carry, first, kind, first)
            else:
                carry, _ = lax.scan(
                    lambda cr, j, kind=kind, first=first: (
                        one(cr, j, kind, first), None),
                    carry, first + jnp.arange(n, dtype=jnp.int32))
        return carry, None
    for j, kind in enumerate(pro):
        x, out, _ = layer(
            x, jax.tree.map(lambda a: a[j], params["dense_layers"]), kind,
            pools[kind], None, None, pro[:j].count(kind) * pool_pages[kind])
        pools = {**pools, kind: out}
    (x, new, stats), _ = lax.scan(body, (x, pools, zero), (lps, ads, index))
    return x, new, stats


def paged_decode_forward(params, tokens: jax.Array, paged: Dict,
                         block_tables: jax.Array, lengths: jax.Array,
                         cfg: LlamaConfig, *, active=None,
                         use_kernel=None, tp_axis=None, dp_axis=None,
                         fused=None, adapters=None, adapter_slots=None,
                         window_tables=None, with_stats=False):
    """One continuous-batching decode step over the ragged batch: every
    slot advances one token in a single static-shape program.

    tokens:       (B,) int32 — each slot's previous token
    block_tables: (B, ppseq) int32 page ids per slot
    lengths:      (B,) valid lengths; the new token's KV lands at
                  position ``lengths`` and attention sees ``lengths+1``
    active:       (B,) bool — inactive slots still compute (static
                  shapes) but their KV writes are routed to the trash
                  page and their logits are garbage to be ignored
    returns (logits (B, V) f32, updated pools).

    One way through the layers for every config: a kind's pools ride
    whole in the layer scan's carry as ``(L_kind * P, page, ...)``; a
    layer writes its B rows at ``dst + base * page`` and attends through
    ``table + base``, ``base`` its first page. Donated pools are written
    in place (the compiled temp is gated in tests/test_v5e_aot.py).

    Math is kept op-for-op identical to the dense decode
    (:func:`_block_infer` + ``_attn_with_cache``-equivalent paged
    attention), so greedy tokens match the dense path exactly.

    ``tp_axis``: run as one shard of a tensor-parallel serving mesh
    (inside shard_map): weights arrive column-sharded, ``paged`` holds
    the shard's own kv heads (same page ids on every shard — block
    tables/lengths replicate), attention is per-head local (no comm in
    the kernel), and activations all-gather to full width before each
    contraction — exact concats, so tp decode stays BIT-identical to
    single-chip paged decode (gated in tests/test_tp_serving.py).

    ``fused`` (ISSUE 11): route attention through the FUSED
    dequant+RoPE+paged-attention kernel
    (:func:`~paddle_tpu.ops.pallas.serving_fused.
    fused_paged_decode_attention`) — q streams into the kernel
    unrotated with its per-row cos/sin rows and both the rotation and
    the int8 dequant happen in VMEM, removing the rotated-q HBM
    round-trip per layer. Off-TPU the fused reference path is
    BIT-identical to the unfused one by construction; the kernel path
    is gated token-identical per tier (tests/test_lowbit_decode.py).
    Weight-quantized params (int8/int4 — :func:`quantize_weights`) ride
    either path unchanged: ``_w`` dequants on the fly, which is the
    low-bit decode tier.

    ``adapters`` / ``adapter_slots`` (ISSUE 14): the multi-LoRA term —
    ``adapters`` is the :class:`~paddle_tpu.serving.adapters.
    AdapterPool` array dict (per-layer packed A/B factors + per-slot
    α/r scales), ``adapter_slots`` the (B,) per-row pool slot ids; the
    q and o projections grow a batched ``y += (x @ A_i) @ B_i · α/r``
    term gathered per row. Slot 0 is the base model's exact-zero
    factors, and ``adapters=None`` (the default) compiles the term out
    entirely — both ends of the bit-identity gate.

    ``dp_axis`` (ISSUE 17): run as one dp shard of a 2-D tp x dp
    serving mesh — the batch args (tokens/block_tables/lengths/active/
    adapter_slots) arrive SPLIT over dp (B here is the per-shard
    B/dp), while the page pools stay replicated across dp. Each shard
    computes its own rows' attention and FFN; the freshly computed KV
    rows AND their destination slots all-gather across dp (exact tiled
    concats in shard order) before every pool scatter, so each dp
    replica of the pool receives the FULL batch's writes in the single-
    chip row order and the replicas stay bit-identical. The logits
    batch-gather at the end hands every shard the full (B_total, V) —
    sampling stays on replicated data outside the mesh. With
    ``cfg.moe`` set the dense SwiGLU is replaced by :func:`_moe_ffn`
    (expert-parallel over dp when the expert stacks arrive
    E-sharded).

    ``window_tables`` (B, ppseq): each slot's page ids in the SLIDING
    layers' pool, by logical page index like ``block_tables``; a
    sliding layer writes and reads there, and its attention walks only
    the pages a window back from the row's length (the kernel's and the
    reference's ``window``). The fused decode kernel has no window, so
    under ``fused`` those layers take the unfused call.
    ``with_stats``: also return the ``_moe_ffn`` stats summed over
    layers, counted over the ``active`` rows.

    Leading dense layers (``cfg.prologue``) run one by one before the scan
    over periods, each on its kind's pool from the first layer on; the
    scanned layers' pools follow theirs."""
    _refuse_parts(cfg, tp_axis=tp_axis, dp_axis=dp_axis, fused=fused,
                  adapters=adapters)
    if cfg.hybrid is not None:
        from . import hybrid as _hybrid
        _hybrid.refuse(tp_axis=tp_axis, dp_axis=dp_axis, fused=fused,
                       adapters=adapters)
        out = _hybrid.decode_forward(
            params, tokens, paged, block_tables, lengths, cfg,
            active=active, use_kernel=use_kernel)
        return out if with_stats else out[:2]
    if cfg.latent is not None:
        from . import latent as _latent
        _latent.refuse(tp_axis=tp_axis, dp_axis=dp_axis, fused=fused,
                       adapters=adapters)
        out = _latent.decode_forward(
            params, tokens, paged, block_tables, lengths, cfg,
            active=active, use_kernel=use_kernel)
        return out if with_stats else out[:2]
    fused = bool(fused)
    B = tokens.shape[0]
    page = paged["k"].shape[2]
    ext = block_tables.shape[1] * page
    quant = "ks" in paged
    if active is None:
        active = jnp.ones((B,), bool)
    lengths = jnp.asarray(lengths, jnp.int32)
    kinds = tuple(dict.fromkeys(cfg.prologue + cfg.period))
    if "sliding" in kinds and window_tables is None:
        raise ValueError(
            "paged_decode_forward: the config has sliding layers; "
            "window_tables must give their pool's page ids")
    tables = {"full": block_tables, "sliding": window_tables}
    rope = llama.rope_tables_by_kind(cfg, ext)
    rpos = lengths[:, None]                          # (B, 1)
    if fused:
        # per-row rope table rows for the in-kernel rotation (the new
        # token sits at position ``lengths``, always < ext)
        rope_row = {kind: (jnp.take(c, lengths, axis=0),
                           jnp.take(s_, lengths, axis=0))
                    for kind, (c, s_) in rope.items()}
    # per-row destination slot; inactive rows dump into the trash page
    # (page 0 slot 0 — reserved by serving.BlockAllocator) so a retired
    # slot's stale table can never clobber a live request's pages
    row = jnp.arange(B)
    dsts = {}
    for kind in kinds:
        dst = jnp.where(active,
                        tables[kind][row, lengths // page] * page
                        + lengths % page,
                        0)
        if dp_axis is not None:
            # the FULL batch's destination slots, in single-chip row
            # order (tiled concat over dp shards = the batch split's
            # inverse); gathered ONCE here, closed over by every
            # layer's scatter
            dst = _tp_allgather(dst, dp_axis, 0)
        dsts[kind] = dst
    x = llama.embed_rows(params, tokens[:, None], cfg)   # (B, 1, H)
    names = ("k", "v", "ks", "vs") if quant else ("k", "v")

    def attend(q, k, v, held, kind, dst, table):
        cos, sin = rope[kind]
        window = cfg.window_of(kind)
        # the fused kernel has no window: a sliding layer's call is the
        # unfused one
        fuse = fused and window is None
        if cos is not None:
            if not fuse:
                # unfused: q rotates here in XLA and round-trips HBM into
                # the attention op; fused moves this rotation into VMEM
                q = _rope_rows(q, cos, sin, rpos)
            k = _rope_rows(k, cos, sin, rpos)
        o, *out = _paged_kv_attend(
            q, k, v, tuple(held.get(n) for n in ("k", "v", "ks", "vs")),
            dst, table, lengths, page, window=window, rope_row=rope_row[kind] if fuse else None,
            use_kernel=use_kernel, dp_axis=dp_axis, dtype=cfg.dtype)
        return o, dict(zip(names, out))

    pools = {kind: _of_kind(paged, kind) for kind in kinds}
    # K/V pools as (layers * pages, page, ...); the int8 tier's scale pools
    # as (layers * pages, 1, page * heads) lane rows, the layout the kernel
    # reads: laid out once a step here, not whole in every layer call.
    flat = {kind: {n: a.reshape((-1, 1, a.shape[2] * a.shape[3])
                                if a.ndim == 4 else (-1,) + a.shape[2:])
                   for n, a in d.items()} for kind, d in pools.items()}
    x, new, stats = _paged_layers(
        params, cfg, x, flat,
        {kind: d["k"].shape[1] for kind, d in pools.items()}, dsts, tables,
        attend, valid=active, use_kernel=use_kernel,
        tp_axis=tp_axis, dp_axis=dp_axis, adapters=adapters,
        adapter_slots=adapter_slots)
    new_paged = {n + KIND_SUFFIX[kind]: a.reshape(
        paged[n + KIND_SUFFIX[kind]].shape)
        for kind in kinds for n, a in new[kind].items()}
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        head = params["embed"].T.astype(x.dtype)    # replicated: full
        gather = False
    else:
        head = _w(params, "lm_head", x.dtype)
        gather = tp_axis is not None                # vocab-sharded
    logits = (x[:, -1] @ head).astype(jnp.float32)
    if gather:
        logits = _tp_allgather(logits, tp_axis, 1)
    if dp_axis is not None:
        # full-batch logits on every shard: sampling + constraint masks
        # stay on replicated data outside the mesh
        logits = _tp_allgather(logits, dp_axis, 0)
    if with_stats:
        return logits, new_paged, stats
    return logits, new_paged


def quantize_weights(params, cfg: LlamaConfig, bits: int = 8,
                     group_size: int = 128) -> Dict:
    """Weight-only quantization for serving (reference:
    paddle/phi/kernels/fusion weight_only_linear / llm.int8 path;
    python surface nn.quant.weight_quantize, weight_only int4 variant).

    ``bits=8``: per-output-channel symmetric int8, w ~= q * scale[None,:].
    ``bits=4``: per-group symmetric int4 (``group_size`` rows of the
    input dim share a scale — reference GroupWiseWeightObserver), stored
    as ``jnp.int4`` so HBM holds true 4-bit weights. Decode is
    HBM-bandwidth-bound, so weight bytes are the TPU win; dequant
    (convert+scale) fuses into the matmul read. The embedding table stays
    bf16 (it is a gather, and the tied head reuses it)."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")

    def q8(w):
        scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0) / 127.0
        scale = jnp.maximum(scale, 1e-8)
        qw = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[None, :]),
                      -127, 127).astype(jnp.int8)
        return qw, scale.astype(jnp.float32)

    def q4(w):
        din, dout = w.shape
        g = min(group_size, din)
        if din % g:
            # serving weights are multiples of 128; bail to one group
            g = din
        wf = w.astype(jnp.float32).reshape(din // g, g, dout)
        scale = jnp.max(jnp.abs(wf), axis=1) / 7.0          # (G, out)
        scale = jnp.maximum(scale, 1e-8)
        qw = jnp.clip(jnp.round(wf / scale[:, None, :]), -7, 7)
        return (qw.reshape(din, dout).astype(jnp.int4),
                scale.astype(jnp.float32))

    q = q4 if bits == 4 else q8
    out = {k: v for k, v in params.items()}

    def stacks(layers, names):
        layers = dict(layers)
        for name in names:
            if name not in layers:
                continue        # MoE trees: moe_* expert stacks stay fp
            qw, sc = jax.vmap(q)(layers[name])
            layers[name] = qw
            layers[name + "_scale"] = sc
        return layers
    if cfg.latent is not None:
        # the attention projections, the dense and the shared FFN; the
        # expert stacks stay as they are
        from .latent import QUANT_LEAVES
        for group in ("layers", "dense_layers"):
            if group in params:
                out[group] = stacks(params[group], QUANT_LEAVES)
    elif cfg.hybrid is not None:
        # a stack per kind: the attention, Mamba and dense MLP
        # projections; the expert layers stay as they are
        names = {"attention": ("wq", "wk", "wv", "wo"),
                 "mamba2": ("w_in", "w_out"),
                 "mamba1": ("w_in", "w_x", "w_dt", "w_out"),
                 "mlp": ("wg", "wu", "wd")}
        out["layers"] = {
            **params["layers"],
            **{kind: stacks(params["layers"][kind], names[kind])
               for kind in names if kind in params["layers"]}}
    else:
        # the attention projections (and its output gate), the dense and
        # the shared FFN; the expert stacks stay as they are
        names = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "wgate",
                 "ws_g", "ws_u", "ws_d")
        for group in ("layers", "dense_layers"):
            if group in params:
                out[group] = stacks(params[group], names)
    if not cfg.tie_embeddings and "lm_head" in params:
        qw, sc = q(params["lm_head"])
        out["lm_head"] = qw
        out["lm_head_scale"] = sc
    return out


def _w(lp, name, dtype):
    """Weight fetch with on-the-fly dequant when quantized: per-channel
    int8 (scale (out,)) or per-group int4 (scale (G, out))."""
    w = lp[name]
    if name + "_scale" in lp:
        s = lp[name + "_scale"]
        if s.ndim == w.ndim:              # per-group: (G, out) vs (in, out)
            gct = s.shape[-2]
            g = w.shape[-2] // gct
            wf = w.astype(dtype).reshape(w.shape[:-2] + (gct, g, w.shape[-1]))
            wf = wf * s[..., :, None, :].astype(dtype)
            return wf.reshape(w.shape)
        return w.astype(dtype) * s[None, :].astype(dtype)
    return w


def _project_heads(x, w, heads: int, delta=None):
    """Project ``x`` (..., in) through ``w`` (in, heads * d) and split the
    result into heads: (..., heads, d). ``delta`` (an adapter's term) is
    added before the split; ``w`` is ``_w``'s result.

    The barrier keeps the split out of the dot, and changes no value.
    Without it XLA:TPU folds the reshape into the dot, takes the head axis
    for a convolution's spatial dimension and wants the weight with its
    contraction dimension minor: the program then cuts the matrix out of
    the scanned stack, copies it transposed and feeds the dot from the
    copy in every layer call, or re-lays the whole stack ahead of the
    loop. Behind the barrier the dot is the plain (rows, in) x (in, out)
    one that reads its slice of the stack as stored, as ``wo``'s and the
    MLP's do (``tests/test_v5e_aot.py`` holds the compiled text)."""
    y = x @ w
    if delta is not None:
        y = y + delta
    return lax.optimization_barrier(y).reshape(x.shape[:-1] + (heads, -1))


def _qk_norm(q, k, lp, cfg: LlamaConfig):
    """QK-norm: each query and key head RMS-normed over its lanes, one gain
    a layer for all heads (``q_norm`` / ``k_norm``), before rotary and
    before the keys go into the cache."""
    return (rms_norm(q, lp["q_norm"], cfg.rms_eps),
            rms_norm(k, lp["k_norm"], cfg.rms_eps))


def _gate(o, u, lp, heads: int):
    """The attention's output gate on the heads' results ``o`` (..., heads,
    d): times ``sigmoid(u W_gate)`` elementwise, in float32, ``u`` the
    layer's normed input; ``W_gate`` projects like ``W_q``
    (:func:`_project_heads`)."""
    with jax.named_scope("attention_output_gate"):
        g = _project_heads(u, _w(lp, "wgate", u.dtype), heads)
        return (o.astype(jnp.float32)
                * jax.nn.sigmoid(g.astype(jnp.float32))).astype(o.dtype)


def _post_norm(y, lp, name: str, cfg: LlamaConfig):
    """A sandwich norm on a part's output before its residual add, where
    the config has them; ``y`` itself where not."""
    if not cfg.sandwich_norms:
        return y
    return rms_norm(y, lp[name], cfg.rms_eps)


#: the float32 score matrix of a multi-token attention above which
#: :func:`_attn_with_cache` takes one key head's group at a time (a chunk
#: of 256 over a 16k context at 48 heads would hold 0.8 GB)
SCORES_WHOLE_BYTES = 512 << 20


def _use_decode_kernel(override=None):
    """Pallas decode attention on real TPU; jnp composition elsewhere
    (interpret-mode pallas inside a scan is pointlessly slow on CPU)."""
    if override is not None:
        return override
    from ..ops.pallas import flash_attention as _fa
    return _fa.on_tpu()


def _attn_with_cache(q, ck, cv, length, nh, use_kernel=None,
                     kstart=None, k_rows=None, v_rows=None,
                     fused=False, tree_mask=None, window=None):
    """q (B,T,nh,hd) vs cache (B,Smax,nkv,hd); positions >= length masked.
    length: scalar or (B,) current valid length INCLUDING q's tokens.
    kstart: optional (B,) first VALID cache position per row (left-padded
    ragged prompts — positions below it are pad slots and masked out).
    k_rows/v_rows: per-row dequant scales (B, Smax, nkv) for an int8
    cache (see init_cache kv_dtype).
    fused (ISSUE 11): route MULTI-token ragged attention (the chunked-
    prefill and spec-verify programs — T > 1 with per-row ``kstart``)
    through the flash chunk kernel
    (:func:`~paddle_tpu.ops.pallas.serving_fused.flash_chunk_attention`)
    instead of materializing the full (B, H, T, W) score tensor; the
    off-TPU reference is op-for-op this function's jnp composition.
    tree_mask (ISSUE 20): optional (B, T, T) bool ancestor-or-self
    matrix for TREE speculative verify — the T chunk lanes are token-
    tree nodes, and node i may attend chunk lane j only when j lies on
    i's root path. It REPLACES the intra-chunk causal triangle (the
    committed cache below the chunk stays fully visible, the kstart pad
    mask still applies); a linear-chain tree's matrix is exactly the
    lower triangle, reproducing this function's causal mask bit for
    bit. Requires the verify layout: static ``length`` == Smax (the
    chunk is the last T cache rows).
    window: a sliding layer's — a query at cache position p sees the
    keys at ``p - window < kpos <= p`` (the dense decode kernel has no
    such bound, so a windowed layer takes the jnp or the flash path)."""
    B, T, _, hd = q.shape
    if (T == 1 and kstart is None and window is None
            and _use_decode_kernel(use_kernel)):
        # single-token decode: fused block attention against the padded
        # cache (reference: block_multi_head_attention_kernel.cu); int8
        # caches dequantize INSIDE the kernel
        from ..ops.pallas.fused import decode_attention
        o = decode_attention(q[:, 0], ck, cv, length,
                             k_dequant_rows=k_rows, v_dequant_rows=v_rows)
        return o[:, None]
    if tree_mask is not None and not (
            isinstance(length, int) and length == ck.shape[1]):
        raise ValueError(
            "_attn_with_cache: tree_mask requires the verify layout — "
            f"static length ({length}) == Smax ({ck.shape[1]})")
    if fused and kstart is not None and isinstance(length, int):
        # flash prefill/verify kernel: online softmax over cache blocks
        # with the exact kstart + per-query causal masks of the jnp
        # path below; int8 temp caches dequantize in VMEM. The
        # bytes-saved estimate is the f32 score+prob round-trip the
        # unfused composition materializes. Trace-time counter, once
        # per compile (serving_tp_allgather contract).
        from ..ops.pallas.serving_fused import flash_chunk_attention
        _obs.serving_fused_dispatch(
            "chunk_flash_attn", 2 * B * nh * T * ck.shape[1] * 4)
        return flash_chunk_attention(
            q, ck, cv, length, kstart, k_rows=k_rows, v_rows=v_rows,
            use_kernel=use_kernel, tree_mask=tree_mask, window=window)
    if k_rows is not None:
        # XLA fuses the dequant into the attention reads
        ck = (ck.astype(jnp.float32) * k_rows[..., None]).astype(q.dtype)
        cv = (cv.astype(jnp.float32) * v_rows[..., None]).astype(q.dtype)
    nkv = ck.shape[2]
    if (T > 1 and nkv > 1 and tree_mask is None
            and B * nh * T * ck.shape[1] * 4 > SCORES_WHOLE_BYTES):
        # one key head's group of query heads at a time: the float32
        # scores of all heads of a long chunk would not fit beside a
        # chip's weights and pools
        rep = nh // nkv
        qg = q.reshape(B, T, nkv, rep, hd)

        def group(g):
            cut = lambda a: lax.dynamic_slice_in_dim(a, g, 1, axis=2)
            return _attn_with_cache(
                lax.dynamic_index_in_dim(qg, g, 2, keepdims=False),
                cut(ck), cut(cv), length, rep, kstart=kstart, window=window)
        o = lax.map(group, jnp.arange(nkv))          # (nkv, B, T, rep, hd)
        return jnp.moveaxis(o, 0, 2).reshape(B, T, nh, hd)
    if nkv != nh:
        ck = jnp.repeat(ck, nh // nkv, axis=2)
        cv = jnp.repeat(cv, nh // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   ck.astype(jnp.float32)) / math.sqrt(hd)
    Smax = ck.shape[1]
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 3)
    if tree_mask is None:
        # query i (global position length-T+i) attends to kpos <= its
        # position
        qpos = (length - T) + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= qpos, s, -1e30)
        if window is not None:
            s = jnp.where(kpos > qpos - window, s, -1e30)
    else:
        if window is not None:
            raise ValueError("_attn_with_cache: tree_mask with a window")
        # tree verify: committed columns (below the chunk) stay fully
        # visible, chunk columns obey the ancestor matrix
        allow = jnp.concatenate(
            [jnp.ones((B, T, Smax - T), bool), tree_mask], axis=2)
        s = jnp.where(allow[:, None], s, -1e30)
    if kstart is not None:
        s = jnp.where(kpos >= kstart[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(cv.dtype), cv)


def _rope_rows(x, cos, sin, rpos):
    """Per-row rope: x (B,T,H,hd), rpos (B,T) int32 logical positions
    (ragged left-padded prompts shift each row's rotation)."""
    c = cos[rpos][:, :, None, :]
    s = sin[rpos][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _rowq(t):
    """Per-row symmetric int8: (B,T,nkv,hd) -> (int8 rows, (B,T,nkv)
    scales)."""
    sc = jnp.maximum(jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
                     / 127.0, 1e-8)
    ti = jnp.clip(jnp.round(t.astype(jnp.float32) / sc[..., None]),
                  -127, 127).astype(jnp.int8)
    return ti, sc.astype(jnp.float32)


def _cache_write(cache_k, cache_v, cache_ks, cache_vs, k, v, pos):
    """Write the rows ``k`` / ``v`` (B, T, nkv, hd) of one layer into its
    dense cache at ``pos``; with scale arrays, as int8 rows and their
    scales. Returns the four arrays (scales None without them)."""
    if cache_ks is not None:
        kqr, ksc = _rowq(k)
        vqr, vsc = _rowq(v)
        cache_k = lax.dynamic_update_slice_in_dim(cache_k, kqr, pos,
                                                  axis=1)
        cache_v = lax.dynamic_update_slice_in_dim(cache_v, vqr, pos,
                                                  axis=1)
        cache_ks = lax.dynamic_update_slice_in_dim(cache_ks, ksc, pos,
                                                   axis=1)
        cache_vs = lax.dynamic_update_slice_in_dim(cache_vs, vsc, pos,
                                                   axis=1)
    else:
        cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(
            cache_k.dtype), pos, axis=1)
        cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(
            cache_v.dtype), pos, axis=1)
    return cache_k, cache_v, cache_ks, cache_vs


def _block_infer(x, lp, cache_k, cache_v, pos, cos, sin, cfg: LlamaConfig,
                 use_kernel=None, rpos=None, kstart=None,
                 cache_ks=None, cache_vs=None, tp_axis=None,
                 dp_axis=None, fused=False, ad_l=None, aslot=None,
                 ascale=None, tree_mask=None, window=None,
                 moe_valid=None, experts=None, layer=0):
    """One decoder layer over T tokens starting at cache index ``pos``.
    cache_k/v: (B, Smax, nkv, hd) this layer's cache; returns
    ``(x, cache_k, cache_v, cache_ks, cache_vs, moe stats or None)``.
    window: the layer's sliding window (see :func:`_attn_with_cache`);
    moe_valid: (B, T) rows the ``_moe_ffn`` stats count; experts,
    layer: the expert stacks of all layers and which one this is.
    rpos: optional (B,T) per-row rope positions (!= cache index when the
    batch is left-padded); kstart: optional (B,) first valid cache slot.
    cache_ks/vs: (B, Smax, nkv) per-row dequant scales when the cache is
    int8 (see init_cache kv_dtype).
    tp_axis: mesh axis name when running as one shard of a
    tensor-parallel serving mesh (inside shard_map): weights arrive
    column-sharded (local head/ffn/hidden output columns), the cache
    holds the shard's own kv heads, and activations all-gather to full
    width before each contraction — exact concats, so the math stays
    bit-identical to the single-chip path (see llama.SERVING_TP_RULES).
    ad_l/aslot/ascale (ISSUE 14): this layer's adapter-pool factor
    slice + per-row slot/scale — the q/o projections grow the batched
    LoRA term (see :func:`paged_decode_forward`); None compiles it out.
    """
    B, T, H = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    if tp_axis is not None:
        nh, nkv = _tp_heads(lp, cfg)
    h1 = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = _project_heads(
        h1, _w(lp, "wq", x.dtype), nh,
        None if ad_l is None
        else _lora_delta(h1, ad_l[0], ad_l[1], aslot, ascale))
    k = _project_heads(h1, _w(lp, "wk", x.dtype), nkv)
    v = _project_heads(h1, _w(lp, "wv", x.dtype), nkv)
    if cfg.qk_norm:
        q, k = _qk_norm(q, k, lp, cfg)
    if cos is None:
        pass                                     # no rotary embedding
    elif rpos is None:
        q = apply_rope(q, lax.dynamic_slice_in_dim(cos, pos, T),
                       lax.dynamic_slice_in_dim(sin, pos, T))
        k = apply_rope(k, lax.dynamic_slice_in_dim(cos, pos, T),
                       lax.dynamic_slice_in_dim(sin, pos, T))
    else:
        q = _rope_rows(q, cos, sin, rpos)
        k = _rope_rows(k, cos, sin, rpos)
    quant = cache_ks is not None
    cache_k, cache_v, cache_ks, cache_vs = _cache_write(
        cache_k, cache_v, cache_ks, cache_vs, k, v, pos)
    o = _attn_with_cache(q, cache_k, cache_v, pos + T, nh,
                         use_kernel=use_kernel, kstart=kstart,
                         k_rows=cache_ks if quant else None,
                         v_rows=cache_vs if quant else None,
                         fused=fused, tree_mask=tree_mask, window=window)
    if cfg.attn_gate:
        o = _gate(o, h1, lp, nh)
    o = o.reshape(B, T, nh * hd)
    if tp_axis is not None:
        # full heads before the (column-sharded) wo contraction, then
        # full hidden before the residual add — both exact concats
        o = _tp_allgather(o, tp_axis, 2)
    ow = o @ _w(lp, "wo", x.dtype)
    if ad_l is not None:
        ow = ow + _lora_delta(o, ad_l[2], ad_l[3], aslot, ascale)
    if tp_axis is not None:
        x = x + _tp_allgather(ow, tp_axis, 2)
    else:
        x = x + _post_norm(ow, lp, "post_attn_norm", cfg)
    h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if cfg.moe is not None and "wg" not in lp:
        # serving MoE FFN (ISSUE 17): dense-dispatch on a single chip,
        # expert-parallel over dp when the stacks arrive E-sharded
        ff, stats = _moe_ffn(h2, lp, cfg, tp_axis=tp_axis,
                             dp_axis=dp_axis, valid=moe_valid,
                             experts=experts, layer=layer,
                             use_kernel=use_kernel)
        ff = _post_norm(ff, lp, "post_mlp_norm", cfg)
        return x + ff, cache_k, cache_v, cache_ks, cache_vs, stats
    g = jax.nn.silu((h2 @ _w(lp, "wg", x.dtype)).astype(
        jnp.float32)).astype(x.dtype)
    u = h2 @ _w(lp, "wu", x.dtype)
    if tp_axis is not None:
        gu = _tp_allgather(g * u, tp_axis, 2)
        ff = _tp_allgather(gu @ _w(lp, "wd", x.dtype), tp_axis, 2)
        return x + ff, cache_k, cache_v, cache_ks, cache_vs, None
    ff = _post_norm((g * u) @ _w(lp, "wd", x.dtype), lp, "post_mlp_norm", cfg)
    return (x + ff, cache_k, cache_v, cache_ks, cache_vs, None)


def _forward_cached(params, tokens, cache, pos, cfg: LlamaConfig,
                    max_len: int, use_kernel=None, rpos=None,
                    kstart=None, logits_at=None, logits_all=False,
                    tp_axis=None, dp_axis=None, fused=False,
                    adapters=None, adapter_slots=None, tree_mask=None,
                    pos_w=None, kstart_w=None, moe_valid=None,
                    with_stats=False):
    """tokens (B, T) at cache positions [pos, pos+T) -> (logits_last
    (B, V), updated cache). ``logits_at``: optional TRACED row index
    into ``tokens`` — logits are taken there instead of at row T-1
    (chunked prefill right-pads the final chunk, so the last VALID
    token is not the last row). ``logits_all``: return logits at EVERY
    row — (B, T, V) — for the speculative-verify program, which needs
    the greedy target at all draft positions. ``tp_axis``: run as one
    shard of a tensor-parallel serving mesh (see :func:`_block_infer`);
    the vocab-sharded lm_head's partial logits all-gather at the end —
    the single logits collective the tp decode path pays.

    ``cache`` holds one set of arrays a layer kind (:func:`init_cache`).
    ``pos_w`` / ``kstart_w``: where the tokens sit in the SLIDING
    layers' arrays and those arrays' first valid slot, when they differ
    from ``pos`` / ``kstart`` (the chunk program's narrower window
    cache); rope positions are the same for both. ``with_stats``: a
    third result, the ``_moe_ffn`` stats summed over layers, counted
    over the rows ``moe_valid`` marks.

    Leading dense layers (``cfg.prologue``) run one by one before the scan,
    each against its kind's arrays from the first on."""
    x = llama.embed_rows(params, tokens, cfg)
    period = cfg.period
    plen = len(period)
    pro = cfg.prologue
    kinds = tuple(dict.fromkeys(period))
    rope = llama.rope_tables_by_kind(cfg, max_len)
    at = {"full": (pos, kstart),
          "sliding": (pos if pos_w is None else pos_w,
                      kstart if kstart_w is None else kstart_w)}
    quant = "ks" in cache
    names = ("k", "v", "ks", "vs") if quant else ("k", "v")
    slot_of = [period[:j].count(kind) for j, kind in enumerate(period)]
    aslot = asc = None
    if adapters is not None:
        aslot, asc = _adapter_prep(adapters, adapter_slots, cfg)

    def body(xc, xs):
        # one period: its runs of one layer kind in order (:func:`_runs`),
        # each against its kind's cache; a run of several layers is a
        # scan of its own over one layer body
        lps_i, caches, ads_i, i = xs

        def one(xc, j, kind, c):
            xc, *out, st = _block_infer(
                xc, take_lp(lps_i, i, j), c["k"], c["v"],
                at[kind][0], *rope[kind], cfg, use_kernel=use_kernel,
                rpos=rpos, kstart=at[kind][1], cache_ks=c.get("ks"),
                cache_vs=c.get("vs"), tp_axis=tp_axis, dp_axis=dp_axis,
                fused=fused, ad_l=take_ad(ads_i, i, j), aslot=aslot,
                ascale=asc, tree_mask=tree_mask,
                window=cfg.window_of(kind), moe_valid=moe_valid,
                experts=experts, layer=i * plen + j)
            return xc, dict(zip(names, out)), st

        if plen == 1:
            xc, new, stats = one(xc, 0, period[0], caches[period[0]])
            new = {period[0]: new}
        else:
            new = {kind: {n: [] for n in names} for kind in kinds}
            stats = None
            for kind, first, count in _runs(period):
                mine = {n: a[slot_of[first]:slot_of[first] + count]
                        for n, a in caches[kind].items()}
                if count == 1:
                    xc, out, st = one(xc, first, kind,
                                      {n: a[0] for n, a in mine.items()})
                    out = {n: a[None] for n, a in out.items()}
                else:
                    def step(carry, xs_, kind=kind):
                        xc, out, st = one(carry[0], xs_[0], kind, xs_[1])
                        return (xc, None if st is None
                                else carry[1] + st), out
                    (xc, st), out = lax.scan(
                        step,
                        (xc, None if cfg.moe is None
                         else _no_stats(scanned)),
                        (first + jnp.arange(count, dtype=jnp.int32), mine))
                for n in names:
                    new[kind][n].append(out[n])
                if st is not None:
                    stats = st if stats is None else stats + st
            new = {kind: {n: (a[0] if len(a) == 1 else jnp.concatenate(a))
                          for n, a in d.items()} for kind, d in new.items()}
        return xc, (new if stats is None else (new, stats))

    def stacked(kind, a):
        return _by_period(a, period.count(kind), plen)

    scanned, experts = _split_experts(params["layers"])
    lps, take_lp = _layer_feed(scanned, plen)
    ads, take_ad = _layer_feed(
        None if adapters is None else tuple(
            adapters[n] for n in ("aq", "bq", "ao", "bo")), plen)
    arrays = {kind: _of_kind(cache, kind)
              for kind in dict.fromkeys(pro + period)}
    lead = {}                     # kind -> the prologue's rows, in order
    for j, kind in enumerate(pro):
        c = {n: a[pro[:j].count(kind)] for n, a in arrays[kind].items()}
        x, *out, _ = _block_infer(
            x, jax.tree.map(lambda a: a[j], params["dense_layers"]),
            c["k"], c["v"], at[kind][0], *rope[kind], cfg,
            use_kernel=use_kernel, rpos=rpos, kstart=at[kind][1],
            cache_ks=c.get("ks"), cache_vs=c.get("vs"),
            window=cfg.window_of(kind))
        lead.setdefault(kind, []).append(dict(zip(names, out)))
    xs = (lps,
          {kind: {n: stacked(kind, a[pro.count(kind):] if pro else a)
                  for n, a in arrays[kind].items()}
           for kind in kinds},
          ads, jnp.arange(cfg.periods, dtype=jnp.int32))
    x, new = lax.scan(body, x, xs)
    stats = None
    if cfg.moe is not None:
        new, stats = new[0], jnp.sum(new[1], axis=0)
    new_cache = {}
    for kind in arrays:
        for n in arrays[kind]:
            rows = [r[n][None] for r in lead.get(kind, [])]
            if kind in new:
                rows.append(new[kind][n].reshape(
                    (-1,) + arrays[kind][n].shape[1:]))
            new_cache[n + KIND_SUFFIX[kind]] = (
                rows[0] if len(rows) == 1 else jnp.concatenate(rows)
            ).reshape(cache[n + KIND_SUFFIX[kind]].shape)
    extra = (stats,) if with_stats else ()
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if logits_at is not None:
        idx = jnp.clip(jnp.asarray(logits_at, jnp.int32).reshape(()),
                       0, x.shape[1] - 1)
        x = lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
    if cfg.tie_embeddings:
        # tied head = the replicated embedding table: logits are already
        # full on every shard, no collective needed
        head = params["embed"].T.astype(x.dtype)
        gather = False
    else:
        head = _w(params, "lm_head", x.dtype)
        gather = tp_axis is not None          # vocab-sharded partials
    if logits_all:
        logits = (x @ head).astype(jnp.float32)
        if gather:
            logits = _tp_allgather(logits, tp_axis, 2)
        return (logits, new_cache) + extra
    logits = (x[:, -1] @ head).astype(jnp.float32)
    if gather:
        logits = _tp_allgather(logits, tp_axis, 1)
    return (logits, new_cache) + extra


def precompute_prompt_cache(params, prefix: jax.Array, cfg: LlamaConfig, *,
                            kv_cache_dtype=None) -> Dict:
    """Prefill a SHARED prompt prefix once and return its KV state for
    reuse across requests (reference capability: pre_key_cache /
    pre_value_cache of block_multihead_attention + the serving stacks'
    system-prompt caching). The returned dict feeds
    ``generate(prompt_cache=...)``, which skips re-prefilling the prefix
    for every request — the standard shared-system-prompt win.

    ``prefix``: (P,) or (1, P) int32 token ids. The prefix KV is stored
    at exactly P positions — the consumer's own cache provides the
    capacity for its prompt + new tokens. ``kv_cache_dtype`` must match
    the consumer's (int8 prefixes feed int8 decode caches)."""
    prefix = jnp.asarray(prefix, jnp.int32)
    if prefix.ndim == 1:
        prefix = prefix[None, :]
    if prefix.shape[0] != 1:
        raise ValueError(
            "precompute_prompt_cache: the shared prefix is one sequence "
            f"(got batch {prefix.shape[0]}); it is broadcast across the "
            "request batch at generate() time")
    P = prefix.shape[1]
    cache = init_cache(cfg, 1, P, kv_dtype=kv_cache_dtype)
    _, cache = _forward_cached(params, prefix, cache, 0, cfg, P)
    return {"cache": cache, "len": P}


def generate(params, prompt: jax.Array, cfg: LlamaConfig, *,
             max_new_tokens: int = 32, max_len: Optional[int] = None,
             temperature: float = 0.0, top_k: int = 0,
             top_p: float = 0.0,
             key: Optional[jax.Array] = None,
             eos_token_id: Optional[int] = None,
             pad_token_id: Optional[int] = None,
             prompt_lengths: Optional[jax.Array] = None,
             use_kernel: Optional[bool] = None,
             kv_cache_dtype=None,
             prompt_cache: Optional[Dict] = None) -> jax.Array:
    """prompt (B, S_prompt) int32 -> (B, S_prompt + max_new_tokens).

    ``kv_cache_dtype="int8"``: int8 KV cache with per-row dequant scales
    (self-calibrating, halves KV HBM; the decode kernel dequants in
    VMEM on TPU).

    greedy when temperature == 0, else temperature (+ optional top-k)
    sampling. Whole decode loop is one jitted scan.

    ``pad_token_id``: ragged batches LEFT-padded with this id — each
    row's rope positions start at its first real token and pad cache
    slots are masked out of attention, so every row decodes exactly as
    it would unpadded (reference: the generation stack's attention_mask
    handling, python/paddle/generation/utils.py). Detection takes the
    leading run of pad ids; pass ``prompt_lengths`` (B,) instead when a
    row's genuine first token may equal the pad id.

    ``prompt_cache``: a :func:`precompute_prompt_cache` result — the
    shared prefix's KV is broadcast into every row's cache and the
    per-request ``prompt`` continues at position P, so the prefix is
    never re-prefilled (reference: pre_key/value_cache serving path).
    The returned array holds ``prompt`` + new tokens (prefix excluded).
    Decoded tokens match a run whose prompt is ``concat(prefix,
    prompt)`` exactly.
    """
    B, S = prompt.shape
    # telemetry anchor (observability.hooks): prefill/decode latency
    # histograms + tokens counters + profiler spans; 0 when disabled.
    # Timings under jax.jit are TRACE times (fired once per compile) —
    # eager serving calls get real per-phase wall time.
    _t_obs = _obs.generate_begin()
    P = 0
    if prompt_cache is not None:
        if pad_token_id is not None or prompt_lengths is not None:
            raise ValueError(
                "generate: prompt_cache cannot be combined with left-"
                "padded ragged prompts (pad_token_id/prompt_lengths) — "
                "the shared prefix assumes aligned positions")
        P = int(prompt_cache["len"])
        pc = prompt_cache["cache"]
        if ("ks" in pc) != (kv_cache_dtype is not None):
            raise ValueError(
                "generate: prompt_cache kv dtype does not match "
                "kv_cache_dtype — an int8 prefix must feed an int8 cache")
    total = P + S + max_new_tokens
    max_len = max_len or total
    assert max_len >= total
    if key is None:
        key = jax.random.key(0)
    cache = init_cache(cfg, B, max_len, kv_dtype=kv_cache_dtype)
    if prompt_cache is not None:
        # broadcast the prefix KV (batch 1) into every request row
        for name, arr in cache.items():
            src = prompt_cache["cache"][name][:, :, :P]
            src = jnp.broadcast_to(
                src, (src.shape[0], B) + src.shape[2:]).astype(arr.dtype)
            cache[name] = lax.dynamic_update_slice_in_dim(
                arr, src, 0, axis=2)

    rpos = kstart = None
    if prompt_lengths is not None:
        # explicit per-row lengths are unambiguous (a genuine first
        # token equal to pad_token_id cannot be mis-detected)
        kstart = (S - jnp.asarray(prompt_lengths, jnp.int32))
        kstart = jnp.clip(kstart, 0, S - 1)
    elif pad_token_id is not None:
        # length of the LEADING pad run per row; an all-pad row clamps
        # to keep one slot real instead of decoding from garbage
        kstart = jnp.argmax(prompt != pad_token_id, axis=1).astype(
            jnp.int32)
        kstart = jnp.where(jnp.any(prompt != pad_token_id, axis=1),
                           kstart, S - 1)
    if kstart is not None:
        rpos = jnp.clip(jnp.arange(S, dtype=jnp.int32)[None, :]
                        - kstart[:, None], 0, None)
        # (_attn_with_cache bypasses the fused decode kernel itself
        # whenever kstart is set — it has no pad-slot mask)

    logits, cache = _forward_cached(params, prompt, cache, P, cfg,
                                    max_len, rpos=rpos, kstart=kstart)
    # prefill uses the jnp path (multi-token); decode steps may use the
    # fused pallas kernel
    _t_obs = _obs.generate_phase("prefill", _t_obs, logits, B * S)

    def sample(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        l = logits / temperature
        if top_p > 0.0:
            # one descending sort serves BOTH filters: rank < top_k and
            # the nucleus rule "exclusive prefix sum < top_p" (which
            # always keeps the argmax; reference: top_p_sampling kernel)
            order = jnp.argsort(-l, axis=-1)
            ls = jnp.take_along_axis(l, order, axis=-1)
            keep_sorted = jnp.ones_like(ls, bool)
            if top_k > 0:
                keep_sorted &= (lax.broadcasted_iota(
                    jnp.int32, ls.shape, 1) < top_k)
            p = jax.nn.softmax(jnp.where(keep_sorted, ls, -1e30),
                               axis=-1)
            keep_sorted &= (jnp.cumsum(p, axis=-1) - p) < top_p
            keep = jnp.zeros_like(keep_sorted).at[
                jnp.arange(l.shape[0])[:, None], order].set(keep_sorted)
            l = jnp.where(keep, l, -1e30)
        elif top_k > 0:
            kth = jnp.sort(l, axis=-1)[:, -top_k][:, None]
            l = jnp.where(l < kth, -1e30, l)
        return jax.random.categorical(k, l, axis=-1).astype(jnp.int32)

    key, k0 = jax.random.split(key)
    first = sample(logits, k0)
    # EOS handling in a static scan: early exit is impossible, so carry a
    # per-sequence finished flag and pin tokens to eos once it fires
    # (matches the reference generation stack's padded outputs —
    # reference: python/paddle/generation/utils.py stopping_criteria).
    eos = eos_token_id
    done0 = (first == eos) if eos is not None else jnp.zeros((B,), bool)

    def step(carry, i):
        cache, tok, kk, done = carry
        kk, ks = jax.random.split(kk)
        drpos = (None if kstart is None
                 else (S + i - kstart)[:, None].astype(jnp.int32))
        logits, cache = _forward_cached(
            params, tok[:, None], cache, P + S + i, cfg, max_len,
            use_kernel=use_kernel, rpos=drpos, kstart=kstart)
        nxt = sample(logits, ks)
        if eos is not None:
            nxt = jnp.where(done, jnp.int32(eos), nxt)
            done = done | (nxt == eos)
        return (cache, nxt, kk, done), nxt

    (_, _, _, _), toks = lax.scan(
        step, (cache, first, key, done0), jnp.arange(max_new_tokens - 1))
    out = jnp.concatenate(
        [prompt, first[:, None], jnp.moveaxis(toks, 0, 1)], axis=1)
    _obs.generate_phase("decode", _t_obs, out, B * max_new_tokens)
    return out


def beam_search(params, prompt: jax.Array, cfg: LlamaConfig, *,
                num_beams: int = 4, max_new_tokens: int = 32,
                max_len: Optional[int] = None,
                eos_token_id: Optional[int] = None,
                length_penalty: float = 1.0,
                pad_token_id: Optional[int] = None,
                prompt_lengths: Optional[jax.Array] = None,
                use_kernel: Optional[bool] = None) -> jax.Array:
    """Beam-search decoding with a reordered KV cache (reference: the
    generation stack's beam_search + gather_tree finalize; here beams
    live as cache rows and every step gathers the winning rows, so no
    backpointer walk is needed). prompt (B, S) -> (B, S+max_new_tokens),
    the best beam per batch row; finished beams emit EOS forever.

    Scoring: sum of token log-probs, finalized with GNMT-style
    ``score / len**length_penalty``. Ragged LEFT-padded batches via
    ``pad_token_id`` / ``prompt_lengths`` — same semantics as
    :func:`generate`.
    """
    B, S = prompt.shape
    K = num_beams
    total = S + max_new_tokens
    max_len = max_len or total
    assert max_len >= total
    eos = eos_token_id
    NEG = jnp.float32(-1e30)

    kstart = rpos = ktile = None
    if prompt_lengths is not None:
        kstart = jnp.clip(S - jnp.asarray(prompt_lengths, jnp.int32),
                          0, S - 1)
    elif pad_token_id is not None:
        kstart = jnp.argmax(prompt != pad_token_id, axis=1).astype(
            jnp.int32)
        kstart = jnp.where(jnp.any(prompt != pad_token_id, axis=1),
                           kstart, S - 1)
    if kstart is not None:
        ktile = jnp.repeat(kstart, K, axis=0)            # (B*K,)
        rpos = jnp.clip(jnp.arange(S, dtype=jnp.int32)[None, :]
                        - ktile[:, None], 0, None)

    cache = init_cache(cfg, B * K, max_len)
    ptile = jnp.repeat(prompt, K, axis=0)                    # (B*K, S)
    logits, cache = _forward_cached(params, ptile, cache, 0, cfg,
                                    max_len, use_kernel=use_kernel,
                                    rpos=rpos, kstart=ktile)
    V = logits.shape[-1]
    logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, V)
    # all K beams are identical after prefill: expand from beam 0 only
    scores, first = lax.top_k(logp[:, 0], K)                 # (B, K)
    first = first.astype(jnp.int32)
    done = (first == eos) if eos is not None else jnp.zeros((B, K), bool)
    gen = jnp.zeros((B, K, max_new_tokens), jnp.int32)
    gen = gen.at[:, :, 0].set(first)

    def step(carry, i):
        cache, gen, scores, done, last = carry
        # `last` holds the tokens generated at step i-1 — they live at
        # cache position S+i-1; their successors land at gen index i
        drpos = (None if ktile is None
                 else (S + i - 1 - ktile)[:, None].astype(jnp.int32))
        logits, cache = _forward_cached(
            params, last.reshape(B * K, 1), cache, S + i - 1, cfg,
            max_len, use_kernel=use_kernel, rpos=drpos, kstart=ktile)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, V)
        if eos is not None:
            # finished beams: only "emit eos at zero cost" survives, so
            # their cumulative score freezes
            frozen = jnp.full((V,), NEG).at[eos].set(0.0)
            logp = jnp.where(done[:, :, None], frozen[None, None, :],
                             logp)
        cand = (scores[:, :, None] + logp).reshape(B, K * V)
        scores2, idx = lax.top_k(cand, K)                    # (B, K)
        beam = idx // V                                      # (B, K)
        tok = (idx % V).astype(jnp.int32)
        gen = jnp.take_along_axis(gen, beam[:, :, None], axis=1)
        gen = lax.dynamic_update_slice_in_dim(gen, tok[:, :, None], i,
                                              axis=2)
        if eos is not None:
            done = jnp.take_along_axis(done, beam, axis=1) | (tok == eos)
        # gather the winning beams' cache rows
        rows = (jnp.arange(B)[:, None] * K + beam).reshape(-1)  # (B*K,)
        cache = {n: v[:, rows] for n, v in cache.items()}
        return (cache, gen, scores2, done, tok), None

    (cache, gen, scores, done, _), _ = lax.scan(
        step, (cache, gen, scores, done, first),
        jnp.arange(1, max_new_tokens))

    # GNMT length normalization: length = tokens up to and incl. eos
    if eos is not None:
        has = jnp.any(gen == eos, axis=-1)
        first_eos = jnp.argmax(gen == eos, axis=-1)
        lengths = jnp.where(has, first_eos + 1, max_new_tokens)
    else:
        lengths = jnp.full((B, K), max_new_tokens)
    final = scores / (lengths.astype(jnp.float32) ** length_penalty)
    best = jnp.argmax(final, axis=1)                         # (B,)
    seq = jnp.take_along_axis(gen, best[:, None, None], axis=1)[:, 0]
    return jnp.concatenate([prompt, seq], axis=1)
