"""Context parallelism: ring attention + Ulysses (DeepSpeed-style) alltoall.

The reference snapshot has NO ring attention / Ulysses (SURVEY §2.3 CP row:
"Not present"); its long-context story is SEP + Megatron-SP +
FlashAttention. CP is nonetheless first-class here (SURVEY §7 hard part 8):
long sequences shard along a "cp"/"sep" mesh axis and attention runs as a
ring of `ppermute` steps over ICI, overlapping compute with neighbor
transfers, or as Ulysses head↔seq `all_to_all` swaps.

Both functions are *collective* ops: they must be called inside
``shard_map`` (or an equivalent SPMD region) with the sequence dimension
sharded over ``axis_name``. Layout: (B, S_local, H, D).

Numerics: blockwise online softmax in fp32 with a custom VJP whose backward
re-runs the ring (kv + traveling dk/dv buffers), so peak memory stays
O(S_local) — the point of ring attention (Liu et al. 2023).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30


def _chunk_scores(q, k, scale, causal, qi, kj, s_loc):
    """q (B,H,S,D) x k (B,H,S,D) -> masked fp32 scores (B,H,S,S).

    qi/kj: ring positions of the q and kv chunks along the cp axis (traced
    ints); global token index = chunk_pos * s_loc + local index.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * s_loc + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        kpos = kj * s_loc + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(qpos >= kpos, s, _NEG)
    return s


def _rep_heads(t, rep):
    """Local GQA head repeat (B,Hk,S,D) -> (B,Hk*rep,S,D). Lives INSIDE
    the ring body so the traveling kv buffers stay unrepeated — ICI
    moves h/hk× less data per step."""
    return t if rep == 1 else jnp.repeat(t, rep, axis=1)


def _ring_fwd_scan(q, k, v, axis_name, causal, scale):
    """Returns (out fp32 (B,H,S,D), lse (B,H,S)). k/v may carry fewer
    (GQA) heads than q."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    rep = H // k.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]  # kv travels to next rank

    def body(carry, step):
        acc, m, l, kc, vc = carry
        src = (me - step) % n          # ring position of current kv chunk
        s = _chunk_scores(q, _rep_heads(kc, rep), scale, causal, me, src,
                          S)
        mj = jnp.max(s, axis=-1)                     # (B,H,S)
        m_new = jnp.maximum(m, mj)
        # fully-masked rows keep m=_NEG; guard exp of (-inf - -inf)
        safe_m = jnp.where(m_new <= _NEG, 0.0, m_new)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(s <= _NEG, 0.0, p)
        alpha = jnp.where(m <= _NEG, 0.0, jnp.exp(m - safe_m))
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p,
            _rep_heads(vc, rep).astype(jnp.float32),
            preferred_element_type=jnp.float32)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (acc_new, m_new, l_new, kc, vc), None

    init = (jnp.zeros((B, H, S, D), jnp.float32),
            jnp.full((B, H, S), _NEG, jnp.float32),
            jnp.zeros((B, H, S), jnp.float32), k, v)
    (acc, m, l, _, _), _ = lax.scan(body, init, jnp.arange(n))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]
    lse = jnp.where(l == 0.0, _NEG, m + jnp.log(l_safe))
    return out, lse


def _flash_ring_ok(q) -> bool:
    """Static gate: use the Pallas flash kernel for the per-chunk
    attention inside the ring (the einsum path materializes a fp32
    (B,H,S,S) score block per ring step — the flash partials never do)."""
    from ....ops.pallas import flash_attention as fa
    return fa.flash_eligible(q.shape[2], q.shape[3])


def _ring_fwd_flash(q, k, v, axis_name, causal, scale):
    """Flash-partial ring: step 0 runs the SELF chunk (statically causal
    when ``causal``), later steps run full-attention partials whose lse
    is knocked to -1e30 on ranks where the chunk is future context; the
    online log-sum-exp merge combines normalized partials exactly.
    Returns (out fp32, lse) — same contract as :func:`_ring_fwd_scan`,
    so the einsum backward (which only consumes q,k,v,out,lse) is
    untouched."""
    from ....ops.pallas import flash_attention as fa
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    rep = H // k.shape[1]
    qf = q.reshape(B * H, S, D)

    def chunk(kc, vc, is_causal):
        # fp32 partials: rounding each chunk's output to bf16 before the
        # cross-chunk merge would compound error ~n times vs the einsum
        # ring's end-to-end fp32 accumulation. GQA kv stays UNREPEATED —
        # the kernel's kv index map divides by rep (no HBM duplication)
        Hk = kc.shape[1]
        o, l = fa._fwd(qf, kc.reshape(B * Hk, S, D),
                       vc.reshape(B * Hk, S, D),
                       scale, is_causal, 512, 1024,
                       out_dtype=jnp.float32, kv_rep=rep)
        return o.reshape(B, H, S, D), l.reshape(B, H, S)

    perm = [(i, (i + 1) % n) for i in range(n)]
    acc, m = chunk(k, v, causal)            # self chunk: never all-masked
    ssum = jnp.ones_like(m)
    # prologue rotate; the scan body computes on the CARRIED chunk and
    # permutes at the tail, so the next chunk's ICI transfer overlaps the
    # current chunk's kernel (same schedule as the einsum ring)
    kc = lax.ppermute(k, axis_name, perm)
    vc = lax.ppermute(v, axis_name, perm)

    def body(carry, step):
        acc, m, ssum, kc, vc = carry
        src = (me - step) % n               # ring position of this chunk
        oj, lj = chunk(kc, vc, False)
        if causal:
            lj = jnp.where(src < me, lj, _NEG)   # future chunks: no mass
        m2 = jnp.maximum(m, lj)
        a = jnp.exp(m - m2)                 # m is finite from step 0 on
        bw = jnp.exp(lj - m2)               # exp(-1e30 - m2) == 0
        acc = acc * a[..., None] + oj * bw[..., None]
        ssum = ssum * a + bw
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (acc, m2, ssum, kc, vc), None

    (acc, m, ssum, _, _), _ = lax.scan(
        body, (acc, m, ssum, kc, vc), jnp.arange(1, n))
    return acc / ssum[..., None], m + jnp.log(ssum)


def _ring_fwd(q, k, v, axis_name, causal, scale):
    if _flash_ring_ok(q):
        return _ring_fwd_flash(q, k, v, axis_name, causal, scale)
    return _ring_fwd_scan(q, k, v, axis_name, causal, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attn_bhsd(q, k, v, axis_name, causal, scale):
    out, _ = _ring_fwd(q, k, v, axis_name, causal, scale)
    return out.astype(q.dtype)


def _ring_attn_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_fwd(q, k, v, axis_name, causal, scale)
    out = out.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_attn_bwd(axis_name, causal, scale, res, do):
    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    Hk = k.shape[1]
    rep = H // Hk
    perm = [(i, (i + 1) % n) for i in range(n)]
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # (B,H,S)

    def gqa_sum(g):  # (B,H,S,D) grads -> (B,Hk,S,D) traveling layout
        return g if rep == 1 else g.reshape(B, Hk, rep, S, D).sum(2)

    def body(carry, step):
        dq, kc, vc, dkc, dvc = carry
        src = (me - step) % n
        kr = _rep_heads(kc, rep)
        s = _chunk_scores(q, kr, scale, causal, me, src, S)
        safe_lse = jnp.where(lse <= _NEG, 0.0, lse)
        p = jnp.exp(s - safe_lse[..., None])
        p = jnp.where(s <= _NEG, 0.0, p)
        dvc = dvc + gqa_sum(jnp.einsum(
            "bhqk,bhqd->bhkd", p, do32,
            preferred_element_type=jnp.float32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", do32,
                        _rep_heads(vc, rep).astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kr.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        dkc = dkc + gqa_sum(jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q.astype(jnp.float32),
            preferred_element_type=jnp.float32))
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        dkc = lax.ppermute(dkc, axis_name, perm)
        dvc = lax.ppermute(dvc, axis_name, perm)
        return (dq, kc, vc, dkc, dvc), None

    init = (jnp.zeros((B, H, S, D), jnp.float32), k, v,
            jnp.zeros((B, Hk, S, D), jnp.float32),
            jnp.zeros((B, Hk, S, D), jnp.float32))
    (dq, _, _, dk, dv), _ = lax.scan(body, init, jnp.arange(n))
    # after n ppermute hops the traveling dk/dv buffers are home again
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attn_bhsd.defvjp(_ring_attn_fwd, _ring_attn_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None):
    """Ring attention over sequence-sharded q/k/v (B, S_local, H, D).

    Call inside ``shard_map`` with seq sharded over ``axis_name``. GQA:
    the UNREPEATED kv heads travel the ring (h/hk× less ICI traffic);
    the per-chunk compute repeats them locally.
    """
    b, s, h, d = q.shape
    hk = k.shape[2]
    assert h % hk == 0
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _ring_attn_bhsd(qt, kt, vt, axis_name, causal, sc)
    return out.transpose(0, 2, 1, 3)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      scale: Optional[float] = None, attn_fn=None):
    """Ulysses/DeepSpeed sequence parallelism: all_to_all swaps the sharded
    dim from seq to heads, runs FULL-sequence attention locally (any
    attn_fn, e.g. the Pallas flash kernel), and swaps back.

    Requires num_heads % cp == 0. q/k/v: (B, S_local, H, D) inside
    shard_map.
    """
    n = lax.psum(1, axis_name)
    b, s, h, d = q.shape
    if h % n != 0:
        raise ValueError(
            f"ulysses_attention: num_heads ({h}) must be divisible by the "
            f"context-parallel degree ({n}) — the all_to_all splits the "
            f"head dim across cp ranks")
    hk = k.shape[2]
    if hk != h:
        assert h % hk == 0
        # GQA: repeat kv only enough for the head dim to split over n
        # ranks — the local attention maps q-head groups to kv heads, so
        # the all_to_all moves up to h/hk× less kv than a full repeat.
        # Custom attn_fn gets the full repeat (its GQA support is
        # unknown; the default _attention and the flash wrapper repeat
        # residual groups themselves).
        need = n // math.gcd(hk, n)
        hk2 = hk * need
        if attn_fn is None and hk2 <= h and h % hk2 == 0:
            rep = need
        else:
            rep = h // hk
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

    def seq2head(t):  # (B, S/n, H, D) -> (B, S, H/n, D)
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(t):  # (B, S, H/n, D) -> (B, S/n, H, D)
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)
    if attn_fn is None:
        from ....models.llama import _attention
        og = _attention(qg, kg, vg, causal=causal)
    else:
        og = attn_fn(qg, kg, vg, causal=causal)
    return head2seq(og)
