"""Fused-op functional APIs (reference: python/paddle/incubate/nn/functional/
— fused_transformer.py, fused_rms_norm.py, swiglu.py, fused_rotary_position_
embedding.py, fused_bias_act, fused_dropout_add, masked_multihead_attention,
fused_moe; CUDA kernels paddle/phi/kernels/fusion/*).

TPU-native: each is a jnp composition designed so XLA fuses it into one or
few kernels (elementwise chains fold into neighbouring matmuls on the MXU);
on TPU the hot three (fused_rms_norm, swiglu, fused_rotary_position_
embedding) dispatch to the hand-written Pallas kernels in
``ops/pallas/fused.py`` when the call matches the kernels' fully-fused
contract; attention routes to the Pallas flash kernel where applicable.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from ...._core.autograd import apply
from ...._core.tensor import Tensor
from ....ops._registry import as_tensor


def _use_pallas_fused() -> bool:
    """Dispatch to the Pallas fused kernels: on TPU by default (these
    APIs' contract IS the fused kernel); elsewhere only when forced
    (interpret mode is correct but slow — tests use the env).

    ``PADDLE_TPU_FORCE_PALLAS_FUSED=1`` forces the kernels anywhere;
    ``=0`` opts out everywhere (fall back to the XLA-fused jnp
    composition, e.g. after a bench shows it faster on a given shape)."""
    force = os.environ.get("PADDLE_TPU_FORCE_PALLAS_FUSED")
    if force == "1":
        return True
    if force == "0":
        return False
    from ....ops.pallas import flash_attention as _fa
    return _fa.available()


__all__ = [
    "fused_rms_norm", "fused_layer_norm", "swiglu",
    "fused_rotary_position_embedding", "fused_bias_act",
    "fused_dropout_add", "fused_linear", "fused_linear_activation",
    "fused_matmul_bias", "fused_feedforward", "fused_multi_head_attention",
    "fused_bias_dropout_residual_layer_norm", "masked_multihead_attention",
    "fused_moe",
    "softmax_mask_fuse", "softmax_mask_fuse_upper_triangle",
]


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, **_):
    """reference: incubate/nn/functional/fused_rms_norm.py — rms norm with
    optional pre-norm bias/residual add. Returns (out, residual_out) like
    the reference when residual is given, else out."""
    x = as_tensor(x)
    args = [x]
    opt = {}
    for nm, t in (("bias", bias), ("residual", residual),
                  ("w", norm_weight), ("b", norm_bias)):
        if t is not None:
            opt[nm] = len(args)
            args.append(as_tensor(t))
    ax = begin_norm_axis if begin_norm_axis >= 0 else x.ndim + begin_norm_axis
    naxes = tuple(range(ax, x.ndim))

    # fully-fused Pallas path (fused_rms_norm.py's hot shape: norm over the
    # last axis with a weight, no biases)
    if (_use_pallas_fused() and norm_bias is None and bias is None
            and norm_weight is not None and ax == x.ndim - 1):
        from ....ops.pallas import fused as _pf

        if residual is not None:
            def fp(v, res, w):
                return _pf.rms_norm(v, w, float(epsilon), residual=res)
            return apply(fp, x, as_tensor(residual), as_tensor(norm_weight),
                         name="fused_rms_norm", multi_out=True)

        def fp(v, w):
            return _pf.rms_norm(v, w, float(epsilon))
        return apply(fp, x, as_tensor(norm_weight), name="fused_rms_norm")

    def f(v, *rest):
        ct = jnp.float32 if v.dtype in (jnp.bfloat16, jnp.float16) else v.dtype
        vv = v.astype(ct)
        if "bias" in opt:
            vv = vv + rest[opt["bias"] - 1].astype(ct)
        if "residual" in opt:
            vv = vv + rest[opt["residual"] - 1].astype(ct)
        res_out = vv
        var = jnp.mean(jnp.square(vv), axis=naxes, keepdims=True)
        out = vv * jax.lax.rsqrt(var + epsilon)
        if "w" in opt:
            out = out * rest[opt["w"] - 1].astype(ct)
        if "b" in opt:
            out = out + rest[opt["b"] - 1].astype(ct)
        if "residual" in opt:
            return out.astype(v.dtype), res_out.astype(v.dtype)
        return out.astype(v.dtype)

    if residual is not None:
        return apply(f, *args, name="fused_rms_norm", multi_out=True)
    return apply(f, *args, name="fused_rms_norm")


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None, **_):
    """reference: incubate/nn/functional/fused_layer_norm.py."""
    x = as_tensor(x)
    args = [x]
    opt = {}
    for nm, t in (("bias", bias), ("residual", residual),
                  ("w", norm_weight), ("b", norm_bias)):
        if t is not None:
            opt[nm] = len(args)
            args.append(as_tensor(t))
    ax = begin_norm_axis if begin_norm_axis >= 0 else x.ndim + begin_norm_axis
    naxes = tuple(range(ax, x.ndim))

    def f(v, *rest):
        ct = jnp.float32 if v.dtype in (jnp.bfloat16, jnp.float16) else v.dtype
        vv = v.astype(ct)
        if "bias" in opt:
            vv = vv + rest[opt["bias"] - 1].astype(ct)
        if "residual" in opt:
            vv = vv + rest[opt["residual"] - 1].astype(ct)
        res_out = vv
        mean = jnp.mean(vv, axis=naxes, keepdims=True)
        var = jnp.mean(jnp.square(vv - mean), axis=naxes, keepdims=True)
        out = (vv - mean) * jax.lax.rsqrt(var + epsilon)
        if "w" in opt:
            out = out * rest[opt["w"] - 1].astype(ct)
        if "b" in opt:
            out = out + rest[opt["b"] - 1].astype(ct)
        if "residual" in opt:
            return out.astype(v.dtype), res_out.astype(v.dtype)
        return out.astype(v.dtype)

    if residual is not None:
        return apply(f, *args, name="fused_layer_norm", multi_out=True)
    return apply(f, *args, name="fused_layer_norm")


def swiglu(x, y=None, name=None):
    """reference: incubate/nn/functional/swiglu.py — silu(x) * y; if y is
    None, x is split in half along the last dim. On TPU the two-operand
    form runs the one-pass Pallas kernel (fused_bias_act swiglu path)."""
    x = as_tensor(x)
    if y is None:
        if _use_pallas_fused():
            from ....ops.pallas import fused as _pf

            def fsplit(v):
                a, b = jnp.split(v, 2, axis=-1)
                return _pf.swiglu(a, b)
            return apply(fsplit, x, name="swiglu")

        def f(v):
            a, b = jnp.split(v, 2, axis=-1)
            return jax.nn.silu(a.astype(jnp.float32)).astype(v.dtype) * b
        return apply(f, x, name="swiglu")
    y = as_tensor(y)
    if _use_pallas_fused():
        from ....ops.pallas import fused as _pf
        return apply(lambda a, b: _pf.swiglu(a, b), x, y, name="swiglu")
    return apply(
        lambda a, b: jax.nn.silu(a.astype(jnp.float32)).astype(a.dtype) * b,
        x, y, name="swiglu")


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    rotary_emb_base=10000.0, time_major=False):
    """reference: incubate/nn/functional/fused_rotary_position_embedding.py
    (kernel paddle/phi/kernels/fusion/fused_rope_kernel.cu). q/k/v:
    (B, S, H, D). Returns rotated (q, k, v) (None passthrough)."""
    outs = []
    tensors = [t for t in (q, k, v) if t is not None]
    q0 = as_tensor(tensors[0])
    B, S, H, D = q0.shape
    if sin is None or cos is None:
        inv = 1.0 / (rotary_emb_base **
                     (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
        t = jnp.arange(S, dtype=jnp.float32)
        freqs = jnp.outer(t, inv)
        cos_t, sin_t = jnp.cos(freqs), jnp.sin(freqs)
    else:
        cos_t = as_tensor(cos)._value.reshape(S, -1)[:, :D // 2]
        sin_t = as_tensor(sin)._value.reshape(S, -1)[:, :D // 2]
    if position_ids is not None:
        pid = as_tensor(position_ids)._value  # (B, S)
        cos_t = jnp.take(cos_t, pid, axis=0)  # (B, S, D/2)
        sin_t = jnp.take(sin_t, pid, axis=0)
        expand = lambda c: c[:, :, None, :]
    else:
        expand = lambda c: c[None, :, None, :]

    # fully-fused Pallas path (fused_rope_kernel.cu's hot shape: neox
    # style, shared tables, q+k in one launch)
    if (_use_pallas_fused() and use_neox_rotary_style
            and position_ids is None and q is not None and k is not None
            and v is None):
        from ....ops.pallas import fused as _pf

        def frope(qv, kv):
            return _pf.rope_qk(qv, kv, cos_t, sin_t)   # (S, D/2) tables
        rq, rk = apply(frope, as_tensor(q), as_tensor(k),
                       name="fused_rope", multi_out=True)
        return rq, rk, None

    def rot(t):
        def f(x):
            c = expand(cos_t).astype(jnp.float32)
            s = expand(sin_t).astype(jnp.float32)
            xf = x.astype(jnp.float32)
            if use_neox_rotary_style:
                x1, x2 = jnp.split(xf, 2, axis=-1)
                out = jnp.concatenate(
                    [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
            else:  # GPT-J interleaved pairs
                x1 = xf[..., 0::2]
                x2 = xf[..., 1::2]
                o1 = x1 * c - x2 * s
                o2 = x2 * c + x1 * s
                out = jnp.stack([o1, o2], axis=-1).reshape(xf.shape)
            return out.astype(x.dtype)
        return apply(f, as_tensor(t), name="fused_rope")

    result = tuple(rot(t) if t is not None else None for t in (q, k, v))
    return result


_ACTS = {
    "gelu": lambda x: jax.nn.gelu(x.astype(jnp.float32)).astype(x.dtype),
    "relu": jax.nn.relu,
    "silu": lambda x: jax.nn.silu(x.astype(jnp.float32)).astype(x.dtype),
    "swiglu": None,  # handled specially
    "geglu": None,
}


def fused_bias_act(x, bias=None, act_method="gelu", **_):
    """reference: incubate/nn/functional/fused_bias_act (kernel
    fused_bias_act_kernel.cu): out = act(x + bias), with swiglu/geglu
    splitting the last dim."""
    x = as_tensor(x)
    args = [x]
    if bias is not None:
        args.append(as_tensor(bias))

    def f(v, *rest):
        if rest:
            v = v + rest[0]
        if act_method in ("swiglu", "geglu"):
            a, b = jnp.split(v, 2, axis=-1)
            g = (jax.nn.silu if act_method == "swiglu" else jax.nn.gelu)(
                a.astype(jnp.float32)).astype(v.dtype)
            return g * b
        return _ACTS[act_method](v)
    return apply(f, *args, name="fused_bias_act")


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """reference: incubate/nn/functional/fused_dropout_add.py —
    dropout(x) + y in one pass."""
    from ....nn.functional.common import dropout
    d = dropout(x, p=p, training=training, mode=mode)
    return d + as_tensor(y)


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """reference: incubate/nn/functional/blha etc. fused_matmul_bias —
    cublasLt epilogue fusion; XLA does the same fusion natively."""
    x, y = as_tensor(x), as_tensor(y)
    args = [x, y]
    if bias is not None:
        args.append(as_tensor(bias))

    def f(a, b, *rest):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2)
        out = a @ b
        if rest:
            out = out + rest[0]
        return out
    return apply(f, *args, name="fused_matmul_bias")


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    return fused_matmul_bias(x, weight, bias, transpose_y=transpose_weight)


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation="gelu"):
    out = fused_matmul_bias(x, y, bias, transpose_x=trans_x,
                            transpose_y=trans_y)
    if activation in (None, "none"):
        return out
    return apply(_ACTS[activation], out, name=f"fused_linear_{activation}")


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode='upscale_in_train',
                      name=None):
    """reference: incubate/nn/functional/fused_transformer.py
    fused_feedforward (kernel fused_feedforward_kernel.cu):
    residual + dropout(linear2(dropout(act(linear1(ln(x)))))) with pre/post
    layernorm."""
    from ....nn.functional.common import dropout
    from ....nn.functional.norm import layer_norm
    x = as_tensor(x)
    residual = x
    d = x.shape[-1]
    if pre_layer_norm:
        x = layer_norm(x, d, ln1_scale, ln1_bias, ln1_epsilon)
    h = fused_matmul_bias(x, linear1_weight, linear1_bias)
    h = apply(_ACTS.get(activation, jax.nn.relu), h, name=activation)
    h = dropout(h, p=dropout1_rate, training=training, mode=mode)
    h = fused_matmul_bias(h, linear2_weight, linear2_bias)
    h = dropout(h, p=dropout2_rate, training=training, mode=mode)
    out = residual + h
    if not pre_layer_norm:
        out = layer_norm(out, d, ln2_scale, ln2_bias, ln2_epsilon)
    return out


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode='upscale_in_train',
                               ring_id=-1, add_residual=True, num_heads=None,
                               name=None):
    """reference: fused_transformer.py fused_multi_head_attention (kernel
    fused_attention_kernel.cu). qkv_weight: (3, H, D_head, D_in) as in the
    reference layout."""
    from ....nn.functional.common import dropout
    from ....nn.functional.norm import layer_norm
    from ....nn.functional.attention import scaled_dot_product_attention
    x = as_tensor(x)
    residual = x
    B, S, D = x.shape
    if pre_layer_norm:
        x = layer_norm(x, D, pre_ln_scale, pre_ln_bias, pre_ln_epsilon)
    qkvw = as_tensor(qkv_weight)
    three, H, Dh, Din = qkvw.shape
    qkv = fused_matmul_bias(
        x, qkvw.reshape([3 * H * Dh, Din]), qkv_bias, transpose_y=True)
    qkv = qkv.reshape([B, S, 3, H, Dh])

    def split3(t):
        return (apply(lambda v: v[:, :, 0], t, name="slice_q"),
                apply(lambda v: v[:, :, 1], t, name="slice_k"),
                apply(lambda v: v[:, :, 2], t, name="slice_v"))
    q, k, v = split3(qkv)
    o = scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=attn_dropout_rate
        if training else 0.0, is_causal=False)
    o = o.reshape([B, S, H * Dh])
    out = fused_matmul_bias(o, linear_weight, linear_bias)
    out = dropout(out, p=dropout_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = layer_norm(out, D, ln_scale, ln_bias, ln_epsilon)
    return out


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, ln_epsilon=1e-5,
                                           training=True,
                                           mode='upscale_in_train',
                                           name=None):
    """reference: incubate/nn/functional/fused_transformer.py."""
    from ....nn.functional.common import dropout
    from ....nn.functional.norm import layer_norm
    x = as_tensor(x)
    if bias is not None:
        x = x + as_tensor(bias)
    x = dropout(x, p=dropout_rate, training=training, mode=mode)
    out = x + as_tensor(residual)
    return layer_norm(out, out.shape[-1], ln_scale, ln_bias, ln_epsilon)


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               sequence_lengths=None, rotary_tensor=None,
                               out_shift=None, seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False, **_):
    """Decode-time single-token attention against a KV cache
    (reference: incubate/nn/functional/masked_multihead_attention.py,
    kernel masked_multihead_attention_kernel.cu).

    x: (B, 3*H*D) fused qkv for ONE step; cache_kv: (2, B, H, max_seq, D).
    Returns (out (B, H*D), updated cache_kv) following the reference.
    """
    x = as_tensor(x)
    cache = as_tensor(cache_kv)
    args = [x, cache]
    if bias is not None:
        args.append(as_tensor(bias))
    if sequence_lengths is not None:
        args.append(as_tensor(sequence_lengths))

    two, B, H, MS, D = cache.shape

    def f(xv, cachev, *rest):
        i = 0
        if bias is not None:
            xv = xv + rest[i]; i += 1
        if sequence_lengths is not None:
            cur = rest[i].reshape(-1)  # (B,) current lengths
        else:
            cur = None
        qkv = xv.reshape(B, 3, H, D)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]   # (B, H, D)
        if cur is None:
            # without explicit lengths, append at position 0 of empty cache
            step = jnp.zeros((B,), jnp.int32)
        else:
            step = cur.astype(jnp.int32)
        bidx = jnp.arange(B)
        ck = cachev[0].at[bidx, :, step].set(k)
        cv = cachev[1].at[bidx, :, step].set(v)
        # attention over cached positions <= step
        s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) / math.sqrt(D)
        pos = jnp.arange(MS)[None, None, :]
        s = jnp.where(pos <= step[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhs,bhsd->bhd", p.astype(cv.dtype), cv)
        return o.reshape(B, H * D), jnp.stack([ck, cv])

    return apply(f, *args, name="masked_multihead_attention", multi_out=True)


def fused_moe(x, gate_weight, ffn1_weight, ffn2_weight, ffn1_bias=None,
              ffn2_bias=None, quant_method="None", moe_topk=2,
              norm_topk_prob=True, **_):
    """reference: incubate/nn/functional/fused_moe.py — top-k routed expert
    FFN. ffn1_weight: (E, H, 2*I) swiglu-packed; ffn2: (E, I, H)."""
    from ....models.moe import MoEConfig, moe_ffn
    x = as_tensor(x)
    gw = as_tensor(gate_weight)
    w1 = as_tensor(ffn1_weight)
    w2 = as_tensor(ffn2_weight)
    E = gw.shape[-1]
    cfg = MoEConfig(num_experts=E, top_k=moe_topk, capacity_factor=4.0)

    def f(xv, gv, w1v, w2v):
        half = w1v.shape[-1] // 2
        params = {"w_gate": gv, "wg": w1v[..., :half],
                  "wu": w1v[..., half:], "wd": w2v}
        squeeze = xv.ndim == 2
        if squeeze:
            xv = xv[None]
        out, _ = moe_ffn(xv, params, cfg)
        return out[0] if squeeze else out
    return apply(f, x, gw, w1, w2, name="fused_moe")


def softmax_mask_fuse(x, mask, name=None):
    """Fused additive-mask softmax (reference:
    paddle/phi/kernels/fusion/gpu/fused_softmax_mask_kernel.cu;
    incubate/nn/functional/fused_softmax_mask.py). x (B, H, S, S) scores,
    mask (B, 1, S, S) additive (-inf style); softmax computed in fp32 —
    XLA fuses the add into the softmax."""
    def fn(xv, mv):
        s32 = xv.astype(jnp.float32) + mv.astype(jnp.float32)
        return jax.nn.softmax(s32, axis=-1).astype(xv.dtype)
    return apply(fn, as_tensor(x), as_tensor(mask),
                 name="softmax_mask_fuse")


def softmax_mask_fuse_upper_triangle(x, name=None):
    """Causal (upper-triangle-masked) softmax (reference:
    fused_softmax_mask_upper_triangle_kernel.cu)."""
    def fn(xv):
        S = xv.shape[-1]
        causal = jnp.tril(jnp.ones((S, S), bool))
        s32 = jnp.where(causal, xv.astype(jnp.float32),
                        jnp.finfo(jnp.float32).min)
        return jax.nn.softmax(s32, axis=-1).astype(xv.dtype)
    return apply(fn, as_tensor(x), name="softmax_mask_fuse_upper_triangle")


# --------------------------------------------------------------------------
# Serving-stack fused ops
# --------------------------------------------------------------------------
def _norm(x, scale, bias, eps, norm_type="layernorm"):
    xv = x.astype(jnp.float32)
    if norm_type == "rmsnorm":
        out = xv * jax.lax.rsqrt(
            jnp.mean(xv * xv, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xv, axis=-1, keepdims=True)
        var = jnp.var(xv, axis=-1, keepdims=True)
        out = (xv - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        out = out * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, pre_layer_norm=True,
        epsilon=1e-5, residual_alpha=1.0, cache_kvs=None, beam_offset=None,
        pre_caches=None, seq_lens=None, rotary_embs=None, time_step=None,
        attn_mask=None, dropout_rate=0.0, rotary_emb_dims=0,
        activation="gelu", training=False, mode="upscale_in_train",
        trans_qkvw=True, ring_id=-1, norm_type="layernorm",
        use_neox_rotary_style=False, gqa_group_size=-1, name=None):
    """reference: incubate/nn/functional/fused_transformer.py:976
    fused_multi_transformer / fused_multi_transformer_kernel.cu — the
    whole serving transformer stack in one call, with static KV caches.

    TPU-native: one jnp composition per layer; XLA fuses the LN/bias/act
    chains into the matmuls. ``cache_kvs[i]``: [2, B, nh, max_seq, hd].
    ``time_step`` (int/scalar) = decode position; None = context encode.
    Returns (out, cache_kvs) when caches are given, else out.
    """
    xv = as_tensor(x)._value
    B, S, E = xv.shape
    L = len(qkv_weights)

    def raw(t):
        return None if t is None else as_tensor(t)._value

    def pick(seq, i):
        if seq is None:
            return None
        v = seq[i] if i < len(seq) else None
        return None if v is None else as_tensor(v)._value

    # exact (erf) gelu — the reference kernel's GeluFunctor, not the
    # tanh approximation
    exact_gelu = lambda t: jax.nn.gelu(t, approximate=False)
    act = {"gelu": exact_gelu, "relu": jax.nn.relu,
           "swiglu": None}.get(activation, exact_gelu)
    step = None if time_step is None else int(
        np.asarray(raw(time_step)).reshape(-1)[0]) if not isinstance(
        time_step, int) else time_step
    new_caches = []
    h = xv
    for i in range(L):
        qkvw = raw(qkv_weights[i])
        residual = h
        z = _norm(h, pick(ln_scales, i), pick(ln_biases, i), epsilon,
                  norm_type) if pre_layer_norm else h
        if qkvw.ndim != 4:
            raise ValueError(
                "fused_multi_transformer: qkv_weights must be 4-D — "
                "[3, nh, hd, E] with trans_qkvw=True (default) or "
                "[E, 3, nh, hd] with trans_qkvw=False (a 2-D [E, 3E] "
                "weight cannot encode the head split)")
        if trans_qkvw:           # [3, nh, hd, E]
            three, nh, hd, _ = qkvw.shape
            qkv = z @ qkvw.reshape(3 * nh * hd, E).T.astype(z.dtype)
        else:                    # [E, 3, nh, hd]
            _, three, nh, hd = qkvw.shape
            qkv = z @ qkvw.reshape(E, 3 * nh * hd).astype(z.dtype)
        b = pick(qkv_biases, i)
        if b is not None:
            qkv = qkv + b.reshape(-1).astype(qkv.dtype)
        qkv = qkv.reshape(B, S, 3, nh, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if rotary_embs is not None and rotary_emb_dims > 0:
            rot = raw(rotary_embs)      # [2, B, 1, max_seq, hd]
            pos0 = 0 if step is None else step
            cos = jax.lax.dynamic_slice_in_dim(rot[0], pos0, S, axis=2)
            sin = jax.lax.dynamic_slice_in_dim(rot[1], pos0, S, axis=2)
            cos = jnp.moveaxis(cos, 2, 1)   # [B, S, 1, hd]
            sin = jnp.moveaxis(sin, 2, 1)

            def rope(t):
                if use_neox_rotary_style:
                    h1, h2 = jnp.split(t, 2, axis=-1)
                    rot = jnp.concatenate([-h2, h1], axis=-1)
                else:            # interleaved (GPT-J) pairs — the default
                    te, to = t[..., 0::2], t[..., 1::2]
                    rot = jnp.stack([-to, te], axis=-1).reshape(t.shape)
                return t * cos.astype(t.dtype) + rot * sin.astype(t.dtype)
            q, k = rope(q), rope(k)
        if cache_kvs is not None:
            cache = raw(cache_kvs[i])    # [2, B, nh, max_seq, hd]
            kt = jnp.moveaxis(k, 1, 2)   # [B, nh, S, hd]
            vt = jnp.moveaxis(v, 1, 2)
            pos = 0 if step is None else step
            ck = jax.lax.dynamic_update_slice_in_dim(cache[0], kt.astype(
                cache.dtype), pos, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache[1], vt.astype(
                cache.dtype), pos, axis=2)
            new_caches.append(Tensor(jnp.stack([ck, cv]), _internal=True))
            kk, vv = ck, cv
            logits = jnp.einsum("bqhd,bhkd->bhqk",
                                q.astype(jnp.float32),
                                kk.astype(jnp.float32)) / math.sqrt(hd)
            kpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3)
            qpos = pos + jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                  2)
            logits = jnp.where(kpos <= qpos, logits, -1e30)
            if attn_mask is not None:
                # same contract as the no-cache branch: bool keeps, float
                # adds; broadcast over [B, 1|nh, Sq, cache_len]
                m = raw(attn_mask)
                mw = m[..., :logits.shape[-1]]
                if m.dtype == jnp.bool_:
                    logits = jnp.where(mw, logits, -1e30)
                else:
                    logits = logits + mw.astype(jnp.float32)
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bqhd", p.astype(vv.dtype), vv)
        else:
            logits = jnp.einsum("bqhd,bkhd->bhqk",
                                q.astype(jnp.float32),
                                k.astype(jnp.float32)) / math.sqrt(hd)
            if attn_mask is not None:
                m = raw(attn_mask)
                if m.dtype == jnp.bool_:
                    logits = jnp.where(m, logits, -1e30)
                else:
                    logits = logits + m.astype(jnp.float32)
            else:
                kpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3)
                qpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
                logits = jnp.where(kpos <= qpos, logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        o = o.reshape(B, S, nh * hd)
        lw = raw(linear_weights[i])
        o = o @ lw.astype(o.dtype)
        lb = pick(linear_biases, i)
        if lb is not None:
            o = o + lb.astype(o.dtype)
        h = residual * residual_alpha + o
        if not pre_layer_norm:
            h = _norm(h, pick(ln_scales, i), pick(ln_biases, i), epsilon,
                      norm_type)
        # ffn
        residual = h
        z = _norm(h, pick(ffn_ln_scales, i), pick(ffn_ln_biases, i),
                  epsilon, norm_type) if pre_layer_norm else h
        f1 = z @ raw(ffn1_weights[i]).astype(z.dtype)
        f1b = pick(ffn1_biases, i)
        if f1b is not None:
            f1 = f1 + f1b.astype(f1.dtype)
        if activation == "swiglu":
            g, u = jnp.split(f1, 2, axis=-1)
            f1 = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
        else:
            f1 = act(f1.astype(jnp.float32)).astype(f1.dtype)
        f2 = f1 @ raw(ffn2_weights[i]).astype(f1.dtype)
        f2b = pick(ffn2_biases, i)
        if f2b is not None:
            f2 = f2 + f2b.astype(f2.dtype)
        h = residual * residual_alpha + f2
        if not pre_layer_norm:
            h = _norm(h, pick(ffn_ln_scales, i), pick(ffn_ln_biases, i),
                      epsilon, norm_type)
    out = Tensor(h, _internal=True)
    return (out, new_caches) if cache_kvs is not None else out


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size=None,
                     name=None):
    """reference: incubate/nn/functional/blha_get_max_len.py — max
    encoder/decoder lengths for block attention planning."""
    enc = as_tensor(seq_lens_encoder)._value
    dec = as_tensor(seq_lens_decoder)._value
    return (Tensor(jnp.max(enc).reshape(1), _internal=True),
            Tensor(jnp.max(dec).reshape(1), _internal=True))


def block_multihead_attention(
        qkv, key_cache, value_cache, seq_lens_encoder, seq_lens_decoder,
        seq_lens_this_time, padding_offsets=None, cum_offsets=None,
        cu_seqlens_q=None, cu_seqlens_k=None, block_tables=None,
        pre_key_cache=None, pre_value_cache=None,
        cache_k_quant_scales=None, cache_v_quant_scales=None,
        cache_k_dequant_scales=None, cache_v_dequant_scales=None,
        rope_emb=None, mask=None,
        tgt_mask=None, max_seq_len=-1, block_size=64, use_neox_style=False,
        qkv_bias=None, out_shift=None, out_smooth=None,
        max_enc_len_this_time=None, max_dec_len_this_time=None,
        use_dynamic_cachekv_quant=False, **_):
    """reference: incubate/nn/functional/block_multihead_attention.py /
    block_multi_head_attention_kernel.cu — PAGED-kv-cache attention: each
    sequence's cache lives in `block_size`-row pages addressed through
    ``block_tables`` (vLLM-style), mixing prefill rows and decode rows in
    one varlen token batch.

    TPU-native correctness path (jnp; the Pallas decode kernel covers the
    contiguous-cache hot loop): per-row gather of the page list ->
    contiguous K/V -> masked attention. Shapes:
      qkv            [total_tokens, 3*nh*hd]
      key/value_cache[num_blocks, nh, block_size, hd]
      block_tables   [B, max_blocks_per_seq] (-1 padded)
    Returns (out [total_tokens, nh*hd], qkv, key_cache, value_cache).

    **int8 KV cache** (reference: cache_k/v_quant_scales +
    use_dynamic_cachekv_quant — the cachekv-int8 serving tier): when
    quant scales are given the caches hold int8; writes quantize new
    rows with the per-head (static, [nh]) or per-sequence-per-head
    (dynamic, [B, nh]) quant scales, reads dequantize with the
    dequant scales (default 1/quant). Halves KV HBM, the long-context
    decode bandwidth win.
    """
    if (pre_key_cache is None) != (pre_value_cache is None):
        raise ValueError(
            "block_multihead_attention: pre_key_cache and "
            "pre_value_cache must be passed together")
    # pre caches (reference: block_multihead_attention.py:45,86 —
    # [B, num_head, pre_len, head_dim]): prefix-tuning-style virtual
    # tokens PREPENDED to every sequence's attention context. They are
    # fully visible to all queries, never occupy the paged cache, and do
    # not shift real token positions (rope indices stay 0-based).
    pre_k = (as_tensor(pre_key_cache)._value
             if pre_key_cache is not None else None)
    pre_v = (as_tensor(pre_value_cache)._value
             if pre_value_cache is not None else None)
    qv = as_tensor(qkv)._value
    kc = as_tensor(key_cache)._value
    vc = as_tensor(value_cache)._value
    enc = np.asarray(as_tensor(seq_lens_encoder)._value)
    dec = np.asarray(as_tensor(seq_lens_decoder)._value)
    this = np.asarray(as_tensor(seq_lens_this_time)._value)
    bt = np.asarray(as_tensor(block_tables)._value)
    if qkv_bias is not None:
        qv = qv + as_tensor(qkv_bias)._value.reshape(-1)
    nh, bs, hd = kc.shape[1], kc.shape[2], kc.shape[3]
    B = bt.shape[0]
    total = qv.shape[0]
    q3 = qv.reshape(total, 3, nh, hd)

    kq = (as_tensor(cache_k_quant_scales)._value
          if cache_k_quant_scales is not None else None)
    vq = (as_tensor(cache_v_quant_scales)._value
          if cache_v_quant_scales is not None else None)
    kdq = (as_tensor(cache_k_dequant_scales)._value
           if cache_k_dequant_scales is not None else
           (1.0 / kq if kq is not None else None))
    vdq = (as_tensor(cache_v_dequant_scales)._value
           if cache_v_dequant_scales is not None else
           (1.0 / vq if vq is not None else None))
    if (kq is None) != (vq is None):
        raise ValueError(
            "block_multihead_attention: cache_k_quant_scales and "
            "cache_v_quant_scales must be passed together (got only "
            f"{'k' if kq is not None else 'v'} scales) — an int8 cache "
            "quantizes both K and V")
    cache_quant = kq is not None
    if cache_quant:
        want = 2 if use_dynamic_cachekv_quant else 1
        for nm, s in (("cache_k_quant_scales", kq),
                      ("cache_v_quant_scales", vq)):
            if jnp.ndim(s) != want:
                raise ValueError(
                    f"block_multihead_attention: {nm} must be "
                    f"{'[B, num_head]' if want == 2 else '[num_head]'} "
                    f"for use_dynamic_cachekv_quant="
                    f"{use_dynamic_cachekv_quant}, got ndim "
                    f"{jnp.ndim(s)}")

    def _sc(scales, b, shape):
        """Per-head scale broadcast: static [nh] or dynamic [B, nh]."""
        s = scales[b] if (use_dynamic_cachekv_quant and
                          jnp.ndim(scales) == 2) else scales
        return jnp.asarray(s, jnp.float32).reshape(shape)

    def _quant_rows(x, scales, b):
        # x: (t, nh, hd) new rows -> int8
        s = _sc(scales, b, (1, nh, 1))
        return jnp.clip(jnp.round(x.astype(jnp.float32) * s),
                        -127, 127).astype(jnp.int8)

    def _dequant_ctx(x, scales, b):
        # x: (nh, kl, hd) gathered cache -> fp32
        s = _sc(scales, b, (nh, 1, 1))
        return x.astype(jnp.float32) * s

    # pure-decode batches (one new token per sequence, no prefill rows)
    # take the Pallas paged-attention kernel: the block-table gather rides
    # the kernel's scalar-prefetch index map instead of materializing a
    # contiguous copy per sequence
    from ....ops.pallas import fused as _pf
    if (rope_emb is None and mask is None and total == B
            and int(enc.max(initial=0)) == 0 and np.all(this == 1)
            and pre_k is None
            and _pf.available()):   # True on TPU or under set_interpret
        q1 = q3[:, 0]                       # (B, nh, hd)
        pos = dec.astype(np.int64)
        pages = jnp.asarray(bt[np.arange(B), pos // bs].astype(np.int32))
        rows = jnp.asarray((pos % bs).astype(np.int32))
        if cache_quant:
            # int8 pages stay int8 in HBM; the kernel dequants in VMEM.
            # ONE vectorized quantize per cache — this is the decode hot
            # path, not a place for a per-sequence python loop
            def _qbatch(x, scales):   # x: (B, nh, hd)
                s = jnp.asarray(scales, jnp.float32)
                s = s[:, :, None] if use_dynamic_cachekv_quant \
                    else s.reshape(1, nh, 1)
                return jnp.clip(jnp.round(x.astype(jnp.float32) * s),
                                -127, 127).astype(jnp.int8)
            kc = kc.at[pages, :, rows].set(_qbatch(q3[:, 1], kq))
            vc = vc.at[pages, :, rows].set(_qbatch(q3[:, 2], vq))
        else:
            kc = kc.at[pages, :, rows].set(q3[:, 1].astype(kc.dtype))
            vc = vc.at[pages, :, rows].set(q3[:, 2].astype(vc.dtype))
        # kernel page layout: (P, HK, page, D) == this cache layout
        out = _pf.paged_decode_attention(
            q1, kc, vc, jnp.asarray(bt), jnp.asarray(
                (dec + 1).astype(np.int32)),
            k_dequant_scale=kdq if cache_quant else None,
            v_dequant_scale=vdq if cache_quant else None)
        return (Tensor(out.reshape(B, nh * hd), _internal=True),
                Tensor(qv, _internal=True), Tensor(kc, _internal=True),
                Tensor(vc, _internal=True))

    outs = []
    tok = 0
    for b in range(B):
        t = int(this[b])
        if t == 0:
            continue
        q = q3[tok:tok + t, 0]
        k_new = q3[tok:tok + t, 1]
        v_new = q3[tok:tok + t, 2]
        start = int(dec[b])          # existing cache length (decode rows)
        if int(enc[b]) > 0:
            start = 0                # prefill writes from position 0
        if rope_emb is not None:
            rot = as_tensor(rope_emb)._value   # [2, 1|B, 1, max_seq, hd]
            rb = rot[:, b] if rot.shape[1] > 1 else rot[:, 0]
            cos = rb[0, 0, start:start + t][:, None, :]
            sin = rb[1, 0, start:start + t][:, None, :]

            def rope_t(tn):
                if use_neox_style:
                    h1, h2 = jnp.split(tn, 2, axis=-1)
                    r = jnp.concatenate([-h2, h1], axis=-1)
                else:
                    te, to = tn[..., 0::2], tn[..., 1::2]
                    r = jnp.stack([-to, te], axis=-1).reshape(tn.shape)
                return tn * cos.astype(tn.dtype) + r * sin.astype(tn.dtype)
            q, k_new = rope_t(q), rope_t(k_new)
        # ONE vectorized page scatter for this row's tokens
        pos = start + np.arange(t)
        pages = jnp.asarray(bt[b, pos // bs].astype(np.int32))
        rows = jnp.asarray((pos % bs).astype(np.int32))
        if cache_quant:
            kc = kc.at[pages, :, rows].set(_quant_rows(k_new, kq, b))
            vc = vc.at[pages, :, rows].set(_quant_rows(v_new, vq, b))
        else:
            kc = kc.at[pages, :, rows].set(k_new.astype(kc.dtype))
            vc = vc.at[pages, :, rows].set(v_new.astype(vc.dtype))
        kl = start + t
        npages = (kl + bs - 1) // bs
        pages = [int(bt[b, p]) for p in range(npages)]
        ks = jnp.concatenate([kc[p] for p in pages], axis=1)[:, :kl]
        vs = jnp.concatenate([vc[p] for p in pages], axis=1)[:, :kl]
        if cache_quant:
            ks = _dequant_ctx(ks, kdq, b)
            vs = _dequant_ctx(vs, vdq, b).astype(qv.dtype)
        plen = 0
        if pre_k is not None:
            # prepend the prefix context: columns [0, plen) are virtual
            # tokens visible to every query; cache columns shift right
            plen = pre_k.shape[2]
            ks = jnp.concatenate([pre_k[b].astype(ks.dtype), ks], axis=1)
            vs = jnp.concatenate([pre_v[b].astype(vs.dtype), vs], axis=1)
        logits = jnp.einsum("qhd,hkd->hqk", q.astype(jnp.float32),
                            ks.astype(jnp.float32)) / math.sqrt(hd)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        kpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        logits = jnp.where((kpos < plen) | (kpos - plen <= qpos),
                           logits, -1e30)
        if mask is not None:
            mv = as_tensor(mask)._value    # [B, 1, Smax, Smax]-broadcast
            mb = mv[b if mv.shape[0] > 1 else 0]
            mb = mb[..., start:start + t, :kl].astype(jnp.float32)
            if plen:
                # the user mask addresses real cache positions; prefix
                # columns are additively transparent
                mb = jnp.concatenate(
                    [jnp.zeros(mb.shape[:-1] + (plen,), jnp.float32), mb],
                    axis=-1)
            logits = logits + mb
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("hqk,hkd->qhd", p.astype(vs.dtype), vs)
        outs.append(o.reshape(t, nh * hd))
        tok += t
    out = jnp.concatenate(outs, axis=0) if outs else \
        jnp.zeros((0, nh * hd), qv.dtype)
    return (Tensor(out, _internal=True), Tensor(qv, _internal=True),
            Tensor(kc, _internal=True), Tensor(vc, _internal=True))


def fused_dot_product_attention(q, k, v, attn_mask=None, scaling_factor=None,
                                dropout_p=0.0, is_causal=False,
                                training=False, name=None, **_):
    """reference: incubate/nn/functional/fused_dot_product_attention.py —
    cuDNN fused SDPA; here the flash/sdpa path (Pallas on TPU)."""
    from ....nn.functional.attention import scaled_dot_product_attention
    return scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training)


def variable_length_memory_efficient_attention(
        query, key, value, seq_lens, kv_seq_lens, mask=None, scale=None,
        causal=False, pre_cache_length=0, name=None):
    """reference: incubate/nn/functional/
    variable_length_memory_efficient_attention.py — varlen attention with
    per-sequence lengths. q/k/v: [B, nh, S, hd]; seq_lens [B, 1]."""
    qv = as_tensor(query)._value
    kv = as_tensor(key)._value
    vv = as_tensor(value)._value
    ql = as_tensor(seq_lens)._value.reshape(-1)
    kl = as_tensor(kv_seq_lens)._value.reshape(-1)
    hd = qv.shape[-1]
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qv.astype(jnp.float32),
                        kv.astype(jnp.float32)) * sc
    qpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    kpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3)
    valid = (qpos < ql[:, None, None, None]) & \
        (kpos < kl[:, None, None, None])
    if causal:
        valid = valid & (kpos <= qpos)
    if mask is not None:
        m = as_tensor(mask)._value
        logits = logits + m.astype(jnp.float32)
    logits = jnp.where(valid, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(valid, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv)
    return Tensor(out, _internal=True)


def fused_gate_attention(query, key=None, query_weight=None,
                         key_weight=None, value_weight=None, qkv_weight=None,
                         gate_linear_weight=None, gate_linear_bias=None,
                         out_linear_weight=None, out_linear_bias=None,
                         nonbatched_bias=None, attn_mask=None,
                         has_gating=True, merge_qkv=True,
                         use_flash_attn=False, name=None):
    """reference: incubate/nn/functional fused_gate_attention
    (AlphaFold-style gated attention, fused_gate_attention_kernel).
    query: [B, M, S, E]; qkv_weight: [3, nh, hd, E] when merge_qkv."""
    qv = as_tensor(query)._value

    def raw(t):
        return None if t is None else as_tensor(t)._value
    if merge_qkv:
        w = raw(qkv_weight)          # [3, nh, hd, E]
        three, nh, hd, E = w.shape
        qkv = jnp.einsum("bmse,cnde->bmscnd", qv, w)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
    else:
        kv = as_tensor(key)._value
        qw, kw, vw = raw(query_weight), raw(key_weight), raw(value_weight)
        # per-projection weights: [E, nh, hd]
        q = jnp.einsum("bmse,end->bmsnd", qv, qw)
        k = jnp.einsum("bmse,end->bmsnd", kv, kw)
        v = jnp.einsum("bmse,end->bmsnd", kv, vw)
    logits = jnp.einsum("bmsnd,bmtnd->bmnst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(q.shape[-1])
    if nonbatched_bias is not None:
        logits = logits + raw(nonbatched_bias).astype(jnp.float32)[:, None]
    if attn_mask is not None:
        m = raw(attn_mask)
        logits = logits + (1.0 - m.astype(jnp.float32)) * -1e9
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bmnst,bmtnd->bmsnd", p.astype(v.dtype), v)
    if has_gating and gate_linear_weight is not None:
        gw = raw(gate_linear_weight)      # [E, nh, hd]
        g = jnp.einsum("bmse,end->bmsnd", qv, gw)
        if gate_linear_bias is not None:
            g = g + raw(gate_linear_bias).astype(g.dtype)
        o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(o.dtype)
    ow = raw(out_linear_weight)           # [nh, hd, E]
    out = jnp.einsum("bmsnd,nde->bmse", o, ow)
    if out_linear_bias is not None:
        out = out + raw(out_linear_bias).astype(out.dtype)
    return Tensor(out, _internal=True)


import numpy as np  # noqa: E402 — used by fused_multi_transformer

__all__ += ["fused_multi_transformer", "block_multihead_attention",
            "blha_get_max_len", "fused_dot_product_attention",
            "variable_length_memory_efficient_attention",
            "fused_gate_attention"]
