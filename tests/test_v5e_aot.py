"""Compile the repo's programs for the real chip with no chip present.

The installed libtpu describes a v5e 2x2 topology under
``JAX_PLATFORMS=cpu``, and ``jit(f).lower(<avals sharded on those
devices>).compile()`` runs the real XLA:TPU and Mosaic compilers, so a
kernel that overflows scoped VMEM or a Mosaic call left to GSPMD fails
HERE and not on the first chip run. The CPU meshes cannot show either:
there the flash kernel is not eligible and every test takes the jnp path.
It proves compilation only; a cell of ``python3 -m chipbench.run`` on the
chip proves the run.
"""
import json
import math
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.models import llama, train, train_pp
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused, paged_attention

# a 664M Llama-shaped width, and a narrow twin with the same 128-wide
# heads for the tier-1 train steps
FLAGSHIP = dict(vocab_size=32000, hidden_size=1536, intermediate_size=4096,
                num_heads=12, num_kv_heads=12, max_seq_len=4096)
NARROW = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
              num_heads=2, num_kv_heads=2, max_seq_len=512)


@pytest.fixture(scope="module")
def v5e():
    # libtpu takes a machine-wide lock; another session compiling at the
    # same time must not fail this one
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    devs = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices
    assert len(devs) == 4 and devs[0].device_kind == "TPU v5 lite"
    return devs


def _compile(fn, *avals):
    """Lower and compile ``fn`` (jitted here unless it already is) for the
    avals' TPU devices, and see that the kernel is in the lowering."""
    with fa.force_compiled_lowering():
        lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*avals)
        compiled = lowered.compile()
    assert "tpu_custom_call" in lowered.as_text()
    return compiled


def _on(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(dev))


# ernie45-0.3b.train-4k's call, 16 query heads on 2 KV heads: K and V of a
# head stay whole in VMEM (forward, dq), q and do beside them and the dk/dv
# accumulators (dkv), and Mosaic refuses here what does not fit. At 16k a
# head is over the budget: majors of 8,192 rows under clamped index maps
@pytest.mark.parametrize("b,s,h", [(4, 4096, 16), (1, 16384, 4)])
def test_flash_fwd_bwd_flagship_shape(v5e, b, s, h):
    q = _on(v5e[0], (b, s, h, 128), jnp.bfloat16)
    kv = _on(v5e[0], (b, s, 2, 128), jnp.bfloat16)
    assert fa._tiling(s, 1024, 512, 256)[0] == min(s, 8192)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


# B, H, HK, page, ppseq, pages: MHA at three page sizes; the benchmark's
# serving cells (internlm2-1.8b); ERNIE-4.5-0.3B's 8:1 GQA; one shard of
# the cells' model under tp=4
PAGED_SHAPES = {
    "mha-p16": (8, 12, 12, 16, 128, 257),
    "mha-p8": (8, 12, 12, 8, 256, 257),
    "mha-p32": (8, 12, 12, 32, 64, 257),
    "cell": (32, 16, 8, 64, 64, 801),
    "ernie": (32, 16, 2, 64, 64, 801),
    "tp4-shard": (32, 4, 2, 64, 64, 801),
}


# in_place: the kernel reads the pool as it is stored. It sees a page as
# page*HK rows of D; that reshape moves no byte where XLA tiles the HK rows
# of a position as whole packed words (8 bf16 or int8 heads, 2 bf16 heads),
# and is a relayout where it pads them (12 heads, 2 int8 heads)
@pytest.mark.parametrize("shape,kv,in_place", [
    ("mha-p16", "bf16", False), ("mha-p16", "int8", False),
    ("mha-p8", "bf16", False), ("mha-p32", "int8", False),
    ("cell", "bf16", True), ("cell", "int8", True),
    ("ernie", "bf16", True), ("ernie", "int8", False),
    ("tp4-shard", "bf16", True), ("tp4-shard", "int8", False)])
def test_paged_decode_kernel(v5e, shape, kv, in_place):
    B, H, HK, page, ppseq, pages = PAGED_SHAPES[shape]
    D = 128
    d = v5e[0]
    pool = _on(d, (pages, page, HK, D),
               jnp.int8 if kv == "int8" else jnp.bfloat16)
    scales = _on(d, (pages, page, HK), jnp.float32)
    args = [_on(d, (B, H, D), jnp.bfloat16), pool, pool,
            _on(d, (B, ppseq), jnp.int32), _on(d, (B,), jnp.int32)]

    def f(q, k, v, bt, lens, *sc):
        ks, vs = sc if sc else (None, None)
        # use_kernel=None: the dispatcher itself must pick the kernel
        return paged_attention.paged_attention(
            q, k, v, bt, lens, ks_pages=ks, vs_pages=vs)
    if kv == "int8":
        args += [scales, scales]
    compiled = _compile(f, *args)
    if in_place:
        # no transposed, gathered or re-tiled copy of a pool in the temp
        pool_bytes = pages * page * HK * D * pool.dtype.itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2


# Mellum2-12B-A2.5B's published widths (chipbench/configs): 32 query and 4
# kv heads of 128, pages of 64, a window of 1,024 in the sliding layers'
# pool of 673 pages, the full layers' of 5,121; the pools of ALL layers of
# a kind as one run of pages, as the decode step hands them over
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("window,layers,pages", [(1024, 9, 673),
                                                 (None, 3, 5121)])
def test_paged_decode_kernel_mellum_widths(v5e, kv, window, layers, pages):
    B, H, HK, D, page, ppseq = 32, 32, 4, 128, 64, 160
    d = v5e[0]
    pool = _on(d, (layers * pages, page, HK, D),
               jnp.int8 if kv == "int8" else jnp.bfloat16)
    args = [_on(d, (B, H, D), jnp.bfloat16), pool, pool,
            _on(d, (B, ppseq), jnp.int32), _on(d, (B,), jnp.int32)]
    if kv == "int8":
        args += [_on(d, (layers * pages, page, HK), jnp.float32)] * 2

    def f(q, k, v, bt, lens, *sc):
        ks, vs = sc if sc else (None, None)
        return paged_attention.paged_attention(
            q, k, v, bt + 2 * pages, lens, ks_pages=ks, vs_pages=vs,
            window=window)
    compiled = _compile(f, *args)
    if kv == "bf16":
        # the kernel reads the whole pool in place: no copy of it
        pool_bytes = layers * pages * page * HK * D * 2
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


# The dense serving cells' WHOLE decode step (chipbench: InternLM2-1.8B at
# its published widths, 24 layers, 801 pages of 64, batch 32, 64 pages a
# row), pools donated as the engine donates them. The pools ride in the
# layer scan's carry and are written where they lie; a program that slices
# a layer's pool out of the stack and stacks it back (the scan's xs/ys,
# until PR 29) holds a second copy of both pools in its temp. Temp beside
# the pools' bytes as compiled here: bf16 322,560 B / 5.04 GB (5.46 GB
# before); int8, which no benchmark cell times, 999,936 B / 2.60 GB (2.82 GB
# before; 0.79 GB with the scale pools carried as (pages, page, heads),
# which the kernel's wrapper lays out anew in every layer call).
def _internlm2(num_layers=24):
    return llama.LlamaConfig(
        vocab_size=92544, hidden_size=2048, intermediate_size=8192,
        num_layers=num_layers, num_heads=16, num_kv_heads=8, head_dim=128,
        max_seq_len=4096, rope_theta=1e6, rms_eps=1e-5, dtype=jnp.bfloat16,
        tie_embeddings=False)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_dense_decode_step_writes_pools_in_place(v5e, kv):
    from paddle_tpu.models import generate as gen
    cfg = _internlm2()
    d = v5e[0]
    on = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    params = on(jax.eval_shape(lambda k: llama.init_params(k, cfg),
                               jax.random.key(0)))
    pools = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, 801, 64, kv_dtype="int8" if kv == "int8" else None)))
    B, pps = 32, 64

    def decode(params, last, paged, tables, lengths, active):
        logits, paged = gen.paged_decode_forward(
            params, last, paged, tables, lengths, cfg, active=active,
            use_kernel=True)
        return jnp.argmax(logits, -1), paged
    compiled = _compile(
        jax.jit(decode, donate_argnums=(2,)), params, _on(d, (B,), jnp.int32),
        pools, _on(d, (B, pps), jnp.int32), _on(d, (B,), jnp.int32),
        _on(d, (B,), jnp.bool_))
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pools))
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


# The serving programs read the attention projections as the parameter
# tree stores them (PR 35). A projection whose result is split into heads
# goes through ``generate._project_heads``; spelled ``(x @ w).reshape(...,
# heads, d)`` XLA:TPU folds the split into the dot, takes the head axis for
# a convolution's spatial dimension and wants the weight with its
# contraction dimension minor: the dense programs then cut ``wq`` / ``wk`` /
# ``wv`` of the layer out of the stack (three root-level ``dynamic-slice``
# fusions) and copied each transposed, once a layer a program call, and at
# Mellum's widths the transposes were hoisted out of the loop and the WHOLE
# stacks re-laid every call (228 MB of temp at 12 layers). Four layers (one
# of Mellum's periods) show either; the widths are the cells'. The chunk
# program's context is 256 tokens, so that the keys and values it gathers
# for its four layers stay smaller than a layer's ``wk``.
@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("widths", ["internlm2", "mellum"])
def test_serving_programs_read_attention_weights_as_stored(v5e, widths,
                                                           program):
    from paddle_tpu.models import generate as gen
    B, page, pps, i32 = 32, 64, 64, jnp.int32
    d = v5e[0]
    on = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    window = widths == "mellum"
    if window:
        from chipbench.archs import mellum as arch
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "chipbench", "configs",
                               "mellum2-12b-a2.5b.json")) as f:
            c = dict(json.load(f), num_hidden_layers=4)
        cfg = arch.program_config(c, pps * page)
        params = on(jax.eval_shape(lambda k: arch.weights(k, c),
                                   jax.random.key(0)))
        pools = on(jax.eval_shape(lambda: gen.init_paged_cache(
            cfg, 801, page, window_pages=1 + B * 21)))
    else:
        cfg = _internlm2(4)
        params = on(jax.eval_shape(lambda k: llama.init_params(k, cfg),
                                   jax.random.key(0)))
        pools = on(jax.eval_shape(lambda: gen.init_paged_cache(
            cfg, 801, page)))
    # a windowed config also takes its rows' pages in the sliding pool
    # and hands back the expert layer's counters
    if program == "decode":
        def step(params, last, paged, lengths, active, table, wt):
            kw = {"window_tables": wt, "with_stats": True} if window else {}
            out = gen.paged_decode_forward(
                params, last, paged, table, lengths, cfg, active=active,
                use_kernel=True, **kw)
            return (jnp.argmax(out[0], -1),) + tuple(out[1:])
        avals = (_on(d, (B,), i32), pools, _on(d, (B,), i32),
                 _on(d, (B,), jnp.bool_))
    else:
        def step(params, toks, paged, ctx_len, chunk_len, table, wt):
            kw = ({"window_table": wt[0], "with_stats": True} if window
                  else {})
            return gen.paged_prefill_chunk(
                params, toks, paged, table[0], cfg, ctx_cap=256,
                ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=True, **kw)
        avals = (_on(d, (1, 256), i32), pools, _on(d, (), i32),
                 _on(d, (), i32))
    avals += (_on(d, (B, pps), i32),) * 2
    with fa.force_compiled_lowering():
        compiled = jax.jit(step, donate_argnums=(2,)).lower(
            params, *avals).compile()
    text = compiled.as_text()
    layers = params["layers"]
    least = cfg.hidden_size * cfg.num_kv_heads * cfg.hd
    assert least == math.prod(layers["wk"].shape[1:])
    moved = [
        f"%{name} = {dt}[{dims}]" for name, dt, dims in re.findall(
            r"%((?:copy|\w*dynamic-slice_fusion)[\w.\-]*) = (\w+)"
            r"\[([\d,]+)\]\S* (?:copy|fusion)\(", text)
        if dt == "bf16" and math.prod(map(int, dims.split(","))) >= least]
    assert not moved, moved
    if program == "decode":
        assert "paged_attention" in text
        if widths == "mellum":
            wq_stack = math.prod(layers["wq"].shape) * 2
            assert compiled.memory_analysis().temp_size_in_bytes \
                < wq_stack // 10
    else:
        # the chunk's attention is the page-walking kernel, and the two
        # patterns by which the benchmark finds the DECODE kernel in a
        # trace (any Pallas call whose first two operands are int32; a name
        # that starts with paged_attention) do not take it for one
        calls = [ln.strip() for ln in text.splitlines()
                 if ln.strip().startswith("%kv_chunk_attention")]
        assert calls
        for cell in ("chat", "longctx"):
            with open(os.path.join(
                    os.path.dirname(os.path.dirname(os.path.abspath(
                        __file__))), "chipbench", "metrics",
                    f"kernel.paged_attention_roofline.{cell}.json")) as f:
                pattern = json.load(f)["op_pattern"]
            assert not [ln for ln in calls if re.search(pattern, ln)]


@pytest.mark.parametrize("items", [32 * 8, 256 * 8])
def test_grouped_expert_matmul_mellum_widths(v5e, items):
    """The serving expert layer at a decode step's and a chunk's item
    counts: three grouped matmuls over the stacks of all twelve layers as
    they are stored, none of which may be copied."""
    from paddle_tpu.models import generate as gen
    L, E, H, I = 12, 64, 2304, 896
    d = v5e[0]
    dt = jnp.bfloat16
    stacks = (_on(d, (L, E, H, I), dt), _on(d, (L, E, H, I), dt),
              _on(d, (L, E, I, H), dt))

    def f(x, le, layer, wg, wu, wd):
        return gen._expert_apply(
            x, jnp.arange(items, dtype=jnp.int32) // 8, le,
            (wg, wu, wd), layer, use_kernel=True)
    with fa.force_compiled_lowering():
        compiled = jax.jit(f).lower(
            _on(d, (items // 8, H), jnp.bfloat16), _on(d, (items,), jnp.int32),
            _on(d, (), jnp.int32), *stacks).compile()
    assert compiled.as_text().count("grouped_expert_matmul") >= 3
    one_layer = E * H * I * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer // 4


# NVIDIA-Nemotron-3-Super-120B-A12B's published widths at the cut of
# chipbench/configs/nemotron3-super-120b-a12b.json: one period of 11 layers
# (5 Mamba-2, 5 expert, 1 attention), 128 of the router's 512 experts held,
# a quarter of the vocabulary; batch 128, 24,577 pages of 64, 192 pages a
# row. Weights 9.32 GB, the recurrent-state pool 2.72 GB, the KV pool
# 1.61 GB. As compiled here the decode program's temp is 0.02 GB (the pools
# are written where they lie) and the largest chunk program's (context
# 12,288, width 256) 0.56 GB: 14.21 GB of the chip's 15.75.
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_hybrid_serving_programs_fit_one_chip(v5e, program):
    from paddle_tpu.models import generate as gen, hybrid
    from paddle_tpu.models.moe import MoEConfig
    cfg = llama.LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=2688,
        num_layers=11, num_heads=32, num_kv_heads=2, head_dim=128,
        max_seq_len=12288, rms_eps=1e-5, dtype=jnp.bfloat16,
        tie_embeddings=False, moe=MoEConfig(num_experts=512, top_k=22),
        layer_pattern=("mamba2", "experts") * 3 + (
            "mamba2", "attention", "experts", "mamba2", "experts"),
        hybrid=llama.HybridConfig(
            ssm_heads=128, ssm_head_dim=64, ssm_groups=8, ssm_state=128,
            conv_kernel=4, chunk_size=128, latent_size=1024,
            expert_size=2688, shared_size=5376, routed_scale=5.0))
    d = v5e[0]
    on = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    params = on(jax.eval_shape(
        lambda k: hybrid.init_params(k, cfg, experts_held=128),
        jax.random.key(0)))
    B, page, pps = 128, 64, 192
    pools = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, 24577, page, state_slots=B)))
    i32 = jnp.int32
    if program == "decode":
        def step(params, last, paged, tables, lengths, active):
            logits, paged, stats = gen.paged_decode_forward(
                params, last, paged, tables, lengths, cfg, active=active,
                use_kernel=True, with_stats=True)
            return jnp.argmax(logits, -1), paged, stats
        avals = (_on(d, (B,), i32), pools, _on(d, (B, pps), i32),
                 _on(d, (B,), i32), _on(d, (B,), jnp.bool_))
    else:
        def step(params, toks, paged, table, ctx_len, chunk_len, slot):
            return gen.paged_prefill_chunk(
                params, toks, paged, table, cfg, ctx_cap=pps * page,
                ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=True,
                with_stats=True, state_slot=slot)
        avals = (_on(d, (1, 256), i32), pools, _on(d, (pps,), i32),
                 _on(d, (), i32), _on(d, (), i32), _on(d, (), i32))
    compiled = _compile(jax.jit(step, donate_argnums=(2,)), params, *avals)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert held < 15.75e9
    text = compiled.as_text()
    assert text.count("grouped_expert_matmul") >= 10
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pools))
    if program == "decode":
        # the state and KV pools ride through the layers whole, in place
        assert text.count("ssm_state_update") >= 5
        assert m.temp_size_in_bytes < pool_bytes // 8


# Kimi-K2-Instruct's published widths at the cut of chipbench/configs/
# kimi-k2-instruct.json: the leading dense layer and six expert layers, 12 of
# the router's 384 experts held, an eighth of the vocabulary; batch 32, 7,169
# pages of 64, 224 pages a row. Weights 9.73 GB, the pool of latents 4.11 GB
# (576 numbers a token a layer laid out in 640 lanes). As compiled here the
# decode program's temp is under 0.01 GB (the pool is written where it lies)
# and the largest chunk program's (context 14,336, width 256) 0.02 GB: its
# attention is the chunk kernel over the row's pages, with no float32 score
# block of 8 heads (0.10 GB until PR 37) and no gathered copy of the context.
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_latent_serving_programs_fit_one_chip(v5e, program):
    from paddle_tpu.models import generate as gen, latent
    from paddle_tpu.models.moe import MoEConfig
    cfg = llama.LlamaConfig(
        vocab_size=20480, hidden_size=7168, intermediate_size=18432,
        num_layers=7, num_heads=64, num_kv_heads=64, max_seq_len=14336,
        rope_theta=50000.0, rms_eps=1e-6, dtype=jnp.bfloat16,
        tie_embeddings=False,
        yarn=llama.YarnRope(32, 4096, 1, 1, attention_factor=1.0),
        moe=MoEConfig(num_experts=384, top_k=8, score="sigmoid",
                      routed_scale=2.827, expert_size=2048, shared_size=2048),
        layer_pattern=("latent",), dense_layers=1,
        latent=llama.LatentConfig(q_rank=1536, kv_rank=512, nope_dim=128,
                                  rope_dim=64, v_dim=128, mscale=1.34657))
    d = v5e[0]
    on = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    params = on(jax.eval_shape(
        lambda k: latent.init_params(k, cfg, experts_held=12),
        jax.random.key(0)))
    B, page, pps = 32, 64, 224
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(cfg, 7169, page)))
    assert pool["c"].shape == (7, 7169, 64, 640)
    i32 = jnp.int32
    if program == "decode":
        def step(params, last, paged, tables, lengths, active):
            logits, paged, stats = gen.paged_decode_forward(
                params, last, paged, tables, lengths, cfg, active=active,
                use_kernel=True, with_stats=True)
            return jnp.argmax(logits, -1), paged, stats
        avals = (_on(d, (B,), i32), pool, _on(d, (B, pps), i32),
                 _on(d, (B,), i32), _on(d, (B,), jnp.bool_))
    else:
        def step(params, toks, paged, table, ctx_len, chunk_len):
            return gen.paged_prefill_chunk(
                params, toks, paged, table, cfg, ctx_cap=pps * page,
                ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=True,
                with_stats=True)
        avals = (_on(d, (1, 256), i32), pool, _on(d, (pps,), i32),
                 _on(d, (), i32), _on(d, (), i32))
    compiled = _compile(jax.jit(step, donate_argnums=(2,)), params, *avals)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert held < 15.75e9
    text = compiled.as_text()
    assert text.count("grouped_expert_matmul") >= 3
    pool_bytes = pool["c"].size * 2
    if program == "decode":
        # the prologue's layer and the scanned one: the pool rides through
        # both whole, written in place
        assert text.count("paged_latent_attention") >= 2
        assert m.temp_size_in_bytes < pool_bytes // 8
    else:
        # the prologue's layer and the scanned one; no score of a block of
        # heads over 256 queries in HBM
        assert len(re.findall(r"%latent_chunk_attention[\w.\-]* = ",
                              text)) >= 2
        assert not re.search(r"f32\[([2-9]|\d\d+),256,\d+\]", text)
        assert m.temp_size_in_bytes < 0.10e9


# Trinity-Large-Preview's published widths at the cut of chipbench/configs/
# trinity-large-preview.json: layers 5-9 (the last dense layer, sliding, then
# sliding, full, sliding, sliding expert layers), 32 of the router's 256
# experts held, an eighth of the vocabulary; batch 32, a full pool of 12,289
# pages of 64 (every row can reach 24,576) and a sliding pool of 32 x 69 + 1.
# Weights 8.65 GB + pools 5.54 GB resident. As compiled here the decode
# program's temp is under 0.01 GB and the largest chunk program the cell
# reaches (context 16,384, width 256) 0.013 GB: its attention is the K/V
# chunk kernel over the row's pages, with no gathered temp cache and no
# float32 scores of a key head's group over 256 queries in HBM (0.66 GB
# until then, 1.21 GB with all 48 heads at once).
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_gated_window_serving_programs_fit_one_chip(v5e, program):
    from chipbench.archs import afmoe as arch
    from paddle_tpu.models import generate as gen
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "trinity-large-preview.json")) as f:
        c = json.load(f)
    cfg = arch.program_config(c, 24576)
    d = v5e[0]
    on = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    params = on(jax.eval_shape(lambda k: arch.weights(k, c),
                               jax.random.key(0)))
    B, page, pps = 32, 64, 384
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, 12289, page, window_pages=1 + B * 69)))
    assert pool["k"].shape == (1, 12289, 64, 8, 128)
    assert pool["k_w"].shape == (4, 2209, 64, 8, 128)
    i32 = jnp.int32
    if program == "decode":
        def step(params, last, paged, tables, lengths, active, wt):
            logits, paged, stats = gen.paged_decode_forward(
                params, last, paged, tables, lengths, cfg, active=active,
                use_kernel=True, window_tables=wt, with_stats=True)
            return jnp.argmax(logits, -1), paged, stats
        avals = (_on(d, (B,), i32), pool, _on(d, (B, pps), i32),
                 _on(d, (B,), i32), _on(d, (B,), jnp.bool_),
                 _on(d, (B, pps), i32))
    else:
        def step(params, toks, paged, table, ctx_len, chunk_len, wt):
            return gen.paged_prefill_chunk(
                params, toks, paged, table, cfg, ctx_cap=256 * page,
                ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=True,
                window_table=wt, with_stats=True)
        avals = (_on(d, (1, 256), i32), pool, _on(d, (pps,), i32),
                 _on(d, (), i32), _on(d, (), i32), _on(d, (pps,), i32))
    compiled = _compile(jax.jit(step, donate_argnums=(2,)), params, *avals)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert held < 15.75e9
    text = compiled.as_text()
    assert text.count("grouped_expert_matmul") >= 3
    if program == "decode":
        # the prologue's sliding layer, the scanned sliding runs and the
        # full layer: the pools ride through them whole, written in place
        assert len(re.findall(r"%paged_attention[\w.\-]* = ", text)) >= 3
        assert m.temp_size_in_bytes < 0.01e9
    else:
        # the prologue's sliding layer and the scanned runs, sliding and
        # full; no float32 score of 48 heads over 256 queries in HBM
        assert len(re.findall(r"%kv_chunk_attention[\w.\-]* = ",
                              text)) >= 2
        assert not re.search(r"f32\[(\d+,)?48,256,\d+\]", text)
        assert m.temp_size_in_bytes < 0.05e9


# AI21-Jamba2-3B whole, as chipbench/configs/ai21-jamba2-3b.json has it: 26
# Mamba-1 and 2 attention layers of one key/value head, a dense MLP each;
# batch 256, 49,153 pages of 64 (every row can reach 12,288), 192 pages a
# row. Weights 6.06 GB, the recurrent-state pool 2.39 GB (2.18 of state and
# 0.20 of convolution tails), the KV pool 3.22 GB: 11.67 GB resident. As
# compiled here the decode program's temp is 0.014 GB and the widest chunk
# program's (context 12,288, width 256) 0.30 GB, nearly all of it the
# float32 scores of 20 heads over 256 queries and 12,544 keys (the hybrid
# family's chunk attends over a gathered context in jnp); 11.97 GB of the
# chip's 15.75. The chunk program's temp was 2.17 GB while the chunk's keys
# went into the two layers' pages by one scatter (generate._scatter_rows: a
# copy of each pool in and out), and the tails' pool was re-laid in and out
# of both programs (and on the chip twice again in every Mamba-1 layer)
# while a row's convolution tail was laid (3, 5120). Laid flat and written
# by jnp slices in the decode step, the tails' pool was carried column-major
# (copied in and out of the program: the decode temp was 0.23 GB) and each
# layer's rows written by a slow dynamic-update-slice; the convolution
# step's kernel, aliasing the pool, takes it as stored.
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_mamba1_serving_programs_fit_one_chip(v5e, program):
    from chipbench.archs import jamba as arch
    from paddle_tpu.models import generate as gen
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "ai21-jamba2-3b.json")) as f:
        c = json.load(f)
    cfg = arch.program_config(c, 12288)
    d = v5e[0]
    on = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    params = on(jax.eval_shape(lambda k: arch.weights(k, c),
                               jax.random.key(0)))
    B, page, pps = 256, 64, 192
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, 49153, page, state_slots=B)))
    assert pool["ssm"].shape == (26, 256, 16, 5120)
    assert pool["k"].shape == (2, 49153, 64, 1, 128)
    i32 = jnp.int32
    if program == "decode":
        def step(params, last, paged, tables, lengths, active):
            logits, paged = gen.paged_decode_forward(
                params, last, paged, tables, lengths, cfg, active=active,
                use_kernel=True)
            return jnp.argmax(logits, -1), paged
        avals = (_on(d, (B,), i32), pool, _on(d, (B, pps), i32),
                 _on(d, (B,), i32), _on(d, (B,), jnp.bool_))
    else:
        def step(params, toks, paged, table, ctx_len, chunk_len, slot):
            return gen.paged_prefill_chunk(
                params, toks, paged, table, cfg, ctx_cap=pps * page,
                ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=True,
                state_slot=slot)
        avals = (_on(d, (1, 256), i32), pool, _on(d, (pps,), i32),
                 _on(d, (), i32), _on(d, (), i32), _on(d, (), i32))
    compiled = _compile(jax.jit(step, donate_argnums=(2,)), params, *avals)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    resident = sum(a.size * a.dtype.itemsize for a in
                   jax.tree.leaves(params) + jax.tree.leaves(pool))
    assert 11.6e9 < resident < 11.7e9
    assert held < 12.0e9
    assert m.temp_size_in_bytes < (0.05e9 if program == "decode" else 0.32e9)
    text = compiled.as_text()
    # no whole pool is copied: the convolution tails' (204 MB, either
    # layout) and the K and V pools (1.6 GB each) were; in the decode step
    # only the kernel writes the tails (a chunk writes its one row in place)
    assert not re.search(r"= bf16\[(26,256|6656),3,5120\]\S* copy\(", text)
    assert not re.search(r"= bf16\[(26,256|6656),15360\]\S* copy\(", text)
    if program == "decode":
        assert "dynamic-update-slice" not in text
    assert not re.search(r"= bf16\[2,49153,64,1,128\]\S* copy\(", text)
    # the 13 Mamba-1 layers of a period, unrolled in the scan over two
    names = (("mamba1_state_update", "mamba1_conv_step") if program ==
             "decode" else ("mamba1_chunk_scan",))
    for name in names:
        assert len(re.findall(rf"%{name}[\w.\-]* = ", text)) >= 13


# Mellum2-12B-A2.5B's published widths at the cut of chipbench/configs/
# mellum2-12b-a2.5b.json (12 layers: nine sliding over 1,024 keys, three
# full); batch 32, a full pool of 5,121 pages of 64 and a sliding pool of 32
# x 21 + 1; the largest chunk program the cell reaches (context 10,240, width
# 256) held 1.07 GB of temp with its full layers' context gathered and their
# float32 scores of 32 heads in HBM; over the pages 0.004 GB.
def test_windowed_chunk_program_attends_over_pages(v5e):
    from chipbench.archs import mellum as arch
    from paddle_tpu.models import generate as gen
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "mellum2-12b-a2.5b.json")) as f:
        c = json.load(f)
    cfg = arch.program_config(c, 10240)
    d = v5e[0]
    on = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    params = on(jax.eval_shape(lambda k: arch.weights(k, c),
                               jax.random.key(0)))
    B, page, pps = 32, 64, 160
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, 5121, page, window_pages=1 + B * 21)))
    i32 = jnp.int32

    def step(params, toks, paged, table, ctx_len, chunk_len, wt):
        return gen.paged_prefill_chunk(
            params, toks, paged, table, cfg, ctx_cap=pps * page - 256,
            ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=True,
            window_table=wt, with_stats=True)
    compiled = _compile(jax.jit(step, donate_argnums=(2,)), params,
                        _on(d, (1, 256), i32), pool, _on(d, (pps,), i32),
                        _on(d, (), i32), _on(d, (), i32), _on(d, (pps,), i32))
    m = compiled.memory_analysis()
    text = compiled.as_text()
    assert len(re.findall(r"%kv_chunk_attention[\w.\-]* = ", text)) >= 2
    assert not re.search(r"f32\[(\d+,)?32,256,\d+\]", text)
    assert m.temp_size_in_bytes < 0.05e9


def test_swiglu_fits_scoped_vmem_at_width_4096(v5e):
    """block_rows=256 x width 4096 needed 19.93 MiB of the 16 MiB scoped
    VMEM, forward and backward; the row block now follows the width."""
    g = _on(v5e[0], (4 * 4096, 4096), jnp.bfloat16)

    def loss(g, u):
        return fused.swiglu(g, u).astype(jnp.float32).sum()
    _compile(jax.value_and_grad(loss, argnums=(0, 1)), g, g)


def _abstract_state(cfg, shardings):
    state = jax.eval_shape(lambda k: train.init_train_state(k, cfg),
                           jax.random.key(0))
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, shardings)


def _hybrid(v5e, width, seq):
    cfg = llama.LlamaConfig(**{**width, "max_seq_len": seq}, num_layers=2,
                            dtype=jnp.bfloat16, remat=True)
    mesh = Mesh(np.asarray(v5e).reshape(1, 2, 2), ("dp", "fsdp", "tp"))
    step = train.make_train_step(cfg, mesh, seq_chunk=512)
    tokens = jax.ShapeDtypeStruct(
        (4, seq), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))))
    _compile(step, _abstract_state(cfg, train.state_shardings(mesh, cfg)),
             tokens)


def test_hybrid_train_step_lowers_with_the_flash_kernel(v5e):
    """("dp","fsdp","tp") = (1,2,2): the flash call sits on GSPMD-sharded
    operands and lowers only inside a shard_map ("Mosaic kernels cannot
    be automatically partitioned")."""
    _hybrid(v5e, NARROW, 512)


@pytest.mark.slow
def test_hybrid_train_step_flagship_width(v5e):
    _hybrid(v5e, FLAGSHIP, 4096)


def test_pipeline_train_step_lowers_with_the_flash_kernel(v5e):
    """("dp","pp","tp") = (1,2,2), interleave_1f1b: the stages are manual
    over pp only, so the kernel needs the remaining axes mapped too."""
    cfg = llama.LlamaConfig(**NARROW, num_layers=4, dtype=jnp.bfloat16,
                            remat=True)
    mesh = Mesh(np.asarray(v5e).reshape(1, 2, 2), ("dp", "pp", "tp"))
    step = train_pp.make_train_step_pp(
        cfg, mesh, num_microbatches=2, schedule="interleave_1f1b",
        num_chunks=2)
    tokens = jax.ShapeDtypeStruct(
        (4, 512), jnp.int32, sharding=NamedSharding(mesh, P("dp")))
    _compile(step,
             _abstract_state(cfg, train_pp.state_shardings_pp(mesh, cfg)),
             tokens)
