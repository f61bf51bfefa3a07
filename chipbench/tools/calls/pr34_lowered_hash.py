"""PR 34: the decode and chunk programs of the hybrid family (a share of the
experts through ``_latent_moe_ffn``) and of a softmax-routed decoder with
sliding layers (``_moe_ffn``), lowered at a small size on the CPU, as a hash
of their StableHLO text. Run from a checkout of the parent and from this
tree: the same hashes say that merging the routing rule into shared code
moved neither family's program. Uses only what both trees have.

    JAX_PLATFORMS=cpu python3 chipbench/tools/calls/pr34_lowered_hash.py
"""
import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp

from paddle_tpu.models import generate as gen, hybrid, llama
from paddle_tpu.models.moe import MoEConfig


def digest(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return hashlib.md5(text.encode()).hexdigest()[:12], len(text)


def programs(cfg, params, pool_kw, chunk_kw):
    page, B, pps = 4, 2, 8
    pool = gen.init_paged_cache(cfg, 17, page, **pool_kw)
    tables = jnp.zeros((B, pps), jnp.int32)
    i32 = jnp.int32
    win = {"window_tables": tables} if "sliding" in cfg.period else {}
    yield "decode", digest(
        lambda p, last, pg, t, ln: gen.paged_decode_forward(
            p, last, pg, t, ln, cfg, with_stats=True, **win),
        params, jnp.zeros((B,), i32), pool, tables, jnp.zeros((B,), i32))
    winc = {"window_table": tables[0]} if "sliding" in cfg.period else {}
    yield "chunk", digest(
        lambda p, tok, pg, t, a, b: gen.paged_prefill_chunk(
            p, tok, pg, t, cfg, ctx_cap=8, ctx_len=a, chunk_len=b,
            with_stats=True, **winc, **chunk_kw),
        params, jnp.zeros((1, 8), i32), pool, tables[0], jnp.int32(5),
        jnp.int32(7))


hy = llama.LlamaConfig.tiny(
    num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
    moe=MoEConfig(num_experts=16, top_k=6),
    layer_pattern=("mamba2", "experts", "attention", "experts"),
    hybrid=llama.HybridConfig(routed_scale=2.5))
for name, d in programs(hy, hybrid.init_params(jax.random.key(0), hy,
                                                experts_held=4,
                                                first_expert=4),
                        {"state_slots": 2}, {"state_slot": jnp.int32(1)}):
    print("hybrid", name, *d)
me = llama.LlamaConfig.tiny(
    num_layers=4, moe=MoEConfig(num_experts=8, top_k=2),
    layer_pattern=("sliding", "sliding", "sliding", "full"),
    sliding_window=8, rope_theta_sliding=10000.0,
    yarn=llama.YarnRope(4.0, 32))
for name, d in programs(me, llama.init_params(jax.random.key(0), me),
                        {"window_pages": 9}, {}):
    print("softmax-routed", name, *d)
