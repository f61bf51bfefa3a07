"""PR 28: the dense cells' decode and chunk programs, compiled for v5e
without a chip, as operation counts by kind: run from a checkout of the
parent and from this tree (``python3 chipbench/tools/calls/pr28_hlo_ops.py``
at its root) and compare the two printed lines. Uses only what both trees
have."""
import collections
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench import harness, weights
from paddle_tpu.models import generate as gen
from paddle_tpu.ops.pallas import flash_attention as fa

cell = harness.Cell("internlm2-1.8b.longgen-overload")
c, e = cell.config, cell.mix["engine"]
cfg = harness.program_config(c, e["max_len"])
one = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
on = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
params = on(jax.eval_shape(lambda k: weights.make(k, c), jax.random.key(0)))
pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
    cfg, e["num_pages"], e["page_size"])))
B, pps, i32 = e["max_batch"], e["max_len"] // e["page_size"], jnp.int32


def counts(compiled):
    text = compiled.as_text()
    ops = collections.Counter(re.findall(r"= \S+ ([a-z][\w\-]*)\(", text))
    m = compiled.memory_analysis()
    return sorted(ops.items()), sum(ops.values()), m.temp_size_in_bytes


def decode(params, last, paged, tables, lengths, active):
    logits, paged = gen.paged_decode_forward(
        params, last, paged, tables, lengths, cfg, active=active,
        use_kernel=True)
    return jnp.argmax(logits, -1), paged


def chunk(params, toks, paged, table, ctx_len, chunk_len):
    return gen.paged_prefill_chunk(params, toks, paged, table, cfg,
                                   ctx_cap=512, ctx_len=ctx_len,
                                   chunk_len=chunk_len, use_kernel=True)


with fa.force_compiled_lowering():
    d = jax.jit(decode, donate_argnums=(2,)).lower(
        params, sds((B,), i32), pool, sds((B, pps), i32), sds((B,), i32),
        sds((B,), jnp.bool_)).compile()
    k = jax.jit(chunk, donate_argnums=(2,)).lower(
        params, sds((1, 256), i32), pool, sds((pps,), i32), sds((), i32),
        sds((), i32)).compile()
print("jit_paged_decode", *counts(d))
print("jit_prefill_chunk_c512_w256", *counts(k))
