"""PR 29: a serving cell's decode and chunk programs, compiled for v5e
without a chip, as operation counts by kind, the compiled temp, and every
operation whose result is as large as a layer's K pool or larger. Run from
a checkout of the parent and from this tree and compare what is printed:

    python3 chipbench/tools/calls/pr29_hlo_ops.py <cell>

The Mellum cell must print the same counts on both (its decode program ran
the carry branch before); a dense cell's decode program loses its pool-sized
``copy`` / ``dynamic-slice`` / ``dynamic-update-slice`` and its temp. Uses
only what both trees have."""
import collections
import math
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

from chipbench import harness
from paddle_tpu.models import generate as gen
from paddle_tpu.ops.pallas import flash_attention as fa

cell = harness.Cell(sys.argv[1])
c, e = cell.config, cell.mix["engine"]
if cell.mix["kind"] == "serve_arch":
    from chipbench.drivers.serve_arch import arch_of
    arch = arch_of(cell)
    cfg = arch.program_config(c, e["max_len"])
    make = lambda k: arch.weights(k, c)
else:
    from chipbench import weights
    cfg = harness.program_config(c, e["max_len"])
    make = lambda k: weights.make(k, c)
one = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
on = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
params = on(jax.eval_shape(make, jax.random.key(0)))
page, B, i32 = e["page_size"], e["max_batch"], jnp.int32
pps = -(-e["max_len"] // page)
window = "sliding" in cfg.period
moe = cfg.moe is not None
pool_kw = {}
if window:
    ring = min(pps, -(-cfg.sliding_window // page)
               + -(-e["prefill_chunk"] // page) + 1)
    pool_kw = {"window_pages": 1 + B * ring}
pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
    cfg, e["num_pages"], page, **pool_kw)))
# a layer's K pool, the smallest thing the old branch moved whole
layer_pool = min(math.prod(a.shape[1:]) for a in jax.tree.leaves(pool)
                 if a.dtype != jnp.float32)


def show(name, compiled):
    text = compiled.as_text()
    ops = collections.Counter(re.findall(r"= \S+ ([a-z][\w\-]*)\(", text))
    big = collections.Counter()
    for dt, dims, op in re.findall(
            r"= (\w+)\[([\d,]+)\]\S* ([a-z][\w\-]*)\(", text):
        if math.prod(int(d) for d in dims.split(",")) >= layer_pool:
            big[f"{op} {dt}[{dims}]"] += 1
    m = compiled.memory_analysis()
    print(name, "ops", sum(ops.values()), "temp bytes", m.temp_size_in_bytes)
    print("  counts", sorted(ops.items()))
    print("  results of a layer's pool or more", sorted(big.items()),
          flush=True)


def decode(params, last, paged, tables, lengths, active, wt):
    kw = {"window_tables": wt} if window else {}
    if moe:
        kw["with_stats"] = True
    out = gen.paged_decode_forward(params, last, paged, tables, lengths, cfg,
                                   active=active, use_kernel=True, **kw)
    return (jnp.argmax(out[0], -1),) + tuple(out[1:])


def chunk(params, toks, paged, table, ctx_len, chunk_len, wt):
    kw = {"window_table": wt} if window else {}
    if moe:
        kw["with_stats"] = True
    return gen.paged_prefill_chunk(params, toks, paged, table, cfg,
                                   ctx_cap=512, ctx_len=ctx_len,
                                   chunk_len=chunk_len, use_kernel=True, **kw)


with fa.force_compiled_lowering():
    show("jit_paged_decode", jax.jit(decode, donate_argnums=(2,)).lower(
        params, sds((B,), i32), pool, sds((B, pps), i32), sds((B,), i32),
        sds((B,), jnp.bool_), sds((B, pps), i32)).compile())
    show("jit_prefill_chunk_c512_w%d" % e["prefill_chunk"],
         jax.jit(chunk, donate_argnums=(2,)).lower(
             params, sds((1, e["prefill_chunk"]), i32), pool, sds((pps,), i32),
             sds((), i32), sds((), i32), sds((pps,), i32)).compile())
