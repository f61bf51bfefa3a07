"""Scratch: compile the decode and chunk programs of a ``serve_arch`` cell
for ``v5e:2x2`` at the real sizes, in the sandbox and without a chip, and
print ``memory_analysis``: whether weights, both pools and the programs'
temp fit one chip settles how many layers the cell runs. ``--ops`` also
prints the compiled decode program's operation counts by kind (the dense
cells' too: what a change to the layer scan must leave alone). Proves
compilation only; nothing runs.

    JAX_PLATFORMS=cpu python3 -m chipbench.tools.aot_arch <cell> [--layers N] [--ops]
"""
import argparse
import collections
import os
import re

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench import harness
from chipbench.tools.aot_sizes import GB, report
from paddle_tpu.models import generate as gen
from paddle_tpu.ops.pallas import flash_attention as fa


def op_counts(compiled):
    return collections.Counter(
        re.findall(r"= \S+ ([a-z][\w\-]*)\(", compiled.as_text()))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--ctx-pages", type=int, default=128)
    a = ap.parse_args()
    cell = harness.Cell(a.cell)
    c, e = dict(cell.config), cell.mix["engine"]
    if a.layers:
        c["num_hidden_layers"] = a.layers
    if cell.mix["kind"] == "serve_arch":
        from chipbench.drivers.serve_arch import arch_of
        arch = arch_of(cell)
        cfg = arch.program_config(c, e["max_len"])
        make = lambda k: arch.weights(k, c)
    else:
        from chipbench import weights
        cfg = harness.program_config(c, e["max_len"])
        make = lambda k: weights.make(k, c)
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2").devices[0]
    one = SingleDeviceSharding(dev)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    on = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
    params = on(jax.eval_shape(make, jax.random.key(0)))
    page, B = e["page_size"], e["max_batch"]
    pps = -(-e["max_len"] // page)
    window = "sliding" in cfg.period
    wpages = None
    if window:
        ring = min(pps, -(-cfg.sliding_window // page)
                   + -(-e["prefill_chunk"] // page) + 1)
        wpages = 1 + B * ring
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, e["num_pages"], page, **({"window_pages": wpages}
                                      if window else {}))))
    moe = cfg.moe is not None
    extra = {"window_tables": None, "with_stats": moe} if window or moe else {}

    def decode(params, last, paged, tables, lengths, active, wt):
        kw = dict(extra, window_tables=wt) if window else extra
        out = gen.paged_decode_forward(params, last, paged, tables, lengths,
                                       cfg, active=active, use_kernel=True,
                                       **kw)
        return (jnp.argmax(out[0], -1),) + tuple(out[1:])
    i32 = jnp.int32
    with fa.force_compiled_lowering():
        compiled = jax.jit(decode, donate_argnums=(2,)).lower(
            params, sds((B,), i32), pool, sds((B, pps), i32), sds((B,), i32),
            sds((B,), jnp.bool_), sds((B, pps), i32)).compile()
    report(f"{cell.name} decode step, batch {B}, "
           f"{c['num_hidden_layers']} layers", compiled)
    if a.ops:
        print(sorted(op_counts(compiled).items()), flush=True)
    ctx_cap, width = a.ctx_pages * page, e["prefill_chunk"]

    def chunk(params, toks, paged, table, ctx_len, chunk_len, wt):
        kw = ({"window_table": wt, "with_stats": moe} if window
              else ({"with_stats": True} if moe else {}))
        return gen.paged_prefill_chunk(params, toks, paged, table, cfg,
                                       ctx_cap=ctx_cap, ctx_len=ctx_len,
                                       chunk_len=chunk_len, use_kernel=True,
                                       **kw)
    with fa.force_compiled_lowering():
        compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
            params, sds((1, width), i32), pool, sds((pps,), i32),
            sds((), i32), sds((), i32), sds((pps,), i32)).compile()
    report(f"{cell.name} chunk program, context {ctx_cap}, width {width}",
           compiled)
    nbytes = lambda tree: sum(
        int(jnp.dtype(x.dtype).itemsize) * int(jnp.prod(jnp.asarray(x.shape)))
        for x in jax.tree.leaves(tree))
    print(f"{cell.name}: weights {nbytes(params) / GB:.2f} GB + pools "
          f"{nbytes(pool) / GB:.2f} GB = {(nbytes(params) + nbytes(pool)) / GB:.2f}"
          f" GB resident", flush=True)


if __name__ == "__main__":
    main()
