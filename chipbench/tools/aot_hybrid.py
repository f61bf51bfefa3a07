"""Scratch: compile the decode program and the largest chunk program of a
``serve_arch`` cell whose model keeps recurrent state, for ``v5e:2x2`` at
the real sizes, in the sandbox and without a chip, and print
``memory_analysis`` (``aot_arch.py`` for a model with a state pool beside
its pages: the pool needs its slots, the chunk program the row's). Proves
compilation only; nothing runs.

    JAX_PLATFORMS=cpu python3 -m chipbench.tools.aot_hybrid <cell> [--batch N] [--ctx-pages N ...] [--width N ...]
"""
import argparse
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench import harness
from chipbench.drivers.serve_arch import arch_of
from chipbench.tools.aot_sizes import GB, report
from paddle_tpu.models import generate as gen
from paddle_tpu.ops.pallas import flash_attention as fa


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--batch", type=int)
    ap.add_argument("--ctx-pages", type=int, nargs="*")
    ap.add_argument("--width", type=int, nargs="*")
    a = ap.parse_args()
    cell = harness.Cell(a.cell)
    c, e = cell.config, cell.mix["engine"]
    arch = arch_of(cell)
    cfg = arch.program_config(c, e["max_len"])
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2").devices[0]
    one = SingleDeviceSharding(dev)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    on = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
    params = on(jax.eval_shape(lambda k: arch.weights(k, c),
                               jax.random.key(0)))
    page, B = e["page_size"], a.batch or e["max_batch"]
    pps = -(-e["max_len"] // page)
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, e["num_pages"], page, state_slots=B)))
    i32 = jnp.int32

    def decode(params, last, paged, tables, lengths, active):
        logits, paged, stats = gen.paged_decode_forward(
            params, last, paged, tables, lengths, cfg, active=active,
            use_kernel=True, with_stats=True)
        return jnp.argmax(logits, -1), paged, stats
    with fa.force_compiled_lowering():
        compiled = jax.jit(decode, donate_argnums=(2,)).lower(
            params, sds((B,), i32), pool, sds((B, pps), i32), sds((B,), i32),
            sds((B,), jnp.bool_)).compile()
    report(f"{cell.name} decode step, batch {B}, "
           f"{c['num_hidden_layers']} layers", compiled)
    for width in a.width or [e["prefill_chunk"]]:
        for ctx_pages in a.ctx_pages if a.ctx_pages is not None else [pps]:
            ctx_cap = ctx_pages * page

            def chunk(params, toks, paged, table, ctx_len, chunk_len, slot):
                return gen.paged_prefill_chunk(
                    params, toks, paged, table, cfg, ctx_cap=ctx_cap,
                    ctx_len=ctx_len, chunk_len=chunk_len, use_kernel=True,
                    with_stats=True, state_slot=slot)
            with fa.force_compiled_lowering():
                compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
                    params, sds((1, width), i32), pool, sds((pps,), i32),
                    sds((), i32), sds((), i32), sds((), i32)).compile()
            report(f"{cell.name} chunk program, context {ctx_cap}, width "
                   f"{width}", compiled)
    nbytes = lambda tree: sum(
        int(jnp.dtype(x.dtype).itemsize) * int(jnp.prod(jnp.asarray(x.shape)))
        for x in jax.tree.leaves(tree))
    print(f"{cell.name}: weights {nbytes(params) / GB:.2f} GB + pools "
          f"{nbytes(pool) / GB:.2f} GB = {(nbytes(params) + nbytes(pool)) / GB:.2f}"
          f" GB resident", flush=True)


if __name__ == "__main__":
    main()
