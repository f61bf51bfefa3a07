"""A ``serve_arch`` cell's decode program and a chunk program (context 512,
width 256), compiled for v5e without a chip: the compiled temp, the count of
``scatter`` and ``copy`` operations, a digest of every operation's result
shape and kind, and each operation whose result is 50 MB or more. Run from
a checkout of the parent and from this tree: the same digest says a change
left that cell's compiled programs as they were.

    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr41_hlo_compare.py <cell> [decode|chunk]
"""
import hashlib
import math
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.drivers.serve_arch import arch_of  # noqa: E402
from paddle_tpu.models import generate as gen  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402


def compiled(cell, which):
    c, e = cell.config, cell.mix["engine"]
    arch = arch_of(cell)
    cfg = arch.program_config(c, e["max_len"])
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    on = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
    params = on(jax.eval_shape(lambda k: arch.weights(k, c),
                               jax.random.key(0)))
    page, B, i32 = e["page_size"], e["max_batch"], jnp.int32
    pps = -(-e["max_len"] // page)
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, e["num_pages"], page, state_slots=B)))

    def decode(params, last, paged, tables, lengths, active):
        out = gen.paged_decode_forward(params, last, paged, tables, lengths,
                                       cfg, active=active, use_kernel=True)
        return (jnp.argmax(out[0], -1),) + tuple(out[1:])

    def chunk(params, toks, paged, table, ctx_len, chunk_len, slot):
        return gen.paged_prefill_chunk(
            params, toks, paged, table, cfg, ctx_cap=512, ctx_len=ctx_len,
            chunk_len=chunk_len, use_kernel=True, state_slot=slot)
    with fa.force_compiled_lowering():
        if which == "decode":
            return jax.jit(decode, donate_argnums=(2,)).lower(
                params, sds((B,), i32), pool, sds((B, pps), i32),
                sds((B,), i32), sds((B,), jnp.bool_)).compile()
        return jax.jit(chunk, donate_argnums=(2,)).lower(
            params, sds((1, 256), i32), pool, sds((pps,), i32),
            sds((), i32), sds((), i32), sds((), i32)).compile()


def main():
    cell = harness.Cell(sys.argv[1])
    for which in sys.argv[2:] or ["decode", "chunk"]:
        comp = compiled(cell, which)
        text = comp.as_text()
        ops = re.findall(r"= (\w+\[[\d,]*\])\S* ([a-z][\w\-]*)\(", text)
        digest = hashlib.md5("\n".join(
            sorted(f"{s} {op}" for s, op in ops)).encode()).hexdigest()[:12]
        print(f"{cell.name} {which}: temp "
              f"{comp.memory_analysis().temp_size_in_bytes / 1e9:.4f} GB, "
              f"{sum(op == 'scatter' for _, op in ops)} scatters, "
              f"{sum(op == 'copy' for _, op in ops)} copies, digest {digest}",
              flush=True)
        for line in text.splitlines():
            m = re.search(r"= (\w+)\[([\d,]+)\]\S* ([a-z][\w\-]*)\(", line)
            if m and m.group(3) not in ("parameter", "get-tuple-element",
                                        "bitcast", "tuple"):
                size = math.prod(int(d) for d in m.group(2).split(","))
                if size * (4 if m.group(1) in ("f32", "s32") else 2) >= 50e6:
                    print("   ", line.strip()[:200])


if __name__ == "__main__":
    main()
