"""Scratch: compile each cell's step programs for ``v5e:2x2`` at the real
sizes, in the sandbox and without a chip, and print ``memory_analysis``: it
settles the train batch and shows each cell's fullest device against 16 GB
before chip time is spent. Proves compilation only; nothing runs.

    JAX_PLATFORMS=cpu python3 -m chipbench.tools.aot_sizes [cell ...]
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from chipbench import arith, harness
from paddle_tpu.models import generate as gen, llama, train
from paddle_tpu.ops.pallas import flash_attention as fa

GB = 1e9


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / GB:.2f} GB, outputs "
          f"{m.output_size_in_bytes / GB:.2f}, aliased {m.alias_size_in_bytes / GB:.2f}, "
          f"temp {m.temp_size_in_bytes / GB:.2f}; a device holds {total / GB:.2f} GB "
          f"of 16", flush=True)


llama_cfg = harness.program_config


def train_cell(cell, devices, batches):
    c, job = cell.config, cell.mix
    cfg = llama_cfg(c, job["seq_len"], job["remat"])
    mesh = None
    if job["mesh"]:
        mesh = Mesh(np.asarray(devices[:cell.chips]).reshape(tuple(job["mesh"].values())),
                    tuple(job["mesh"].keys()))
        st_sh = train.state_shardings(mesh, cfg)
        tok_sh = NamedSharding(mesh, P(("dp", "fsdp")))
    else:
        one = SingleDeviceSharding(devices[0])
        tok_sh = one
    shapes = jax.eval_shape(lambda k: train.init_train_state(k, cfg), jax.random.key(0))
    if mesh is None:
        state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
    else:
        state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                             shapes, st_sh)
    o = job["optimizer"]
    step = train.make_train_step(cfg, mesh, lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                 weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
                                 seq_chunk=job["seq_chunk"])
    for b in batches:
        toks = jax.ShapeDtypeStruct((b, job["seq_len"]), jnp.int32, sharding=tok_sh)
        try:
            with fa.force_compiled_lowering():
                compiled = step.lower(state, toks).compile()
            report(f"{cell.name} train step, batch {b}", compiled)
        except Exception as e:  # the compiler's refusal is the answer sought
            print(f"{cell.name} train step, batch {b}: refused: {str(e)[:300]}", flush=True)


def serve_cell(cell, devices):
    c, e = cell.config, cell.mix["engine"]
    cfg = llama_cfg(c, e["max_len"])
    one = SingleDeviceSharding(devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                          jax.eval_shape(lambda k: llama.init_params(k, cfg), jax.random.key(0)))
    L, nkv, hd = c["num_hidden_layers"], c["num_key_value_heads"], arith.head_dim(c)
    pool_shape = (L, e["num_pages"], e["page_size"], nkv, hd)
    pool = {"k": sds(pool_shape, jnp.bfloat16), "v": sds(pool_shape, jnp.bfloat16)}
    B, pps = e["max_batch"], e["max_len"] // e["page_size"]

    def decode(params, last, paged, tables, lengths, active):
        logits, paged = gen.paged_decode_forward(params, last, paged, tables, lengths, cfg,
                                                 active=active, use_kernel=True)
        return jnp.argmax(logits, -1), paged
    with fa.force_compiled_lowering():
        compiled = jax.jit(decode, donate_argnums=(2,)).lower(
            params, sds((B,), jnp.int32), pool, sds((B, pps), jnp.int32),
            sds((B,), jnp.int32), sds((B,), jnp.bool_)).compile()
    report(f"{cell.name} decode step, batch {B}", compiled)
    weights_gb = arith.num_params(c) * 2 / GB
    pool_gb = 2 * np.prod(pool_shape) * 2 / GB
    print(f"{cell.name}: weights {weights_gb:.2f} GB + pool {pool_gb:.2f} GB = "
          f"{weights_gb + pool_gb:.2f} GB resident", flush=True)


def main(names):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        if names and w["name"] not in names:
            continue
        cell = harness.Cell(w["name"])
        if cell.mix["kind"] == "train_steps":
            train_cell(cell, topo.devices, [cell.mix["batch"]])
        else:
            serve_cell(cell, topo.devices)


if __name__ == "__main__":
    main(sys.argv[1:])
