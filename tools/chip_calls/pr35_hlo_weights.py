"""PR 35: what a serving cell's compiled decode and chunk programs do to
the attention projections. Compiles both for v5e without a chip, as
``chipbench/tools/calls/pr29_hlo_ops.py`` does, and prints the operation
counts, the compiled temp and every ``copy`` and every root-level
``dynamic-slice`` fusion whose result has a weight's dtype and at least
as many elements as the cell's smallest attention matrix. Run from a
checkout of the parent and from this tree and compare what is printed:

    python3 tools/chip_calls/pr35_hlo_weights.py <cell> [<cell> ...]

Uses only what both trees have."""
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

ONE = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=ONE)
on = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

# the attention projections by the names the three layer trees give them
ATTENTION = ("wq", "wk", "wv", "wo", "wq_a", "wq_b", "wkv_a", "wkv_b")


def weight_sized(text, least, dtype="bf16"):
    """``(name, shape)`` of every copy and every root-level dynamic-slice
    fusion of the compiled ``text`` with ``least`` elements or more."""
    found = []
    for name, dt, dims in re.findall(
            r"%((?:copy|\w*dynamic-slice_fusion)[\w.\-]*) = (\w+)\[([\d,]+)\]"
            r"\S* (?:copy|fusion)\(", text):
        if dt == dtype and math.prod(map(int, dims.split(","))) >= least:
            found.append((name, f"{dt}[{dims}]"))
    return found


def pallas_calls(text):
    """Every Pallas call of the compiled ``text`` as its name (less the
    instance's number), its result and its operands' types: what the
    benchmark's ``op_pattern``s find a kernel by."""
    for line in text.splitlines():
        m = re.match(r"\s*%([\w.\-]+) = (.*?) custom-call\(.*?\), "
                     r"custom_call_target=\"tpu_custom_call\", "
                     r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=", line)
        if m:
            yield "%s = %s (%s)" % (
                re.sub(r"[.\d]+$", "", m.group(1)),
                re.sub(r"\{[^}]*\}", "", m.group(2)),
                ", ".join(re.findall(r"(\w+\[[\d,]*\])", m.group(3))))


def show(name, compiled, least):
    text = compiled.as_text()
    ops = collections.Counter(re.findall(r"= \S+ ([a-z][\w\-]*)\(", text))
    m = compiled.memory_analysis()
    print(name, "ops", sum(ops.values()), "copy", ops["copy"], "temp bytes",
          m.temp_size_in_bytes)
    print("  pallas calls", sorted(collections.Counter(
        pallas_calls(text)).items()))
    print("  weight-sized copies and standalone slices (>= %d elements)"
          % least, weight_sized(text, least), flush=True)


def programs(cell_name):
    cell = harness.Cell(cell_name)
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
    params = on(jax.eval_shape(make, jax.random.key(0)))
    layers = params["layers"]
    least = min(math.prod(a.shape[1:])
                for n, a in layers.get("attention", layers).items()
                if n in ATTENTION)
    page, B, i32 = e["page_size"], e["max_batch"], jnp.int32
    pps = -(-e["max_len"] // page)
    window = "sliding" in cfg.period
    stats = cfg.moe is not None
    state = getattr(cfg, "hybrid", None) is not None
    pool_kw = {}
    if window:
        ring = min(pps, -(-cfg.sliding_window // page)
                   + -(-e["prefill_chunk"] // page) + 1)
        pool_kw = {"window_pages": 1 + B * ring}
    if state:
        pool_kw = {"state_slots": B}
    pool = on(jax.eval_shape(lambda: gen.init_paged_cache(
        cfg, e["num_pages"], page, **pool_kw)))

    def decode(params, last, paged, tables, lengths, active, wt):
        kw = {"window_tables": wt} if window else {}
        if stats:
            kw["with_stats"] = True
        out = gen.paged_decode_forward(params, last, paged, tables, lengths,
                                       cfg, active=active, use_kernel=True,
                                       **kw)
        return (jnp.argmax(out[0], -1),) + tuple(out[1:])

    def chunk(params, toks, paged, table, ctx_len, chunk_len, wt, slot):
        kw = {"window_table": wt} if window else {}
        if stats:
            kw["with_stats"] = True
        if state:
            kw["state_slot"] = slot
        return gen.paged_prefill_chunk(
            params, toks, paged, table, cfg, ctx_cap=512, ctx_len=ctx_len,
            chunk_len=chunk_len, use_kernel=True, **kw)

    print(cell_name, flush=True)
    with fa.force_compiled_lowering():
        show("jit_paged_decode", jax.jit(decode, donate_argnums=(2,)).lower(
            params, sds((B,), i32), pool, sds((B, pps), i32), sds((B,), i32),
            sds((B,), jnp.bool_), sds((B, pps), i32)).compile(), least)
        show("jit_prefill_chunk_c512_w%d" % e["prefill_chunk"],
             jax.jit(chunk, donate_argnums=(2,)).lower(
                 params, sds((1, e["prefill_chunk"]), i32), pool,
                 sds((pps,), i32), sds((), i32), sds((), i32),
                 sds((pps,), i32), sds((), i32)).compile(), least)


for name in sys.argv[1:]:
    programs(name)
