"""PR 34: compile the comparison that decides ``correct``
(``drivers/serve_arch.py:compare``: the architecture's reference over the
longest row of the mix) for ``v5e:2x2`` in the sandbox, without a chip, and
print what it holds of the chip's memory beside the bfloat16 weights. Proves
compilation only; nothing runs.

    JAX_PLATFORMS=cpu python3 chipbench/tools/calls/pr34_aot_compare.py <serve_arch cell>
"""
import os
import sys

sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench import harness
from chipbench.drivers.serve_arch import arch_of
from chipbench.tools.aot_sizes import report

cell = harness.Cell(sys.argv[1])
arch, c, mix = arch_of(cell), cell.config, cell.mix
ref = arch.reference
dev = topologies.get_topology_desc(platform="tpu",
                                   topology_name="v5e:2x2").devices[0]
one = SingleDeviceSharding(dev)
sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
params = jax.tree.map(lambda s: sds(s.shape, s.dtype), jax.eval_shape(
    lambda k: arch.weights(k, c), jax.random.key(0)))
longest = mix["prefix_tokens"] + mix["tail_tokens"][1] + mix["output_tokens"][1]
width = -(-longest // 256) * 256
n_out = mix["output_tokens"][1]


def gaps(params, row, toks, start, count):
    x = ref.hidden(params, row, c)
    at = jnp.clip(start + jnp.arange(n_out), 0, width - 1)
    lg = ref.logits(params, jnp.take(x, at, axis=0), c)
    g = jnp.max(lg, -1) - jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]
    return jnp.where(jnp.arange(n_out) < count, g, 0.0)


i32 = jnp.int32
compiled = jax.jit(gaps).lower(params, sds((width,), i32), sds((n_out,), i32),
                               sds((), i32), sds((), i32)).compile()
report(f"{cell.name} reference over a row of {width} tokens", compiled)
