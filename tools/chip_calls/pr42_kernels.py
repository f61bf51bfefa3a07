"""The Mamba-1 convolution step alone at AI21-Jamba2-3B's published widths
(D 5,120 channels, a kernel of K = 4): device time of a decode step's call
in one layer (256 rows of a tails' pool of 26 x 256 row-layers, bf16) at
each block of rows, against the ``jnp`` twin, and the bandwidth its least
bytes give (the rows' tails read and written, ``x`` read, ``c`` written in
float32). One program makes the 26 layers' calls in turn, each ``c`` summed
into a float32 accumulator (its bytes counted too): timed a call at a time,
the host's dispatch of the donated pool (about 0.3 ms) hid the kernel.

    python3 tools/chip_calls/pr42_kernels.py            # the chip
    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr42_kernels.py --aot

Exit 1 where a kernel's result differs from its twin's (its tails bit for
bit, ``c`` by more than 1e-3).
"""
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas import mamba1  # noqa: E402

D, K, B, LAYERS = 5120, 4, 256, 26
HBM = 819e9
ROWS = [16, 32, 64, 128]


def timed(step, reps=50, rounds=3):
    out = step()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(reps):
            out = step()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / reps)
    return best


def main():
    aot = "--aot" in sys.argv
    bf = jnp.bfloat16
    k = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k[1], (B, D)).astype(bf)
    w = jax.random.normal(k[2], (K, D)).astype(bf)
    b = jax.random.normal(k[3], (D,)).astype(bf)
    base = 13 * B
    active = jnp.arange(B) % 7 != 3
    shape = (LAYERS * B, (K - 1) * D)
    least = 2 * B * (K - 1) * D * 2 + B * D * 2 + B * D * 4 + 2 * B * D * 4

    def layers(pool, fn):
        def body(i, carry):
            p, acc = carry
            c, p = fn(p, i * B, x, w, b, active)
            return p, acc + c
        return jax.lax.fori_loop(0, LAYERS, body,
                                 (pool, jnp.zeros((B, D), jnp.float32)))
    forms = [("twin", mamba1.mamba1_conv_step_reference)]
    forms += [(f"kernel rows {r}",
               lambda *a, r=r: mamba1.mamba1_conv_step_kernel(*a, rows=r))
              for r in ROWS]
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        for name, fn in forms[1:]:
            try:
                with fa.force_compiled_lowering():
                    jax.jit(lambda p, fn=fn: fn(p, base, x, w, b, active),
                            donate_argnums=(0,)).lower(jax.ShapeDtypeStruct(
                                shape, bf, sharding=one)).compile()
            except Exception as e:           # a block Mosaic refuses
                print(f"conv step {name}: refused: "
                      f"{str(e).splitlines()[0][:160]}", flush=True)
                continue
            print(f"conv step {name}: compiles", flush=True)
        return 0
    pool = jax.random.normal(k[0], shape).astype(bf)
    want = jax.jit(lambda p: mamba1.mamba1_conv_step_reference(
        p, base, x, w, b, active))(pool)
    bad = False
    for name, fn in forms:
        # the pool is donated and handed on, as a decode step's layers
        # hand it on, so that no call copies it whole
        f = jax.jit(lambda p, fn=fn: fn(p, base, x, w, b, active),
                    donate_argnums=(0,))
        try:
            c, new = f(jnp.copy(pool))
        except Exception as e:               # a block Mosaic refuses
            print(f"conv step {name}: refused: "
                  f"{str(e).splitlines()[0][:160]}", flush=True)
            continue
        dc = float(jnp.max(jnp.abs(c - want[0])))
        same = bool(jnp.array_equal(new, want[1]))
        bad |= dc > 1e-3 or not same
        del c, new
        g = jax.jit(lambda p, fn=fn: layers(p, fn), donate_argnums=(0,))
        box = [jnp.copy(pool)]

        def step():
            out = g(box[0])
            box[0] = out[0]
            return out
        sec = timed(step, reps=10) / LAYERS
        print(f"conv step {name}: {1e6 * sec:.1f} us a layer's call, "
              f"{LAYERS * 1e3 * sec:.3f} ms a step of 26 layers, "
              f"{least / sec / 1e9:.0f} GB/s of {HBM / 1e9:.0f} "
              f"({100 * least / sec / HBM:.1f}%), diff c {dc:.2e}, tails "
              f"{'equal' if same else 'DIFFER'}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
