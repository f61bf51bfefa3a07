"""The K/V chunk kernel alone at the serving cells' calls (a layer's call of
a 256-token chunk, pages of 64), against its twin over the power-of-two
bucket the gathering path attended over: device time a call at contexts
across each cell's range, the share of the MXU's peak that the true work
reads at that time (the (query, key) pairs the chunk's valid tokens see, a
window at most on a sliding layer, x nh x hd x 4 operations), and the
largest difference from the twin over the valid rows (exit 1 where it
passes 0.05 in bfloat16).

    python3 tools/chip_calls/pr39_kernel.py [cell ...] [rows ...]   # the chip
    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr39_kernel.py --aot ...

cells: trinity (48 heads over 8, full and a 4,096 window), mellum (32 over
4, full and a 1,024 window), dense (16 over 8); rows: query rows a grid
step, set on the module's constant (the program's own, 4,096, by
default).
"""
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas import paged_chunk_attention as pca  # noqa: E402

PAGE, C, HD, PEAK = 64, 256, 128, 197e12
CELLS = {
    "trinity": (48, 8, 384, (None, 4096), (0, 1984, 4096, 8192, 12288,
                                           16384)),
    "mellum": (32, 4, 160, (None, 1024), (0, 2048, 5120, 9984)),
    "dense": (16, 8, 64, (None,), (0, 256, 1024, 3840)),
}


def bucket(pages, pps):
    p2 = 1 if pages > 0 else 0
    while 0 < p2 < pages:
        p2 *= 2
    return min(p2, pps)


def true_flops(ctx, n, nh, window):
    seen = np.arange(ctx + 1, ctx + n + 1)
    if window is not None:
        seen = np.minimum(seen, window)
    return int(seen.sum()) * nh * HD * 4


def timed(fn, *args, reps=20, rounds=5):
    jax.block_until_ready(fn(*args))
    best = []
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t) / reps)
    return sorted(best)[len(best) // 2]


def main(argv):
    aot = "--aot" in argv
    names = [a for a in argv if a in CELLS] or list(CELLS)
    variants = [int(a) for a in argv if a.isdigit()] or [pca._CHUNK_ROWS]
    print("device", jax.devices()[0].device_kind, "aot" if aot else "")
    worst = 0.0
    for name in names:
        nh, nkv, pps, windows, ctxs = CELLS[name]
        P = pps + 8
        k = jax.random.split(jax.random.key(39), 4)
        if aot:
            from jax.experimental import topologies
            from jax.sharding import SingleDeviceSharding
            dev = topologies.get_topology_desc(
                topology_name="v5e:2x2", platform="tpu").devices[0]
            sd = lambda s, d: jax.ShapeDtypeStruct(  # noqa: E731
                s, d, sharding=SingleDeviceSharding(dev))
            q = sd((C, nh, HD), jnp.bfloat16)
            kp = vp = sd((P, PAGE, nkv, HD), jnp.bfloat16)
            table = np.arange(1, pps + 1, dtype=np.int32)
        else:
            q = jax.random.normal(k[0], (C, nh, HD), jnp.bfloat16)
            kp = jax.random.normal(k[1], (P, PAGE, nkv, HD), jnp.bfloat16)
            vp = jax.random.normal(k[2], (P, PAGE, nkv, HD), jnp.bfloat16)
            table = np.asarray(jax.random.permutation(k[3], P)[:pps],
                               np.int32)
        for window in windows:
            cases = [(c, C) for c in ctxs] + [(ctxs[-2], 100)]
            for ctx, n in cases[-1:] if aot else cases:
                full = jnp.asarray(table)
                tbl = full[:min(pps, bucket(-(-ctx // PAGE), pps)
                                + C // PAGE)]
                twin = jax.jit(lambda q, kp, vp, t, a: (
                    pca.kv_chunk_attention_reference(q, kp, vp, t, a,
                                                     window=window)))
                kern = jax.jit(lambda q, kp, vp, t, a, b: (
                    pca.kv_chunk_attention_kernel(q, kp, vp, t, a, b,
                                                  window=window)))
                row = [f"{name} window {window} ctx {ctx:5d} valid {n:3d}"]
                a, b = jnp.int32(ctx), jnp.int32(n)
                if aot:
                    with fa.force_compiled_lowering():
                        kern.lower(q, kp, vp, full, sd((), jnp.int32),
                                   sd((), jnp.int32)).compile()
                    print(" | ".join(row + ["compiles"]), flush=True)
                    continue
                flops = true_flops(ctx, n, nh, window)
                want = np.asarray(twin(q, kp, vp, tbl, a), np.float32)[:n]
                sec = timed(twin, q, kp, vp, tbl, a)
                row.append(f"twin {sec * 1e3:.3f} ms "
                           f"({100 * flops / PEAK / sec:.1f}%)")
                for rows in variants:
                    pca._CHUNK_ROWS = rows
                    pca._chunk_call.clear_cache()
                    kern = jax.jit(lambda q, kp, vp, t, a, b: (
                        pca.kv_chunk_attention_kernel(q, kp, vp, t, a, b,
                                                      window=window)))
                    got = np.asarray(kern(q, kp, vp, full, a, b),
                                     np.float32)[:n]
                    sec = timed(kern, q, kp, vp, full, a, b)
                    err = float(np.abs(got - want).max())
                    row.append(f"{rows} rows {sec * 1e3:.3f} ms "
                               f"({100 * flops / PEAK / sec:.1f}%, "
                               f"err {err:.4f})")
                    worst = max(worst, err)
                print(" | ".join(row), flush=True)
    return 1 if worst > 0.05 else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
