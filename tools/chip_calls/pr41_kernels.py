"""The Mamba-1 kernels alone at AI21-Jamba2-3B's published widths (D 5,120
channels, N 16 states): device time of a call against the ``jnp`` twins and
the largest difference from them.

- the one-token state update of 256 rows of a pool of 26 x 256 row-layers
  (a decode step's call in one layer), at each (rows, lanes) block, and
  the bandwidth its least bytes (the rows' state read and written) give;
- the chunk scan of one row's 256-token chunk, at each lane block, against
  the twin (a ``lax.scan`` token by token) and the other form a chunk scan
  can take, sub-chunks of cumulative log-decay (quadratic in a sub-chunk of
  Q tokens, ``(Q, Q, N, D)`` weights, the state handed on between).

    python3 tools/chip_calls/pr41_kernels.py            # the chip
    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr41_kernels.py --aot

Exit 1 where a kernel's result is more than 1e-3 from its twin's.
"""
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas import mamba1  # noqa: E402

N, D, B, LAYERS, T = 16, 5120, 256, 26, 256
HBM = 819e9
UPDATE_BLOCKS = [(8, 5120), (8, 2560), (16, 2560), (16, 1280), (32, 640)]
SCAN_LANES = [512, 1024]


def subchunk_scan(dt, x, A, bm, cm, state, q=16):
    """The chunk scan as sub-chunks of cumulative log-decay."""
    steps = dt.shape[0] // q
    cut = lambda a: a.reshape((steps, q) + a.shape[1:])
    mask = jnp.tril(jnp.ones((q, q), bool))[:, :, None, None]

    def step(s, inp):
        d, xx, b, c = inp
        la = jnp.cumsum(d[:, None, :] * A[None], axis=0)       # (q, N, D)
        w = jnp.exp(jnp.where(mask, la[:, None] - la[None], -jnp.inf))
        u = (d * xx)[:, None, :] * b[:, :, None]                # (q, N, D)
        h = jnp.einsum("tsnd,snd->tnd", w, u) + jnp.exp(la) * s[None]
        return h[-1], jnp.einsum("tnd,tn->td", h, c)
    s, y = lax.scan(step, state, tuple(cut(a) for a in (dt, x, bm, cm)))
    return y.reshape(dt.shape), s


def timed(fn, *args, reps=20, rounds=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / reps)
    return best, out


def inputs(rows):
    k = jax.random.split(jax.random.key(0), 6)
    dt = jax.nn.softplus(jax.random.normal(k[0], (rows, D)) - 3.0)
    x = jax.random.normal(k[1], (rows, D))
    A = -jnp.exp(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))
                 )[:, None] * jnp.ones((1, D))
    bm, cm = (jax.random.normal(kk, (rows, N)) for kk in k[2:4])
    return dt, x, A, bm, cm


def main():
    aot = "--aot" in sys.argv
    bad = False
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        sds = lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=SingleDeviceSharding(dev))

        def run(fn, *args, donate=()):
            with fa.force_compiled_lowering():
                jax.jit(fn, donate_argnums=donate).lower(
                    *[sds(a) for a in args]).compile()
            return None, None
    else:
        def run(fn, *args, donate=()):
            if not donate:
                return timed(jax.jit(fn), *args)
            # a donated pool is consumed: each call is handed the last
            # call's result, as a decode step's layers hand it on
            f = jax.jit(fn, donate_argnums=donate)
            box = [args[0]]

            def step():
                out = f(box[0])
                box[0] = out[1]
                return out
            sec, _ = timed(step)
            return sec, None

    # ---- the one-token update ----
    dt, x, A, bm, cm = inputs(B)
    pool = jax.random.normal(jax.random.key(1), (LAYERS * B, N, D))
    base = 13 * B
    active = jnp.arange(B) % 7 != 3
    least = B * 2 * N * D * 4
    # the pool is donated and handed on, so that no call copies it whole
    twin = lambda p: mamba1.mamba1_state_update_reference(
        p, base, dt, x, A, bm, cm, active)
    if not aot:
        y0, p0 = jax.jit(twin)(pool)
        sec, _ = run(twin, jnp.copy(pool), donate=(0,))
        print(f"update twin: {1e3 * sec:.3f} ms, "
              f"{least / sec / 1e9:.0f} GB/s", flush=True)
    for rows, lanes in UPDATE_BLOCKS:
        fn = lambda p, rows=rows, lanes=lanes: \
            mamba1.mamba1_state_update_kernel(p, base, dt, x, A, bm, cm,
                                              active, rows, lanes)
        try:
            if not aot:
                out = jax.jit(fn)(pool)
            sec, _ = run(fn, jnp.copy(pool) if not aot else pool,
                         donate=(0,))
        except Exception as e:           # a block Mosaic refuses
            print(f"update rows {rows} lanes {lanes}: refused: "
                  f"{str(e).splitlines()[0][:160]}", flush=True)
            continue
        if aot:
            print(f"update rows {rows} lanes {lanes}: compiles", flush=True)
            continue
        dy = float(jnp.max(jnp.abs(out[0] - y0)))
        dp = float(jnp.max(jnp.abs(out[1] - p0)))
        del out
        bad |= max(dy, dp) > 1e-3
        print(f"update rows {rows} lanes {lanes}: {1e3 * sec:.3f} ms, "
              f"{least / sec / 1e9:.0f} GB/s of {HBM / 1e9:.0f} "
              f"({100 * least / sec / HBM:.1f}%), diff y {dy:.2e} "
              f"state {dp:.2e}", flush=True)

    # ---- the chunk scan ----
    dt, x, A, bm, cm = inputs(T)
    s0 = jax.random.normal(jax.random.key(2), (N, D))
    scan_least = 4.0 * (T * (3 * D + 2 * N) + 3 * N * D)
    forms = [("twin (lax.scan)", mamba1.mamba1_chunk_scan_reference),
             ("sub-chunks of 16", subchunk_scan)]
    forms += [(f"kernel lanes {db}",
               lambda *a, db=db: mamba1.mamba1_chunk_scan_kernel(*a, db))
              for db in SCAN_LANES]
    pool = None
    ref = None
    for name, fn in forms:
        try:
            sec, out = run(fn, dt, x, A, bm, cm, s0)
        except Exception as e:
            print(f"scan {name}: refused: {str(e).splitlines()[0][:160]}",
                  flush=True)
            continue
        if aot:
            print(f"scan {name}: compiles", flush=True)
            continue
        ref = out if ref is None else ref
        dy = float(jnp.max(jnp.abs(out[0] - ref[0])))
        ds = float(jnp.max(jnp.abs(out[1] - ref[1])))
        bad |= name.startswith("kernel") and max(dy, ds) > 1e-3
        print(f"scan {name}: {1e3 * sec:.3f} ms a layer's call, "
              f"{26 * 1e3 * sec:.2f} ms a chunk of 26 layers, least bytes "
              f"{100 * scan_least / sec / HBM:.1f}% of peak, diff y "
              f"{dy:.2e} state {ds:.2e}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
