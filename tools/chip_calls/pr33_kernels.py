"""PR 33: the three flash kernels alone on the chip, at the train cell's
call (q [4,4096,16,128], k/v [4,4096,2,128], bf16, causal).

    python3 tools/chip_calls/pr33_kernels.py <tag> [bq,bk,bq_bwd,bk_bwd ...]

run from the root of the tree to measure (the parent's checkout or this
one). For each block setting: wall time of forward+backward over 20 calls,
then five traced calls reduced with chipbench.trace_reduce to seconds a
call of every device operation (the benchmark's own reduction), and the
largest error against the float32 jnp attention. --rehearsal: tiny shape,
interpret mode, no trace (CPU)."""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.getcwd())
from paddle_tpu.models import llama  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from chipbench import trace_reduce  # noqa: E402


def main():
    args = [a for a in sys.argv[1:] if a != "--rehearsal"]
    rehearsal = "--rehearsal" in sys.argv
    tag, settings = args[0], args[1:] or ["0,0,0,0"]
    b, s, h, hk, d = (1, 256, 4, 2, 64) if rehearsal else (4, 4096, 16, 2, 128)
    if rehearsal:
        fa.set_interpret(True)
    ks = jax.random.split(jax.random.key(33), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, s, h, d), dt)
    k = jax.random.normal(ks[1], (b, s, hk, d), dt)
    v = jax.random.normal(ks[2], (b, s, hk, d), dt)
    w = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

    def ref_loss(q, k, v, w):
        o = llama._attention_jnp(q, k, v, True)
        return (o.astype(jnp.float32) * w).sum(), o
    (_, ro), rg = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(
        *(x.astype(jnp.float32) for x in (q, k, v)), w)

    for setting in settings:
        bq, bk, bqb, bkb = (int(x) or None for x in setting.split(","))

        def loss(q, k, v, w):
            o = fa.flash_attention(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, block_q_bwd=bqb,
                                   block_k_bwd=bkb)
            return (o.astype(jnp.float32) * w).sum(), o
        f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
        fwd = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk))
        t0 = time.time()
        (_, o), g = jax.block_until_ready(f(q, k, v, w))
        jax.block_until_ready(fwd(q, k, v))
        compile_s = time.time() - t0
        err = {n: float(jnp.abs(a.astype(jnp.float32) - r).max()
                        / jnp.abs(r).max())
               for n, a, r in zip(("out", "dq", "dk", "dv"), (o,) + g,
                                  (ro,) + rg)}
        line = {"tag": tag, "blocks": setting, "compile_s": round(compile_s, 2),
                "rel_err": {n: float("%.3g" % e) for n, e in err.items()}}
        if not rehearsal:
            for name, fn, a in (("fwd_bwd_ms", f, (q, k, v, w)),
                                ("fwd_ms", fwd, (q, k, v))):
                t0 = time.time()
                for _ in range(20):
                    out = fn(*a)
                jax.block_until_ready(out)
                line[name] = round((time.time() - t0) / 20 * 1e3, 3)
            tdir = f"chiprun_out/pr33/trace_{tag}_{setting.replace(',', '_')}"
            with jax.profiler.trace(tdir):
                for _ in range(5):
                    out = f(q, k, v, w)
                jax.block_until_ready(out)
            paths = [os.path.join(r, n) for r, _, ns in os.walk(tdir)
                     for n in ns if n.endswith(".xplane.pb")]
            red = trace_reduce.reduce_file(paths[0], 1)
            line["busy_ms_a_call"] = round(red["busy_s"] / 5 * 1e3, 3)
            line["ops_ms_a_call"] = [
                [trace_reduce.short_name(n)[:60], round(sec / 5 * 1e3, 3),
                 red["counts"][n] / 5]
                for n, sec in sorted(red["ops"].items(),
                                     key=lambda kv: -kv[1])[:8]]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
