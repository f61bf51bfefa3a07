"""One-off perf sweep for the bench config on the real chip.

Runs each variant in a subprocess (isolates OOM/compile failures), prints
tokens/s + MFU per variant. The parent never touches JAX, so each child
in turn is the one process on the chip. A tuning tool: it prints, and
changes no configuration.
"""
import json
import os
import subprocess
import sys

CHILD = r"""
import time, json, os, sys
import numpy as np
import jax, jax.numpy as jnp
from paddle_tpu.models import llama, train

variant = json.loads(os.environ["SWEEP_VARIANT"])
cfg = llama.LlamaConfig(
    vocab_size=32000, hidden_size=1536, intermediate_size=4096,
    num_layers=20, num_heads=12, num_kv_heads=12, max_seq_len=4096,
    dtype=jnp.bfloat16, remat=variant.get("remat", True),
    remat_policy=variant.get("policy", "nothing"),
    fused_kernels=variant.get("fused", "xla"))
batch = variant.get("batch", 4)
seq = 4096
step = train.make_train_step(cfg, seq_chunk=variant.get("seq_chunk", 512))
state = jax.jit(lambda k: train.init_train_state(k, cfg))(jax.random.key(0))
tokens = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg.vocab_size, (batch, seq)), jnp.int32)
state, m = step(state, tokens); float(m["loss"])
state, m = step(state, tokens); float(m["loss"])
iters = 10
t0 = time.perf_counter()
for _ in range(iters):
    state, m = step(state, tokens)
float(m["loss"])
dt = (time.perf_counter() - t0) / iters
tps = batch * seq / dt
mfu = tps * cfg.flops_per_token(seq) / 197e12
print("SWEEP_RESULT " + json.dumps(
    {"variant": variant, "tps": round(tps, 1), "mfu": round(mfu, 4)}))
"""

VARIANTS = [
    {"name": "base_b4_nothing", "batch": 4, "policy": "nothing"},
    {"name": "b4_attn", "batch": 4, "policy": "attn"},
    {"name": "b8_nothing", "batch": 8, "policy": "nothing"},
    {"name": "b8_attn", "batch": 8, "policy": "attn"},
    {"name": "b4_dots", "batch": 4, "policy": "dots"},
    {"name": "b4_chunk1024", "batch": 4, "policy": "nothing",
     "seq_chunk": 1024},
    {"name": "b4_pallas", "batch": 4, "policy": "nothing", "fused": "auto"},
    {"name": "b4_attn_pallas", "batch": 4, "policy": "attn",
     "fused": "auto"},
]


def main():
    names = sys.argv[1:]
    for v in VARIANTS:
        if names and v["name"] not in names:
            continue
        env = dict(os.environ)
        env["SWEEP_VARIANT"] = json.dumps(v)
        try:
            proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True,
                                  timeout=600)
            parsed = None
            for line in proc.stdout.splitlines():
                if line.startswith("SWEEP_RESULT"):
                    try:
                        # runtime log writes can interleave into stdout;
                        # a torn line must not abort the whole sweep
                        parsed = json.loads(line[len("SWEEP_RESULT "):])
                    except ValueError:
                        continue
                    print(line)
                    break
            if parsed is None:
                tail = " | ".join(proc.stdout.strip().splitlines()[-3:])
                print(f"SWEEP_FAIL {v['name']}: {tail[-300:]}")
        except subprocess.TimeoutExpired:
            print(f"SWEEP_TIMEOUT {v['name']}")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
