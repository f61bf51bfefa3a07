"""PR 28: the chunk program and the decode program of the Mellum cell, each
traced alone on the chip: device seconds by operation, a program call.

    python3 chipbench/tools/calls/pr28_05_profile.py
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np

from chipbench import harness, trace_reduce, weights
from chipbench.drivers import serve_arch

cell = harness.Cell("mellum2-12b-a2.5b.repo-context-overload")
jax, device, peaks = harness.start_jax(cell)
arch = serve_arch.arch_of(cell)
c, e = cell.config, cell.mix["engine"]
cfg = arch.program_config(c, e["max_len"])
params = jax.jit(lambda k: arch.weights(k, c))(weights.seed_key(1))
sched = serve_arch.build_engine(jax, cell, cfg, params, None)
eng = sched.engine
rng = np.random.default_rng(0)
TRACE = os.path.join(harness.OUT, "trace", "pr28_profile")


def traced(name, fn, calls):
    shutil.rmtree(TRACE, ignore_errors=True)
    os.makedirs(TRACE, exist_ok=True)
    jax.profiler.start_trace(TRACE)
    t0 = time.perf_counter()
    n = fn()
    jax.block_until_ready(eng.cache.pool)
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(TRACE, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    red = trace_reduce.reduce_file(path, 1)
    print(f"== {name}: {n} calls in {dt * 1e3 / n:.2f} ms a call on the host "
          f"clock; device busy {red['busy_s'] * 1e3 / n:.2f} ms a call",
          flush=True)
    for op, sec in red["device_ops"][:calls]:
        print(f"   {sec * 1e3 / n:8.3f} ms  {op}", flush=True)
    shutil.rmtree(TRACE, ignore_errors=True)


def prefill(tokens):
    h = sched.submit(rng.integers(3, c["vocab_size"], (tokens,)).astype(
        np.int32), max_new_tokens=4)
    sched.step()                         # admits
    n = 0
    while eng.pending_prefills():
        eng.prefill_step()
        n += 1
    return h, n


# meet every chunk program an 8,192-token prompt reaches, then trace one
h, _ = prefill(8192)
while not h.done:
    sched.step()
traced("chunk program, an 8,192-token prompt", lambda: prefill(8192)[1], 45)
# a full batch at mixed depths for the decode program
for _ in range(31):
    prefill(int(rng.integers(2048, 6000)))
for _ in range(3):
    sched.step()


def decode():
    for _ in range(30):
        sched.step()
    return 30


traced("decode step, 32 rows", decode, 45)
