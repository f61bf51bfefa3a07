"""Scratch: record one small profiler trace on the chip (a tiny train step
through the flash kernels, a few scheduler steps through the paged kernel)
and dump its planes, lines, event names and stats, so the reduction in
``chipbench/trace_reduce.py`` is written against what the profiler writes.
Writes ``chiprun_out/probe/``."""
import glob
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.getcwd())
from paddle_tpu.inference.predictor import ContinuousBatchingEngine
from paddle_tpu.models import llama, train
from paddle_tpu.serving import ServingScheduler


def main():
    out = "chiprun_out/probe"
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print(dev.platform, dev.device_kind, len(jax.devices()))
    cfg = llama.LlamaConfig(vocab_size=1024, hidden_size=256,
                            intermediate_size=512, num_layers=2,
                            num_heads=2, num_kv_heads=1, head_dim=128,
                            max_seq_len=512, dtype=jnp.bfloat16, remat=True)
    state = jax.jit(lambda k: train.init_train_state(k, cfg))(jax.random.key(0))
    step = train.make_train_step(cfg, seq_chunk=128)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 1024, (2, 512)), jnp.int32)
    state, m = step(state, toks)
    jax.block_until_ready(m)
    params = jax.jit(lambda k: llama.init_params(k, cfg))(jax.random.key(1))
    eng = ContinuousBatchingEngine(params, cfg, max_batch=4, page_size=64,
                                   max_len=512, num_pages=17, prefill_chunk=128)
    sched = ServingScheduler(eng)
    rng = np.random.default_rng(1)
    hs = [sched.submit(rng.integers(3, 1024, (100 + 60 * i,)).astype(np.int32),
                       max_new_tokens=12) for i in range(3)]
    for _ in range(6):
        sched.step()
    jax.profiler.start_trace(out)
    for i in range(2):
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            state, m = step(state, toks)
            jax.block_until_ready(m)
    for i in range(4):
        with jax.profiler.TraceAnnotation("chipbench.sched_step"):
            sched.step()
    jax.profiler.stop_trace()
    path = glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0]
    print(path, os.path.getsize(path))
    pd = jax.profiler.ProfileData.from_file(path)
    dump = []
    for plane in pd.planes:
        pl = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names.setdefault(e.name, [0, 0.0, None])
                names[e.name][0] += 1
                names[e.name][1] += e.duration_ns
                if names[e.name][2] is None:
                    names[e.name][2] = {k: str(v)[:200] for k, v in e.stats}
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:40]
            pl["lines"].append({"line": line.name, "n_events": len(evs),
                                "first_start_ns": evs[0].start_ns if evs else None,
                                "top": top})
        dump.append(pl)
    with open(out + "/dump.json", "w") as f:
        json.dump(dump, f, indent=1)
    for pl in dump:
        print("PLANE", pl["plane"])
        for ln in pl["lines"]:
            print("  LINE", ln["line"], ln["n_events"])
            for name, (n, dur, stats) in ln["top"][:8]:
                print("     ", name[:70], n, dur, list((stats or {}).keys())[:8])
    import shutil
    shutil.copy(path, out + "/small.xplane.pb")


if __name__ == "__main__":
    main()
