"""Measured component breakdown of the bench train step on a chip.

Times separately-jitted slices of the headline config (660M Llama,
batch 4 x seq 4096), then prints a markdown table of step-time shares.
No chip is an error. One-off tuning/analysis tool — feeds
PERF_NOTES.md (the MFU ceiling accounting), not the driver flow.

  python tools/step_profile.py            # on the real chip
"""
import dataclasses
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, ".")


def timed(fn, fence, iters=6):
    fence(fn())              # compile + warm
    fence(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fence(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def fence_tree(tree):
    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(jnp.sum(leaf[..., 0].astype(jnp.float32)))


def main():
    from paddle_tpu.models import llama, train

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"step_profile: no TPU (platform "
                         f"{dev.platform!r}); nothing to measure")
    cfg = llama.LlamaConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=4096,
        num_layers=20, num_heads=12, num_kv_heads=12,
        max_seq_len=4096, dtype=jnp.bfloat16, remat=True)
    batch, seq, chunk = 4, 4096, 512

    step = train.make_train_step(cfg, seq_chunk=chunk)
    state = jax.jit(lambda k: train.init_train_state(k, cfg))(
        jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)

    # 1) full train step (fwd + bwd + AdamW; state is donated, so thread
    # it through a holder)
    hold = {"s": state}

    def full():
        hold["s"], m = step(hold["s"], tokens)
        return m
    t_full = timed(full, lambda m: float(m["loss"]))
    state = jax.jit(lambda k: train.init_train_state(k, cfg))(
        jax.random.key(0))

    # 2) grads-only (fwd + bwd, no clip/optimizer)
    def loss(p, t):
        return llama.loss_fn(p, t, cfg, None, seq_chunk=chunk)
    gradfn = jax.jit(jax.grad(loss))
    t_grad = timed(lambda: gradfn(state.params, tokens), fence_tree)

    # 3) fwd-only loss
    lossfn = jax.jit(loss)
    t_fwd = timed(lambda: lossfn(state.params, tokens), float)

    # 4) embed + final-norm + logits + CE alone: the same program with
    # zero decoder layers (isolates the 32000-vocab head + embedding)
    cfg0 = dataclasses.replace(cfg, num_layers=0)
    p0 = jax.jit(lambda k: llama.init_params(k, cfg0))(jax.random.key(0))
    headfn = jax.jit(lambda p, t: llama.loss_fn(p, t, cfg0, None,
                                                seq_chunk=chunk))
    t_head = timed(lambda: headfn(p0, tokens), float)
    headgrad = jax.jit(jax.grad(lambda p, t: llama.loss_fn(
        p, t, cfg0, None, seq_chunk=chunk)))
    t_headg = timed(lambda: headgrad(p0, tokens), fence_tree)

    # 5) clip + AdamW update alone over real-shaped grads, at the train
    # step's OWN default hyperparameters (read, not copied — so this
    # cannot drift from the math the full step actually runs)
    import inspect
    hp = {k: p.default for k, p in
          inspect.signature(train.make_train_step).parameters.items()
          if p.default is not inspect.Parameter.empty}
    grads = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32),
                         state.params)

    def optonly(state, grads):
        gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * scale, grads)

        def upd(g, p32, m, v):
            return train._adamw(g, p32, m, v, state.step, hp["lr"],
                                hp["b1"], hp["b2"], hp["eps"],
                                hp["weight_decay"])
        out = jax.tree.map(upd, grads, state.master, state.m, state.v)
        return jax.tree.map(lambda t: t[0], out,
                            is_leaf=lambda x: isinstance(x, tuple))
    optfn = jax.jit(optonly)
    t_opt = timed(lambda: optfn(state, grads), fence_tree)

    rows = [
        ("full step (fwd+bwd+clip+AdamW)", t_full),
        ("fwd+bwd only", t_grad),
        ("fwd only", t_fwd),
        ("embed+head fwd (0-layer model)", t_head),
        ("embed+head fwd+bwd (0-layer model)", t_headg),
        ("clip+AdamW update only", t_opt),
    ]
    print("\n| slice | ms | share of full |")
    print("|---|---|---|")
    for name, t in rows:
        print(f"| {name} | {t * 1e3:.0f} | {100 * t / t_full:.0f}% |")
    toks = batch * seq
    print(f"\ntokens/s full step: {toks / t_full:,.0f}")
    print(f"decoder-layers fwd (fwd - head): "
          f"{1e3 * (t_fwd - t_head):.0f} ms; bwd overhead "
          f"(grad - fwd): {1e3 * (t_grad - t_fwd):.0f} ms; "
          f"opt by subtraction (full - grad): "
          f"{1e3 * (t_full - t_grad):.0f} ms")

    # profiler summary tables (host spans + device op/category tables
    # from the jax.profiler trace) — the per-XLA-op ranking that feeds
    # the MFU residual accounting in PERF_NOTES.md
    profiled_summary(step, hold["s"], tokens)


def profiled_summary(step, state, tokens, record_steps=2):
    """Run the fused step under the Profiler with a device trace and
    print Profiler.summary()'s ranked tables."""
    import os
    import tempfile
    import paddle_tpu.profiler as profiler

    os.environ["PADDLE_TPU_DEVICE_TRACE"] = "1"
    os.environ.setdefault("PADDLE_TPU_DEVICE_TRACE_DIR",
                          tempfile.mkdtemp(prefix="pt_trace_"))
    hold = {"s": state}
    prof = profiler.Profiler(scheduler=(1, 1 + record_steps))
    prof.start()
    for _ in range(1 + record_steps):
        with profiler.RecordEvent("fused_train_step", "Operator"):
            hold["s"], m = step(hold["s"], tokens)
            jax.block_until_ready(m["loss"])
        prof.step()
    prof.stop()
    print()
    print(prof.summary(time_unit="ms"))


if __name__ == "__main__":
    main()
