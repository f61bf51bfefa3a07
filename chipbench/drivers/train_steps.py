"""The training driver: the step of ``train.make_train_step`` on seeded
token batches, on one chip or under a (dp, fsdp, tp) mesh.

Set-up builds one object, the jitted step with its state, drives it from the
seed through its first ``check_steps`` steps by the window's own call and
feed, reads what the comparison needs from the state on the way, and hands
the same state to the window. The window runs whole steps until ``--seconds``
have passed; its rate is all their tokens over all the time they took.
"""
from __future__ import annotations

import functools
import gc
import statistics
import time
from typing import Dict, List, Optional

import numpy as np

from .. import harness, weights


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        plant: Optional[str] = None) -> int:
    jax, device, peaks = harness.start_jax(cell)
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.models import train
    from ..reference import decoder
    meter = harness.CompileMeter(jax)
    job, c = cell.mix, cell.config
    opt = job["optimizer"]
    B, S, vocab = job["batch"], job["seq_len"], c["vocab_size"]
    cfg = harness.program_config(c, S, job["remat"])
    mesh = None
    if job["mesh"]:
        shape = tuple(job["mesh"].values())
        mesh = Mesh(np.asarray(jax.devices()[:cell.chips]).reshape(shape),
                    tuple(job["mesh"].keys()))
    step = train.make_train_step(
        cfg, mesh, lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        seq_chunk=job["seq_chunk"])
    key_w, key_t = weights.seed_key(seed, 0), weights.seed_key(seed, 1)
    if mesh is None:
        st_sh = p_sh = tok_sh = None
    else:
        st_sh = train.state_shardings(mesh, cfg)
        p_sh = st_sh.params
        tok_sh = NamedSharding(mesh, P(("dp", "fsdp")))
    make_params = jax.jit(lambda k: weights.make(k, c), out_shardings=p_sh)
    feed = jax.jit(lambda k, i: weights.batch(k, i, B, S, vocab),
                   out_shardings=tok_sh)

    def tokens_of(i):
        t = feed(key_t, i)
        if plant == "half_batch":
            # half of the batch left out, the mean taken over the rest: the
            # second half of the rows repeats the first
            t = jnp.concatenate([t[:B // 2], t[:B // 2]], 0)
            if tok_sh is not None:
                t = jax.device_put(t, tok_sh)
        return t

    def init_state(params):
        f32 = lambda t: jax.tree.map(lambda p: jnp.array(p, jnp.float32), t)
        zeros = lambda t: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), t)
        return train.TrainState(jnp.zeros((), jnp.int32), params, f32(params),
                                zeros(params), zeros(params))
    init_state = jax.jit(init_state, out_shardings=st_sh)

    norms = jax.jit(decoder.leaf_norms)
    diff_norms = jax.jit(lambda a, b: decoder.leaf_norms(
        jax.tree.map(lambda x, y: x - y.astype(jnp.float32), a, b)))

    state = init_state(make_params(key_w))
    annotate = jax.profiler.TraceAnnotation

    def one(state, i):
        t0 = time.perf_counter()
        with annotate("chipbench.train_step"):
            new, metrics = step(state, tokens_of(i))
            loss = float(metrics["loss"])        # waits for the step
        if plant == "state_unchanged":
            jax.block_until_ready(new)
            return init_state(make_params(key_w)), loss, t0, time.perf_counter()
        return new, loss, t0, time.perf_counter()

    # ---- the first steps, which the reference follows ----
    n_check = int(job["check_steps"])
    got: Dict = {"loss": []}
    for i in range(n_check):
        state, loss, _, _ = one(state, i)
        got["loss"].append(loss)
        if i == 0:
            # m after one step is (1 - b1) times the gradient the optimizer got
            got["grad"] = {k: float(v) / (1 - opt["b1"])
                           for k, v in norms(state.m).items()}
    got["update"] = {k: float(v) for k, v in
                     diff_norms(state.master, make_params(key_w)).items()}
    jax.block_until_ready(state)
    setup_s = harness.process_age_s()
    requests0 = meter.requests

    # ---- the measured window: whole steps until the time is up ----
    # with --trace 1 the profiler runs over the window's last ``trace_steps``
    # steps, so that what it shows is the window's own; it is stopped, and
    # writes its trace, after the last step has ended
    tw = harness.TraceWindow(jax, cell, trace)
    gc.collect()
    gc.disable()        # no collector pause lands in a step
    t_open = time.perf_counter()
    ends: List[float] = []
    step_s: List[float] = []
    i = n_check
    while True:
        state, loss, t0, t1 = one(state, i)
        ends.append(t1)
        step_s.append(t1 - t0)
        i += 1
        if t1 - t_open >= seconds:
            break
        if trace and not tw.open:
            typical = statistics.median(step_s)
            # a second's allowance for the profiler's own start
            if (seconds - (t1 - t_open)
                    <= int(job["trace_steps"]) * typical + 1.0):
                tw.start()
    tw.stop()
    gc.enable()
    in_window = meter.requests - requests0
    elapsed = ends[-1] - t_open
    n_steps = len(ends)
    mem_peak = harness.memory_peak_bytes(jax, cell.chips)
    tokens_per_s_chip = n_steps * B * S / elapsed / cell.chips
    e2e = {"setup_s": setup_s, "train_tokens_per_s_per_chip": tokens_per_s_chip}
    failed = 0 if np.isfinite(loss) else 1
    print(f"chipbench: programs first met in window: {in_window}; set-up "
          f"compile seconds {meter.compile_s:.1f}; steps {n_steps}; last loss "
          f"{loss:.4f}", flush=True)

    # ---- free the state, then the reference ----
    del state
    gc.collect()
    reduced = tw.reduce(cell.chips)
    ref = reference(jax, cell, key_w, key_t, n_check, mesh)
    if plant == "control":
        # the reference put in the program's place, in 8-bit integers
        got = reference(jax, cell, key_w, key_t, n_check, mesh, quant="int8")
    compared = compare(got, ref, job["limits"])
    correct = all(x["value"] <= x["limit"] for x in compared) and not failed
    step_s = np.asarray(step_s)
    record = {"cell": cell, "peaks": peaks, "config": c, "mix": job,
              "trace": reduced, "step_s": [float(x) for x in step_s],
              # over the steps' own time: in a traced run the window also
              # holds the profiler's start, which is no work of the step's
              "tokens_per_s_per_chip":
                  n_steps * B * S / float(step_s.sum()) / cell.chips,
              "programs_in_window": in_window}
    per_layer = harness.read_per_layer(cell, record) if trace else {}
    device["memory_peak_bytes"] = mem_peak
    extra = {"steps": n_steps, "window_s": elapsed, "first_losses": got["loss"],
             "reference_losses": ref["loss"], "leaf_gaps": leaf_gaps(got, ref),
             # where a run reads far off, these say whether a few steps stalled
             "step_ms_p50": 1e3 * float(np.median(step_s)),
             "steps_over_1_1x": int(np.sum(step_s > 1.1 * np.median(step_s))),
             "slowest_steps_ms": [[int(j), 1e3 * float(step_s[j])]
                                  for j in np.argsort(step_s)[::-1][:3]]}
    if plant:
        extra["planted"] = plant
    return harness.emit(cell, trace, device, correct, n_steps, failed, e2e,
                        per_layer, reduced, compared, extra)


def reference(jax, cell: harness.Cell, key_w, key_t, n_steps: int, mesh,
              quant: Optional[str] = None, half_batch: bool = False) -> Dict:
    """The plain float32 reference over the same first steps: its losses, the
    leaf norms of the first gradient as the optimizer gets it, and of the
    parameters' change after the steps."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..reference import decoder
    job, c = cell.mix, cell.config
    opt = job["optimizer"]
    B, S, vocab = job["batch"], job["seq_len"], c["vocab_size"]
    rows = int(job["reference_rows"])
    sh = None
    if mesh is not None:
        # the reference's float32 state is spread over the same chips, each
        # leaf along its largest axis, and the rows over the chips; the
        # arithmetic is still the plain one
        flat = jax.sharding.Mesh(mesh.devices.reshape(-1), ("all",))

        def leaf_sharding(shape):
            ax = int(np.argmax(shape))
            if shape[ax] % flat.size:
                return NamedSharding(flat, P())
            return NamedSharding(flat, P(*[("all" if i == ax else None)
                                           for i in range(len(shape))]))
        sh = jax.tree.map(lambda s: leaf_sharding(s[0]), weights.shapes(c),
                          is_leaf=lambda x: isinstance(x, tuple)
                          and isinstance(x[0], tuple))
    make_p0 = jax.jit(lambda k: jax.tree.map(
        lambda w: w.astype(jnp.float32), weights.make(k, c)), out_shardings=sh)
    p0 = make_p0(key_w)

    def lg(p, t):
        return decoder.loss_and_grads(p, t, c, quant, rows=rows)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def first(p, m, v, t):
        lv, g = lg(p, t)
        g = decoder.clip(g, opt["grad_clip"])
        p, m, v = decoder.adamw(p, g, m, v, 0.0, opt)
        return p, m, v, lv, decoder.leaf_norms(g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def later(p, m, v, t, i):
        lv, g = lg(p, t)
        p, m, v = decoder.adamw(p, decoder.clip(g, opt["grad_clip"]), m, v, i, opt)
        return p, m, v, lv

    @jax.jit
    def toks(i):
        t = weights.batch(key_t, i, B, S, vocab)
        return jnp.concatenate([t[:B // 2], t[:B // 2]], 0) if half_batch else t
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    p, m, v = p0, zeros(p0), zeros(p0)
    del p0                    # donated to the first step; made again below
    out: Dict = {"loss": []}
    for i in range(n_steps):
        t = toks(i)
        if i == 0:
            p, m, v, lv, gn = first(p, m, v, t)
            out["grad"] = {k: float(x) for k, x in gn.items()}
        else:
            p, m, v, lv = later(p, m, v, t, float(i))
        out["loss"].append(float(lv))
    d = jax.jit(lambda a, b: decoder.leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(p, make_p0(key_w))
    out["update"] = {k: float(x) for k, x in d.items()}
    return out


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                   skip_below: float = 0.0) -> float:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Leaves whose reference norm is under
    ``skip_below`` times the median's are left out."""
    med = statistics.median(ref.values())
    worst = 0.0
    for k, r in ref.items():
        if r < skip_below * med:
            continue
        worst = max(worst, abs(got[k] - r) / max(r, med))
    return worst


def leaf_gaps(got: Dict, ref: Dict) -> Dict:
    """Every leaf's own gap, for the record a refused run leaves."""
    out = {}
    for what in ("grad", "update"):
        med = statistics.median(ref[what].values())
        out[what] = {k: round((got[what][k] - r) / max(r, med), 5)
                     for k, r in ref[what].items()}
    return out


def compare(got: Dict, ref: Dict, limits: Dict) -> List[Dict]:
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    # leaves whose first gradient is nought to rounding in the reference move
    # under Adam by round-off alone: they are left out of the change by the
    # rule on the reference's gradient, not by name
    med_g = statistics.median(ref["grad"].values())
    moved = {k for k, g in ref["grad"].items() if g >= 1e-3 * med_g}
    upd = worst_leaf_gap({k: v for k, v in got["update"].items() if k in moved},
                         {k: v for k, v in ref["update"].items() if k in moved})
    return [{"name": "loss_gap", "value": loss_gap, "limit": limits["loss_gap"]},
            {"name": "grad_norm_gap",
             "value": worst_leaf_gap(got["grad"], ref["grad"]),
             "limit": limits["grad_norm_gap"]},
            {"name": "update_norm_gap", "value": upd,
             "limit": limits["update_norm_gap"]}]
