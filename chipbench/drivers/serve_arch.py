"""The serving driver for an architecture ``harness.program_config``,
``weights.make`` and ``reference/decoder.py`` cannot describe: the same
loop as ``serve_open_loop.py`` (its ``Live``, ``Loop``, ``warm``, ``_turn``
and ``_sample`` are imported, not copied), with the model's three hooks
taken from ``chipbench/archs/<model_type>.py`` by the configuration file's
``model_type``: ``program_config(c, max_len)``, ``weights(key, c)`` and
``reference`` (a module with ``hidden(params, tokens, c)`` for one sequence
and ``logits(params, rows, c)``). A traffic file names this driver by
``"kind": "serve_arch"``.

Each step is also stamped with the engine's expert counters as they stood
after it (``readers/moe.py``), and with the context the step's prefill
chunk stood on.

``lengths_seed`` in a traffic file: every ``--seed`` offers the lengths of
the generator's stream at THAT seed, in its order; ``--seed`` draws the
token ids (and the weights). ``traffic/gen.py`` permutes a block's lengths
by ``--seed``, which keeps what a block offers and varies what a window
cut out of the stream holds: with prompts of 8 to 32 prefill chunks, each
at two thirds of a decode step, the share of steps that carry a chunk moved
by +-4% between seeds and ``serve_tokens_per_s`` by 2.5-5% (PERF.md, PR 28).
``gen.stagger`` fixes which answer length opens the window at which depth
for the same reason; this fixes the rest of the order. The order is noise
to a comparison of two programs, which run the same seed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
from typing import Dict, List, Optional

import numpy as np

from .. import harness, weights
from ..traffic import gen
from .serve_open_loop import (Live, Loop, _alter_tokens, _sample, _turn,
                              latencies, percentile, warm)

MOE_COUNTERS = ("moe_routed_items_total", "moe_experts_hit_total",
                "moe_max_expert_load_total", "moe_layer_steps_total")


def stream(mix: Dict, seed: int, vocab: int):
    """``gen.stream``; with ``lengths_seed`` its blocks at that seed, their
    prompts drawn again from ``seed`` (module docstring)."""
    if "lengths_seed" not in mix:
        yield from gen.stream(mix, seed, vocab)
        return
    if mix["prefix_tokens"]:
        raise ValueError("lengths_seed: for mixes without a system prompt")
    for b, block in enumerate(gen.stream(mix, int(mix["lengths_seed"]),
                                         vocab)):
        rng = np.random.default_rng([int(seed), 8, b])
        yield [dataclasses.replace(r, prompt=rng.integers(
            3, vocab, (r.prompt_tokens,)).astype(np.int32)) for r in block]


def arch_of(cell: harness.Cell):
    return importlib.import_module(
        f"chipbench.archs.{cell.config['model_type']}")


class ArchLoop(Loop):
    """``Loop`` whose step records carry the expert counters and the
    prefill's context."""

    def step(self) -> bool:
        # tokens of its sequence (the prompt, and on a resume the answer so
        # far less its last token) that each pending prefill has behind it
        pending = {s: req.prompt.shape[1] + max(len(req.tokens) - 1, 0) - left
                   for s, (req, left) in self.eng.pending_prefills().items()}
        more = super().step()
        counters = self.eng.spans.snapshot()
        if MOE_COUNTERS[0] in counters:
            self.steps[-1]["moe"] = [counters.get(k, 0) for k in MOE_COUNTERS]
        plan = self.sched.last_plan
        if plan is not None and plan.prefills:
            self.steps[-1]["prefill_ctx"] = pending.get(plan.prefills[0][0], 0)
        return more


def build_engine(jax, cell: harness.Cell, cfg, params, plant: Optional[str]):
    """The scheduler and engine, built with the keyword set of
    ``serve_open_loop.build_engine``."""
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import ServingScheduler
    e = cell.mix["engine"]
    kw = dict(max_batch=e["max_batch"], page_size=e["page_size"],
              num_pages=e["num_pages"], max_len=e["max_len"],
              prefill_chunk=e["prefill_chunk"],
              enable_prefix_cache=e["enable_prefix_cache"],
              kv_cache_dtype=e["kv_cache_dtype"],
              temperature=e["temperature"],
              use_kernel=True if cell.rehearsal else None)
    if plant == "control":
        # the program's own lower-precision path, switched on: 8-bit
        # weights (attention and head; the program leaves expert stacks as
        # they are) and an 8-bit KV pool
        kw.update(weight_bits=8, kv_cache_dtype="int8")
    eng = ContinuousBatchingEngine(params, cfg, **kw)
    return ServingScheduler(eng)


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        plant: Optional[str] = None) -> int:
    jax, device, peaks = harness.start_jax(cell)
    arch = arch_of(cell)
    meter = harness.CompileMeter(jax)
    # where set-up's compile seconds go: tracing, lowering, or the compiler
    # (on a cache hit: loading the executable)
    compile_by_event: Dict[str, float] = {}
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compile_by_event.__setitem__(
            event, compile_by_event.get(event, 0.0) + duration)
        if event.startswith("/jax/") else None)
    mix, c = cell.mix, cell.config
    vocab = c["vocab_size"]
    # first of all: a program that cannot describe this architecture (the
    # one before it was added) fails here, before any weight is made
    cfg = arch.program_config(c, mix["engine"]["max_len"])
    params = jax.jit(lambda k: arch.weights(k, c))(weights.seed_key(seed))
    sched = build_engine(jax, cell, cfg, params, plant)
    tw = harness.TraceWindow(jax, cell, trace)
    loop = ArchLoop(sched, annotate=jax.profiler.TraceAnnotation)
    if plant == "token_altered":
        _alter_tokens(sched.engine, vocab)
    warm(loop, cell, seed)

    blocks = stream(mix, seed, vocab)
    backlog = mix["arrivals"]["kind"] == "backlog"
    first = next(blocks)
    # ---- the start state, still set-up ----
    if mix["start"] == "staggered_block":
        for r in gen.stagger(first, seed, vocab):
            loop.submit(Live(r, 0.0, counted=False))
        sched.step()                       # admits the block
        while sched.engine.pending_prefills():   # prefill only: no decode
            sched.engine.prefill_step()
        for lv in loop.open:
            lv.token_t = [loop.clock()] * len(lv.handle.tokens)
            lv.admitted = loop.clock()
        pending: List[Live] = []
    elif mix["start"] == "preroll_block":
        origin = loop.clock()              # the stream's time 0
        pending = [Live(r, origin + r.due_s, counted=False) for r in first]
        t_open = origin + first[-1].due_s  # the window opens as block 0 ends
        while loop.clock() < t_open:
            _turn(loop, pending, t_open)
    else:
        raise ValueError(mix["start"])
    jax.block_until_ready(sched.engine.cache.pool)
    requests0, hits0 = meter.requests, meter.hits
    setup_s = harness.process_age_s()
    pre_stats = sched.stats()

    # ---- the measured window ----
    if backlog:
        t_open = loop.clock()
    t_close = t_open + seconds
    t_trace = t_close - min(seconds, float(mix["trace_seconds"]))
    step0 = len(loop.steps)
    trace_steps = None
    gc.collect()
    gc.disable()        # no collector pause lands on a request's latency
    while True:
        now = loop.clock()
        if now >= t_close:
            break
        if trace and trace_steps is None and now >= t_trace:
            tw.start()
            trace_steps = len(loop.steps)
        if backlog:
            if (sched.load_stats()["queued_total"]
                    < mix["arrivals"]["min_waiting"]):
                for r in next(blocks):
                    loop.submit(Live(r, t_open, counted=True))
        else:
            while not pending or pending[-1].due < t_close:
                pending += [Live(r, origin + r.due_s, counted=True)
                            for r in next(blocks)]
        _turn(loop, pending, t_close)
    tw.stop()
    gc.enable()
    if trace_steps is not None:
        trace_steps = (trace_steps, len(loop.steps))
    t_end = max(loop.steps[-1]["t1"], t_close) if backlog else t_close
    step1 = len(loop.steps)
    in_window = meter.requests - requests0, meter.hits - hits0
    # ---- after the close: every request due inside gets its first token ----
    due_in = [lv for lv in loop.lives if lv.counted and lv.due < t_close]
    unsent = [lv for lv in pending if lv.due < t_close]
    t_give_up = loop.clock() + float(mix["drain_s"])
    while (not backlog and loop.clock() < t_give_up
           and (unsent or any(not lv.token_t for lv in due_in))):
        _turn(loop, unsent, t_give_up)
        due_in = [lv for lv in loop.lives if lv.counted and lv.due < t_close]
    post_stats = sched.stats()
    mem_peak = harness.memory_peak_bytes(jax, cell.chips)

    # ---- end-to-end ----
    window = loop.steps[step0:step1]
    e2e: Dict[str, float] = {"setup_s": setup_s}
    if backlog:
        tokens = sum(s["tokens"] for s in window)
        e2e["serve_tokens_per_s"] = tokens / (t_end - t_open)
        attempted = len([lv for lv in loop.lives
                         if lv.token_t and lv.token_t[-1] > t_open])
        failed = 0
    else:
        worst = t_give_up - t_open
        attempted, failed, ttft, gaps = latencies(
            due_in, unsent, loop.lives, t_open, t_close, worst)
        e2e["itl_p95_ms"] = 1e3 * percentile(gaps, 95)

    # ---- free the program's state, then the comparison ----
    finished = [lv for lv in loop.lives if lv.handle.done and lv.token_t
                and t_open <= lv.token_t[-1] < max(t_end, t_close)]
    unfinished = [lv for lv in loop.lives if lv.counted and not lv.handle.done
                  and len(lv.handle.tokens) >= 32]
    sample = (_sample(finished, seed, int(mix["check_requests"]))
              + _sample(unfinished, seed, int(mix.get("check_unfinished", 0))))
    served = [(np.asarray(lv.handle.prompt[0]), np.asarray(lv.handle.tokens))
              for lv in sample]
    checked_in_window_prefills = sum(1 for lv in sample if lv.counted)
    record = {
        "cell": cell, "peaks": peaks, "config": c, "mix": mix,
        "steps": loop.steps, "window_steps": (step0, step1),
        "trace_steps": trace_steps, "t_open": t_open, "t_close": t_close,
        "lives": [{"due": lv.due, "sent": lv.sent, "admitted": lv.admitted,
                   "token_t": lv.token_t, "counted": lv.counted,
                   "prompt_tokens": int(lv.handle.prompt.shape[1]),
                   "max_new": int(lv.req.max_new)}
                  for lv in loop.lives],
        "stats_open": pre_stats, "stats_close": post_stats,
        "ttft_s": [] if backlog else ttft,
        "programs_in_window": in_window[0],
        "cache_loads_in_window": in_window[1]}
    print(f"chipbench: programs first met in window: {in_window[0]} "
          f"(of which loaded from the cache: {in_window[1]}); set-up "
          f"compile requests {requests0}, cache hits {hits0}, "
          f"compile seconds {meter.compile_s:.1f}", flush=True)
    print("chipbench: seconds by JAX event: " + ", ".join(
        f"{k.rsplit('/', 1)[-1]} {v:.1f}"
        for k, v in sorted(compile_by_event.items(), key=lambda kv: -kv[1])[:8]),
        flush=True)
    steps_in_window, finished_in_window = len(window), len(finished)
    split = _split_by_program(loop.steps, step0, step1,
                              len(c.get("layer_types", [])[:c["num_hidden_layers"]]))
    print("chipbench: the window's steps by program: " + json.dumps(split),
          flush=True)
    for lv in loop.lives:
        lv.handle = None
    del loop, sched, sample, finished, unfinished, due_in
    gc.collect()
    reduced = tw.reduce(cell.chips)
    record["trace"] = reduced
    if reduced is not None:
        # the result line holds ten operations; a new architecture's first
        # traces are read further down than that
        for name, sec in reduced["device_ops"][:40]:
            print(f"chipbench: device op {sec:.4f} s {name}", flush=True)
    compared, widest = compare(jax, cell, arch, params, served)
    correct = bool(served) and all(x["value"] <= x["limit"] for x in compared)
    per_layer = harness.read_per_layer(cell, record) if trace else {}
    device["memory_peak_bytes"] = mem_peak
    extra = {"requests_sent": len(record["lives"]),
             "checked_requests": len(served),
             "checked_prefilled_in_window": checked_in_window_prefills,
             "checked_tokens": int(sum(t.size for _, t in served)),
             "max_logit_gap_not_compared": widest,
             "steps_in_window": steps_in_window,
             "finished_in_window": finished_in_window}
    if plant:
        extra["planted"] = plant
    return harness.emit(cell, trace, device, correct, attempted, failed, e2e,
                        per_layer, reduced, compared, extra)


def _split_by_program(steps, i0: int, i1: int, layers: int) -> Dict:
    """The window's steps with and without a prefill chunk: how many, their
    mean length, and the experts their programs reached a layer (a step
    with a chunk runs two programs: the mean is over both)."""
    out = {}
    for name, want in (("decode_only", False), ("with_chunk", True)):
        mine = [(steps[i - 1], s) for i, s in enumerate(steps[i0:i1], i0)
                if i > 0 and bool(s["prefill_width"]) == want and s["rows"]]
        if not mine:
            continue
        row = {"steps": len(mine),
               "ms": 1e3 * float(np.mean([s["t1"] - s["t0"] for _, s in mine])),
               # a pause of the host shows as one step far above the mean
               "ms_longest": 1e3 * max(s["t1"] - s["t0"] for _, s in mine),
               "rows": float(np.mean([s["rows"] for _, s in mine]))}
        if all("moe" in a and "moe" in b for a, b in mine):
            hit = sum(b["moe"][1] - a["moe"][1] for a, b in mine)
            runs = sum(b["moe"][3] - a["moe"][3] for a, b in mine)
            top = sum(b["moe"][2] - a["moe"][2] for a, b in mine)
            row.update(experts_hit_a_layer=hit / max(runs, 1),
                       max_load_a_layer=top / max(runs, 1),
                       programs_a_step=runs / max(layers * len(mine), 1))
        out[name] = row
    return out


def compare(jax, cell: harness.Cell, arch, params, served):
    """The architecture's reference once over each sampled prompt with its
    served tokens: how far below the reference's best logit each served
    token lies, its mean over the served tokens."""
    import jax.numpy as jnp
    ref = arch.reference
    c, mix = cell.config, cell.mix
    limit = mix["limits"]["mean_logit_gap"]
    if not served:
        return [{"name": "mean_logit_gap", "value": float("inf"),
                 "limit": limit}], float("inf")
    longest = mix["prefix_tokens"] + mix["tail_tokens"][1] + mix["output_tokens"][1]
    width = -(-longest // 256) * 256
    n_out = mix["output_tokens"][1]

    @jax.jit
    def gaps(params, row, toks, start, count):
        x = ref.hidden(params, row, c)
        at = jnp.clip(start + jnp.arange(n_out), 0, width - 1)
        lg = ref.logits(params, jnp.take(x, at, axis=0), c)
        g = jnp.max(lg, -1) - jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]
        return jnp.where(jnp.arange(n_out) < count, g, 0.0)

    worst, total, n = 0.0, 0.0, 0
    for prompt, tokens in served:
        row = np.zeros((width,), np.int32)
        seq = np.concatenate([prompt, tokens])
        row[:seq.size] = seq
        toks = np.zeros((n_out,), np.int32)
        toks[:tokens.size] = tokens
        g = np.asarray(gaps(params, jnp.asarray(row), jnp.asarray(toks),
                            prompt.size - 1, tokens.size))
        worst = max(worst, float(g.max()))
        total += float(g.sum())
        n += tokens.size
    return [{"name": "mean_logit_gap", "value": total / n,
             "limit": limit}], worst
