"""The serving driver: ``ServingScheduler.submit``/``step`` over
``ContinuousBatchingEngine`` on the wall clock, under any traffic mix of
``chipbench/traffic/``.

One thread. Each turn of the loop hands over the requests that are due,
runs one scheduler step inside a ``chipbench.sched_step`` annotation, and
stamps the tokens that the step committed. A request's times: ``due`` (the
generator's schedule), ``sent`` (handed to the scheduler), ``admitted``
(first seen in a slot), ``token_t`` (one stamp a token).
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from .. import harness, weights
from ..traffic import gen


class Live:
    """The harness's record of one request."""
    __slots__ = ("req", "handle", "due", "sent", "admitted", "token_t",
                 "counted")

    def __init__(self, req: gen.Request, due: float, counted: bool):
        self.req, self.due, self.counted = req, due, counted
        self.handle = None
        self.sent = self.admitted = None
        self.token_t: List[float] = []


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies(due_in: List[Live], unsent: List[Live], lives: List[Live],
              t_open: float, t_close: float, worst: float):
    """The window's account: requests attempted and failed, the time from
    due to first token of every request due inside the window (``worst`` for
    one that never got a token or was never handed over), and every gap
    between consecutive tokens that closed inside the window."""
    attempted = len(due_in) + len(unsent)
    ttft = [(lv.token_t[0] - lv.due) if lv.token_t else worst
            for lv in due_in] + [worst] * len(unsent)
    failed = sum(1 for lv in due_in if not lv.token_t) + len(unsent)
    gaps = [b - a for lv in lives
            for a, b in zip(lv.token_t, lv.token_t[1:])
            if t_open <= b < t_close]
    return attempted, failed, ttft, gaps


class Loop:
    """Scheduler, clock and records."""

    def __init__(self, sched, clock=time.perf_counter, annotate=None):
        self.sched, self.eng, self.clock = sched, sched.engine, clock
        self.annotate = annotate
        self.lives: List[Live] = []          # every request handed over
        self.open: List[Live] = []           # not finished yet
        self.steps: List[Dict] = []

    def submit(self, live: Live):
        live.sent = self.clock()
        live.handle = self.sched.submit(live.req.prompt,
                                        max_new_tokens=live.req.max_new)
        self.lives.append(live)
        self.open.append(live)

    def step(self) -> bool:
        t0 = self.clock()
        if self.annotate is not None:
            with self.annotate("chipbench.sched_step"):
                more = self.sched.step()
        else:
            more = self.sched.step()
        t1 = self.clock()
        ctx, new_tokens, still = [], 0, []
        for lv in self.open:
            h = lv.handle
            n = len(h.tokens)
            if lv.admitted is None and (h.slot is not None or n):
                lv.admitted = t1
            if n > len(lv.token_t):
                if lv.token_t:          # a decode token: its context counts
                    ctx.append(h.prompt.shape[1] + n - 1)
                new_tokens += n - len(lv.token_t)
                lv.token_t += [t1] * (n - len(lv.token_t))
            if not h.done:
                still.append(lv)
        self.open = still
        plan = self.sched.last_plan
        self.steps.append({
            "t0": t0, "t1": t1, "tokens": new_tokens, "contexts": ctx,
            "rows": len(plan.decode_slots) if plan is not None else 0,
            "prefill_width": sum(w for _, w in plan.prefills) if plan else 0,
            "pages_used": self.eng.cache.allocator.num_used})
        return more


def build_engine(jax, cell: harness.Cell, params, plant: Optional[str]):
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import ServingScheduler
    c, e = cell.config, cell.mix["engine"]
    cfg = harness.program_config(c, e["max_len"])
    kw = dict(max_batch=e["max_batch"], page_size=e["page_size"],
              num_pages=e["num_pages"], max_len=e["max_len"],
              prefill_chunk=e["prefill_chunk"],
              enable_prefix_cache=e["enable_prefix_cache"],
              kv_cache_dtype=e["kv_cache_dtype"],
              temperature=e["temperature"],
              use_kernel=True if cell.rehearsal else None)
    if plant == "control":
        # the program's own lower-precision path, switched on: 8-bit
        # weights and an 8-bit KV pool
        kw.update(weight_bits=8, kv_cache_dtype="int8")
    eng = ContinuousBatchingEngine(params, cfg, **kw)
    return ServingScheduler(eng)


def warm(loop: Loop, cell: harness.Cell, seed: int):
    """Meet every chunk program the window can reach, and the decode
    program, with seeded prompts: first with nothing cached, then with the
    system prompt served from the cache."""
    mix, vocab = cell.mix, cell.config["vocab_size"]
    rng = np.random.default_rng([int(seed), 4])
    phases = [False, True] if mix["prefix_tokens"] else [False]
    for shared in phases:
        tails = gen.covering_tails(mix, mix["engine"], shared)
        for i, t in enumerate(tails):
            tail = rng.integers(3, vocab, (t,)).astype(np.int32)
            if not mix["prefix_tokens"]:
                prompt = tail
            elif shared:
                prompt = np.concatenate(
                    [gen.system_prompt(mix, seed, 0, vocab), tail])
            else:                  # a system prompt nobody has sent before
                prompt = np.concatenate([rng.integers(
                    3, vocab, (mix["prefix_tokens"],)).astype(np.int32), tail])
            if shared and i == 0:
                # the first of these caches tenant 0's system prompt
                _run_to_end(loop, prompt[:mix["prefix_tokens"] + 1])
            _run_to_end(loop, prompt)


def _run_to_end(loop: Loop, prompt):
    h = loop.sched.submit(prompt, max_new_tokens=2)
    while not h.done:
        loop.sched.step()


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        plant: Optional[str] = None) -> int:
    jax, device, peaks = harness.start_jax(cell)
    meter = harness.CompileMeter(jax)
    mix, c = cell.mix, cell.config
    vocab = c["vocab_size"]
    params = jax.jit(lambda k: weights.make(k, c))(weights.seed_key(seed))
    sched = build_engine(jax, cell, params, plant)
    tw = harness.TraceWindow(jax, cell, trace)
    loop = Loop(sched, annotate=jax.profiler.TraceAnnotation)
    if plant == "token_altered":
        _alter_tokens(sched.engine, vocab)
    warm(loop, cell, seed)

    blocks = gen.stream(mix, seed, vocab)
    backlog = mix["arrivals"]["kind"] == "backlog"
    first = next(blocks)
    # ---- the start state, still set-up ----
    if mix["start"] == "staggered_block":
        for r in gen.stagger(first, seed, vocab):
            loop.submit(Live(r, 0.0, counted=False))
        sched.step()                       # admits the block
        eng = sched.engine
        while eng.pending_prefills():      # prefill only: no decode steps
            eng.prefill_step()
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
    # the profiler runs over the window's last seconds and is stopped after
    # the close: stopping it writes the trace, which would stall the loop
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
    # the step in progress when the clock ran out belongs to the window: a
    # rate is over all the work and all the time
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
    # where answers outlast the window, no request that was prefilled inside
    # it can finish there: the mix then asks for some of those as well, with
    # the tokens they have so far
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
    for lv in loop.lives:
        lv.handle = None
    del loop, sched, sample, finished, unfinished, due_in
    gc.collect()
    reduced = tw.reduce(cell.chips)
    record["trace"] = reduced
    compared, widest = compare(jax, cell, params, served)
    correct = bool(served) and all(x["value"] <= x["limit"] for x in compared)
    per_layer = harness.read_per_layer(cell, record) if trace else {}
    device["memory_peak_bytes"] = mem_peak
    extra = {"requests_sent": len(record["lives"]),
             "checked_requests": len(served),
             "checked_prefilled_in_window": checked_in_window_prefills,
             "checked_tokens": int(sum(t.size for _, t in served)),
             "max_logit_gap_not_compared": widest}
    if plant:
        extra["planted"] = plant
    if not backlog:
        # what the rate sweep reads to find the knee (tools/readings.py with
        # --set arrivals.rate_per_s=..., tools/calls/summarise.py)
        def waiting(t):
            return sum(1 for lv in record["lives"] if lv["due"] <= t
                       and (not lv["token_t"] or lv["token_t"][0] > t))
        extra.update(backlog_mid=waiting((t_open + t_close) / 2),
                     backlog_end=waiting(t_close),
                     tokens_per_s=sum(s["tokens"] for s in window) / seconds,
                     ttft_mean_ms=1e3 * float(np.mean(ttft)),
                     ttft_p90_ms=1e3 * percentile(ttft, 90))
    return harness.emit(cell, trace, device, correct, attempted, failed, e2e,
                        per_layer, reduced, compared, extra)


def _turn(loop: Loop, pending: List[Live], t_stop: float):
    """Hand over what is due, then one step; with nothing to do, wait for the
    next arrival."""
    now = loop.clock()
    while pending and pending[0].due <= now:
        loop.submit(pending.pop(0))
    if loop.open:
        loop.step()
    else:
        nxt = min(pending[0].due if pending else t_stop, t_stop)
        time.sleep(max(0.0, min(nxt - loop.clock(), 0.05)))


def _sample(finished: List[Live], seed: int, n: int) -> List[Live]:
    """``n`` of these requests drawn from the seed, the longest among them."""
    if not finished or n <= 0:
        return []
    longest = max(finished, key=lambda lv: lv.handle.prompt.shape[1]
                  + len(lv.handle.tokens))
    rest = [lv for lv in finished if lv is not longest]
    rng = np.random.default_rng([int(seed), 5])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in pick]


def _alter_tokens(eng, vocab: int):
    """The planted fault 'a token altered where it is produced': the decode
    program's even tokens come out one higher."""
    build = eng._decode

    def broken_build():
        decode = build()

        def broken(*args, **kw):
            nxt, paged = decode(*args, **kw)
            return nxt + (nxt % 2 == 0).astype(nxt.dtype) * (nxt + 1 < vocab), paged
        return broken
    eng._decode = broken_build


def compare(jax, cell: harness.Cell, params, served) -> List[Dict]:
    """The reference once over each sampled prompt with its served tokens:
    how far below the reference's best logit each served token lies."""
    import jax.numpy as jnp
    from ..reference import decoder
    c, mix = cell.config, cell.mix
    if not served:
        return [{"name": "mean_logit_gap", "value": float("inf"),
                 "limit": mix["limits"]["mean_logit_gap"]}], float("inf")
    longest = mix["prefix_tokens"] + mix["tail_tokens"][1] + mix["output_tokens"][1]
    width = -(-longest // 256) * 256
    n_out = mix["output_tokens"][1]

    @jax.jit
    def gaps(params, row, toks, start, count):
        x = decoder.hidden(params, row[None], c)[0]
        at = jnp.clip(start + jnp.arange(n_out), 0, width - 1)
        lg = decoder.logits(params, jnp.take(x, at, axis=0), c)
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
    # the widest gap is printed and not compared: it is an extreme of some
    # hundreds of draws and grows only in step with the rounding noise, so the
    # control read under three times the sound runs (PERF.md); the mean gap
    # grows with its square
    return [{"name": "mean_logit_gap", "value": total / n,
             "limit": mix["limits"]["mean_logit_gap"]}], worst
