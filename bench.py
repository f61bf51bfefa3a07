"""Headline benchmark: flagship LM training throughput on one chip.

Metric (BASELINE.md north star): tokens/sec/chip + MFU on a Llama-style
decoder LM, seq=4096, bf16, flash attention, remat, fused AdamW — the
single-chip row of the reference's hybrid-parallel Llama recipe. The
reference publishes no in-tree numbers (BASELINE.json "published": {}), so
vs_baseline is reported against the 40%-MFU north star.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The measurement runs in this process (a chip belongs to one process) and
any failure is a traceback and a non-zero exit. No chip is an error;
``PADDLE_TPU_BENCH_PLATFORM=cpu`` is the explicit CPU smoke (tiny config,
no MFU). Due to be replaced by the cell benchmark (ROADMAP S1).
"""
from __future__ import annotations

import json
import os
import sys
import time


def pick_config():
    """The flagship 664M config on a TPU: persistent state is 14 B/param
    (bf16 param + fp32 master/m/v) plus a transient fp32 grad tree, and
    batch 4 x seq 4096 fits a 16G-HBM chip (v5e) with headroom. The tiny
    CPU config is taken only under ``PADDLE_TPU_BENCH_PLATFORM=cpu``."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return llama.LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_layers=20, num_heads=12, num_kv_heads=12, max_seq_len=4096,
            dtype=jnp.bfloat16, remat=True), 4096, 4
    if os.environ.get("PADDLE_TPU_BENCH_PLATFORM") != "cpu":
        raise RuntimeError(
            f"bench: no TPU (platform {dev.platform!r}); set "
            f"PADDLE_TPU_BENCH_PLATFORM=cpu for the CPU smoke")
    return llama.LlamaConfig.tiny(num_layers=2, max_seq_len=256), 256, 2


def peak_flops(dev) -> float:
    """bf16 peak per chip by ``device_kind``; an unknown device is an
    error, not a default."""
    kind = dev.device_kind.lower()
    table = {
        "v4": 275e12, "v5e": 197e12, "v5 lite": 197e12, "v5p": 459e12,
        "v6e": 918e12, "v6 lite": 918e12, "trillium": 918e12,
    }
    for k, v in table.items():
        if k in kind:
            return v
    raise ValueError(f"bench: no peak FLOP/s on record for {kind!r}")


def _result(tps, mfu, seq, batch, cfg, lossv, decode_tps,
            decode_int8_tps=None, decode_int4_tps=None,
            decode_w8kv8_tps=None, decode_paged_tps=None,
            decode_prefix_tps=None, decode_sched=None,
            decode_spec=None, decode_treespec=None, decode_tp=None,
            decode_tp2d=None,
            decode_cluster=None,
            decode_offload=None, decode_slo=None, decode_fused=None,
            decode_multilora=None, phases=None):
    import jax
    rec = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tps, 2),
        "unit": "tokens/s",
        "vs_baseline": None if mfu is None else round(mfu / 0.40, 4),
        "extra": {"mfu": None if mfu is None else round(mfu, 4),
                  "seq": seq, "batch": batch,
                  "params": cfg.num_params(),
                  "device": str(jax.devices()[0].device_kind),
                  "loss": lossv,
                  "decode_tokens_per_sec": decode_tps,
                  "decode_int8_tokens_per_sec": decode_int8_tps,
                  "decode_int4_tokens_per_sec": decode_int4_tps,
                  "decode_w8kv8_tokens_per_sec": decode_w8kv8_tps,
                  "decode_paged_tokens_per_sec": decode_paged_tps,
                  "decode_prefix_tokens_per_sec": decode_prefix_tps,
                  "decode_sched_tokens_per_sec": (
                      decode_sched[0] if decode_sched else None),
                  "decode_spec_tokens_per_sec": (
                      decode_spec[0] if decode_spec else None),
                  "decode_treespec_tokens_per_sec": (
                      decode_treespec[0] if decode_treespec else None),
                  "decode_tp_tokens_per_sec": (
                      decode_tp[0] if decode_tp else None),
                  "decode_tp2d_tokens_per_sec": (
                      decode_tp2d[0] if decode_tp2d else None),
                  "decode_cluster_tokens_per_sec": (
                      decode_cluster[0] if decode_cluster else None),
                  "decode_offload_tokens_per_sec": (
                      decode_offload[0] if decode_offload else None),
                  "decode_slo_goodput_tokens_per_sec": (
                      decode_slo[0] if decode_slo else None),
                  "decode_multilora_tokens_per_sec": (
                      decode_multilora[0] if decode_multilora
                      else None)},
    }
    if decode_sched:
        # the tier's point is the BOUND, not just the throughput:
        # p50/p99 step latency under the bursty two-priority workload
        rec["extra"]["decode_sched_step_ms"] = decode_sched[1]
        if len(decode_sched) > 2 and decode_sched[2]:
            # overlap rider (ISSUE 12): the same workload through the
            # double-buffered scheduler — sync vs overlapped step ms +
            # the host_overhead_fraction the overlap hides
            rec["extra"]["decode_overlap_speedup"] = decode_sched[2]
        if len(decode_sched) > 3 and decode_sched[3]:
            # durability rider (ISSUE 15): the same workload through a
            # WAL-backed supervisor at each fsync rung vs journal-off —
            # the measured cost of crash durability
            rec["extra"]["decode_durability_overhead"] = decode_sched[3]
        if len(decode_sched) > 4 and decode_sched[4]:
            # trace rider (ISSUE 16): the same workload with request
            # tracing ON vs the plain run — the measured price of the
            # always-on observability switch
            rec["extra"]["decode_trace_overhead"] = decode_sched[4]
    if decode_spec:
        # the speculative tier's throughput only means something next
        # to the acceptance rate that produced it — they travel together
        rec["extra"]["decode_spec_acceptance"] = decode_spec[1]
    if decode_treespec:
        # the tree tier's throughput only means something next to the
        # realized accepted path length and the tree geometry that
        # produced it (ISSUE 20) — they ride the record together
        rec["extra"]["decode_treespec_stats"] = decode_treespec[1]
    if decode_tp:
        # the tp tier reports an AGGREGATE over tp chips: the scaling
        # factor vs the single-chip paged tier is the honest headline
        rec["extra"]["decode_tp_scaling"] = decode_tp[1]
    if decode_tp2d:
        # the 2-D mesh tier's honest headline is the dp batch-scaling
        # factor vs the 1-D tp tier at the same per-shard geometry —
        # {tp, dp, vs_1d_tp} travel with the aggregate number
        rec["extra"]["decode_tp2d_scaling"] = decode_tp2d[1]
    if decode_cluster:
        # the cluster tier's ratio vs one engine on the same tenant
        # workload (router+handoff overhead on one host, the scaling
        # win on real multi-chip deployments) travels with the number
        rec["extra"]["decode_cluster_scaling"] = decode_cluster[1]
    if decode_offload:
        # the host-tier tier's point is the RESUME cost it removed:
        # swap-in latency + the ratio vs the replay-prefill baseline
        rec["extra"]["decode_offload_resume"] = decode_offload[1]
    if decode_slo:
        # goodput only means something next to the SLO outcomes and
        # autoscale activity that produced it (ISSUE 13) — they ride
        # the record together
        rec["extra"]["decode_slo_metrics"] = decode_slo[1]
    if decode_fused:
        # fused-kernel rider on the paged tier (ISSUE 11): per-step
        # wall ms unfused vs fused + the throughput ratio — the direct
        # measurement of the Pallas fusions' HBM win
        rec["extra"]["decode_fused_speedup"] = decode_fused
    if decode_multilora:
        # the multi-LoRA tier's throughput only means something next
        # to the adapter traffic the pool absorbed (ISSUE 14): variant
        # population, slot hits, demote/promote churn and the ratio vs
        # the one-variant merged-model deployment it replaces
        rec["extra"]["decode_multilora_density"] = decode_multilora[1]
    if phases is not None:
        rec["phases"] = phases
    return rec


def _capture_phases(step, state, tokens, cfg):
    """Instrumented mini-pass AFTER the timed measurement: one train
    step + one small eager generate() under observability + a Profiler,
    yielding the per-phase summary dict that rides the record under
    ``phases``. ``state`` is donated to the step.

    The process-global registry is CLEARED first so the snapshot holds
    only this capture (a PADDLE_TPU_METRICS=1 run would otherwise leak
    trace-time junk from the jitted decode tiers into the record). The
    prior enabled-state is restored on the way out."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu import observability as obs
    from paddle_tpu import profiler as prof
    from paddle_tpu.models import generate as gen
    was_enabled = obs.metrics_enabled()
    obs.REGISTRY.clear()
    obs.enable()
    p = prof.Profiler()
    p.start()
    try:
        with prof.RecordEvent("Train.step", "Operator"):
            state, m2 = step(state, tokens)
            float(m2["loss"])           # host fence
        prompt = jnp.asarray(np.random.default_rng(7).integers(
            0, cfg.vocab_size, (2, 8)), jnp.int32)
        # eager call: the prefill/decode instrumentation inside
        # generate() times real work (jit would record trace time)
        np.asarray(gen.generate(state.params, prompt, cfg,
                                max_new_tokens=4, temperature=0.0))
        p.step()
        return p.phase_summary()
    finally:
        # a mid-capture failure must not leave the collector recording,
        # and a PADDLE_TPU_METRICS=1 opt-in must survive the capture
        p.stop()
        if not was_enabled:
            obs.disable()


def _engine_tier(params, cfg, db, dnew, max_len, on_tpu, make_prompts,
                 between_passes=None, **engine_kwargs):
    """Shared engine-tier measurement scaffold (paged + prefix tiers):
    2x-oversubscribed queue with alternating decode budgets — short
    rows retire mid-run and queued prompts admit into the freed slots,
    exercising the continuous-batching mechanism itself. One warm pass
    (compiles + trie), one timed steady-state pass; ``make_prompts()``
    is called PER PASS so a tier can regenerate its unique parts (the
    prefix tier must not let the warm pass's full prompts recache), and
    ``between_passes(eng)`` — if given — runs after the warm pass so a
    tier can snapshot engine counters the timed pass should be deltaed
    against (the spec tier's acceptance record). Throughput includes
    the host scheduling loop (an ENGINE number, not a kernel
    microbench). Keeping ONE scaffold guarantees the tiers whose delta
    is reported stay comparable by construction. Returns ``(tokens/sec,
    engine)`` — the engine so tiers can read post-run stats.
    ``per_request_kw(i)`` — if given — returns extra ``submit`` kwargs
    for the i-th request of each pass (the multi-LoRA tier's per-row
    ``adapter_id``)."""
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    per_request_kw = engine_kwargs.pop("per_request_kw", None)
    eng = ContinuousBatchingEngine(
        params, cfg, max_batch=db, page_size=16 if on_tpu else 8,
        max_len=max_len, **engine_kwargs)

    def one_pass():
        reqs = [eng.submit(p, max_new_tokens=(
            dnew if i % 2 else max(dnew // 2, 1)),
            **(per_request_kw(i) if per_request_kw else {}))
                for i, p in enumerate(make_prompts())]
        eng.run()
        return sum(r.max_new_tokens for r in reqs)

    one_pass()                                      # compile/warm pass
    if between_passes is not None:
        between_passes(eng)
    t0 = time.perf_counter()
    toks_out = one_pass()                           # steady state
    return round(toks_out / (time.perf_counter() - t0), 2), eng


def paged_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                      kv_cache_dtype=None):
    """The decode_paged_tokens_per_sec measurement, shared by measure()
    and tools/decode_bench.py so the two sources stay comparable:
    mixed prompt lengths through the :func:`_engine_tier` scaffold.
    The prefix cache is OFF: this tier is the paged-engine baseline the
    prefix tier's delta is measured against (the warm pass resubmits
    the same prompts, so a warm trie would silently convert the timed
    pass into a prefix-hit workload).

    Returns ``(tokens_per_sec, decode_fused_speedup)`` (ISSUE 11): the
    rider re-runs the IDENTICAL workload with the fused Pallas serving
    kernels on (``fused=True`` — in-VMEM q-RoPE + KV dequant in the
    decode kernel, flash chunk attention behind prefill) and reports
    per-step wall ms for both paths plus the throughput ratio — the
    direct measurement of what the fusions buy at this geometry."""
    import numpy as np
    plens = [dp_len if i % 2 else max(dp_len // 2, 1)
             for i in range(2 * db)]
    rngp = np.random.default_rng(2)
    prompts = [rngp.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in plens]

    def run(fused):
        info = {}

        def snap(eng):
            info["s0"], info["t0"] = eng._steps, time.perf_counter()

        tps, eng = _engine_tier(
            params, cfg, db, dnew, dp_len + dnew, on_tpu,
            lambda: prompts, between_passes=snap,
            kv_cache_dtype=kv_cache_dtype, enable_prefix_cache=False,
            fused=fused)
        steps = max(eng._steps - info["s0"], 1)
        step_ms = (time.perf_counter() - info["t0"]) * 1e3 / steps
        return tps, round(step_ms, 3)

    tps, step_ms = run(False)
    fused_tps, fused_ms = run(True)
    rider = {"fused_tokens_per_sec": fused_tps,
             "unfused_step_ms": step_ms,
             "fused_step_ms": fused_ms,
             "speedup": round(fused_tps / tps, 3) if tps else None}
    return tps, rider


def lowbit_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                       weight_bits, kv_cache_dtype=None):
    """The decode_int4_tokens_per_sec / decode_w8kv8_tokens_per_sec
    measurement (ISSUE 11), shared by measure() and
    tools/decode_bench.py so the two sources stay comparable.

    The PAGED ENGINE's mixed-length workload (identical mix /
    oversubscription / page-size rule as decode_paged — the tier it is
    deltaed against) with LOW-BIT weights: ``weight_bits=4`` is the
    per-group-int4 tier (quarter weight bytes — decode is HBM-bound,
    so the ratio vs decode_paged at the same lengths IS the
    weight-bandwidth win), ``weight_bits=8`` with
    ``kv_cache_dtype="int8"`` the w8/kv8 tier (weight AND KV bytes
    halved). Until this tier landed both slots were measured on the
    DENSE generate() path and had never produced a live number; the
    engine tier is what the serving tower actually ships. Prefix cache
    OFF (the paged-tier rule: the warm pass must not convert the timed
    pass into a hit workload)."""
    import numpy as np
    plens = [dp_len if i % 2 else max(dp_len // 2, 1)
             for i in range(2 * db)]
    rngp = np.random.default_rng(2)
    prompts = [rngp.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in plens]
    return _engine_tier(params, cfg, db, dnew, dp_len + dnew, on_tpu,
                        lambda: prompts, kv_cache_dtype=kv_cache_dtype,
                        weight_bits=weight_bits,
                        enable_prefix_cache=False)[0]


def prefix_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                       kv_cache_dtype=None):
    """The decode_prefix_tokens_per_sec measurement, shared by measure()
    and tools/decode_bench.py so the two sources stay comparable.

    Shared-SYSTEM-PROMPT workload: every request carries the same long
    prefix (3/4 of the prompt) plus a short unique suffix, through the
    same :func:`_engine_tier` scaffold as the paged tier — the prefix
    cache maps the shared pages into each admission after the first
    (the warm pass seeds the trie), and chunked prefill (one page-pair
    per chunk) bounds the per-step stall. The delta vs
    decode_paged_tokens_per_sec at the same lengths IS the
    prefix-cache + chunked-prefill win (hit rate x prefill FLOPs)."""
    import numpy as np
    page = 16 if on_tpu else 8
    sys_len = min(max(page, (dp_len * 3 // 4 // page) * page), dp_len)
    rngp = np.random.default_rng(3)
    sys_prompt = rngp.integers(0, cfg.vocab_size, (sys_len,)).astype(
        np.int32)
    # prompts stay dp_len total so the tier is length-comparable with
    # decode_paged; a zero-length unique suffix (tiny CPU smoke shapes)
    # degenerates to identical prompts — still a valid hit workload.
    # Suffixes REGENERATE per pass: only the system prefix may hit the
    # warm trie, otherwise the timed pass measures full-prompt
    # recaching instead of the documented shared-prefix workload
    def make_prompts():
        return [np.concatenate([sys_prompt, rngp.integers(
            0, cfg.vocab_size, (dp_len - sys_len,)).astype(np.int32)])
            for _ in range(2 * db)]
    return _engine_tier(params, cfg, db, dnew, dp_len + dnew, on_tpu,
                        make_prompts, kv_cache_dtype=kv_cache_dtype,
                        prefill_chunk=2 * page)[0]


def sched_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                      kv_cache_dtype=None, overlap_rider=True,
                      durability_rider=True, trace_rider=True):
    """The decode_sched_tokens_per_sec measurement, shared by measure()
    and tools/decode_bench.py so the two sources stay comparable.

    Oversubscribed TWO-PRIORITY bursty workload through the ISSUE 4
    :class:`~paddle_tpu.serving.ServingScheduler`: ``db`` LOW
    long-prompt requests fill every slot first, then a burst of ``db``
    HIGH short-prompt requests lands — each HIGH admission preempts a
    LOW victim (pages evicted back to the pool) and the victim later
    resumes token-identically through the continuation-prefill replay.
    The step planner runs with a real token budget (one decode per
    slot + one two-page chunk), so the number measures the whole
    control plane: planning, preempt/evict/resume churn, and the
    budget-bounded step latency. Returns ``(tokens_per_sec,
    {"p50_step_ms", "p99_step_ms", "preemptions"}, overlap_rider)`` —
    the latency percentiles are the tier's point: FIFO has no bound on
    them. Prefix cache OFF (same reason as the paged tier: the warm
    pass must not convert the timed pass into a hit workload).

    The overlap rider (ISSUE 12) re-runs the IDENTICAL workload with
    the double-buffered scheduler (``overlap=True`` — expire/admit/
    plan hidden under the in-flight decode step, one commit fence per
    step) and reports {sync_step_ms, overlapped_step_ms,
    host_overhead_fraction (both modes), speedup} — the direct
    measurement of how much host plane the overlap hides at this
    geometry."""
    import numpy as np
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import Priority, ServingScheduler
    page = 16 if on_tpu else 8

    def build(overlap):
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=db, page_size=page,
            max_len=dp_len + dnew, kv_cache_dtype=kv_cache_dtype,
            enable_prefix_cache=False, overlap=overlap)
        return ServingScheduler(eng, token_budget=db + 2 * page,
                                overlap=overlap)

    def one_pass(sched, rngp):
        def mk(n):
            return rngp.integers(0, cfg.vocab_size, (n,)).astype(
                np.int32)
        lows = [sched.submit(mk(dp_len), max_new_tokens=dnew,
                             priority=Priority.LOW) for _ in range(db)]
        # let the LOW wave occupy every slot before the burst
        for _ in range(4):
            sched.step()
        highs = [sched.submit(mk(max(dp_len // 2, 1)),
                              max_new_tokens=max(dnew // 2, 1),
                              priority=Priority.HIGH)
                 for _ in range(db)]
        lats = []
        while True:
            t0 = time.perf_counter()
            more = sched.step()
            lats.append(time.perf_counter() - t0)
            if not more:
                break
        sched.flush()                   # overlap: drain the last step
        return (sum(len(r.tokens) for r in lows + highs), lats)

    def measure(sched):
        # fresh generator per mode: the sync baseline and the overlap
        # rider must replay the IDENTICAL warm+timed prompt stream, or
        # the speedup would compare two different request sets
        rngp = np.random.default_rng(5)
        one_pass(sched, rngp)                           # compile/warm
        p0 = sched.preemptions_total
        t0 = time.perf_counter()
        toks_out, lats = one_pass(sched, rngp)          # steady state
        tps = round(toks_out / (time.perf_counter() - t0), 2)
        return tps, lats, sched.preemptions_total - p0

    sched = build(False)
    tps, lats, preempts = measure(sched)
    lat = {
        "p50_step_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
        "p99_step_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
        "preemptions": preempts,
    }
    rider = None
    if overlap_rider:
        sched_ov = build(True)
        ov_tps, ov_lats, _ = measure(sched_ov)
        rider = {
            "sync_step_ms": lat["p50_step_ms"],
            "overlapped_step_ms": round(
                float(np.percentile(ov_lats, 50)) * 1e3, 3),
            "host_overhead_fraction": {
                "sync": round(sched.host_frac_ema, 4),
                "overlap": round(sched_ov.host_frac_ema, 4)},
            "speedup": round(ov_tps / tps, 3) if tps else None,
        }
    durability = None
    if durability_rider:
        durability = _durability_rider(
            params, cfg, db, dp_len, dnew, page,
            kv_cache_dtype=kv_cache_dtype)
    trace = None
    if trace_rider:
        # decode_trace_overhead (ISSUE 16): the IDENTICAL two-wave
        # workload with request tracing ON — every span-close site
        # live on every step — against the baseline above. The
        # zero-cost-when-disabled contract makes the off number the
        # plain run; the rider prices the on switch.
        from paddle_tpu.observability import tracing as _tracing
        sched_tr = build(False)
        _tracing.enable()
        try:
            _, tr_lats, _ = measure(sched_tr)
        finally:
            _tracing.disable()
        tr_p50 = round(float(np.percentile(tr_lats, 50)) * 1e3, 3)
        off = lat["p50_step_ms"]
        trace = {
            "tracing_off_step_ms": off,
            "tracing_on_step_ms": tr_p50,
            "overhead_frac": (round(tr_p50 / off - 1.0, 4)
                              if off else None),
        }
    return tps, lat, rider, durability, trace


def _durability_rider(params, cfg, db, dp_len, dnew, page,
                      kv_cache_dtype=None):
    """The decode_durability_overhead rider (ISSUE 15): the sched
    tier's two-wave preemption workload re-run through an
    :class:`~paddle_tpu.serving.EngineSupervisor` with the durable
    journal OFF (in-memory only — the baseline), then with the on-disk
    WAL at each fsync rung (``group`` — the default group-commit
    window — and ``commit`` — fsync every append). Reports
    ``{fsync_policy, wal_ms_per_step, steps_per_sec, overhead_frac}``
    — the measured durability tax next to the PERF_NOTES
    bytes/record · records/step amortization model. The headline gate:
    group-commit overhead < 5% at the CPU smoke geometry."""
    import shutil
    import tempfile

    import numpy as np
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import EngineSupervisor, Priority

    def factory():
        return ContinuousBatchingEngine(
            params, cfg, max_batch=db, page_size=page,
            max_len=dp_len + dnew, kv_cache_dtype=kv_cache_dtype,
            enable_prefix_cache=False)

    root = tempfile.mkdtemp(prefix="bench_wal_")

    def run_mode(mode):
        kw = {}
        if mode != "journal_off":
            kw = dict(wal_dir=os.path.join(root, mode),
                      wal_fsync=mode, checkpoint_every=64)
        rngp = np.random.default_rng(5)

        def mk(n):
            return rngp.integers(0, cfg.vocab_size, (n,)).astype(
                np.int32)

        def one_pass(sup):
            reqs = [sup.submit(mk(dp_len), max_new_tokens=dnew,
                               priority=Priority.LOW)
                    for _ in range(db)]
            for _ in range(4):
                sup.step()
            reqs += [sup.submit(mk(max(dp_len // 2, 1)),
                                max_new_tokens=max(dnew // 2, 1),
                                priority=Priority.HIGH)
                     for _ in range(db)]
            s0 = sup.steps_total
            sup.run()
            return (sum(len(r.tokens) for r in reqs),
                    sup.steps_total - s0 + 4)
        sup = EngineSupervisor(factory, token_budget=db + 2 * page,
                               **kw)
        one_pass(sup)                           # compile/warm
        rates, wal_ms = [], []
        for _ in range(3):                      # median beats CPU noise
            w0 = (sup.wal.append_ns + sup.wal.fsync_ns
                  if sup.wal is not None else 0)
            s0 = sup.steps_total
            t0 = time.perf_counter()
            _toks, steps = one_pass(sup)
            dt = time.perf_counter() - t0
            if dt and steps:
                rates.append(steps / dt)
            if sup.wal is not None:
                wal_ms.append(
                    (sup.wal.append_ns + sup.wal.fsync_ns - w0) / 1e6
                    / max(1, sup.steps_total - s0))
        return {"steps_per_sec": (float(np.median(rates))
                                  if rates else None),
                "wal_ms_per_step": (float(np.median(wal_ms))
                                    if wal_ms else None)}
    try:
        base = run_mode("journal_off")
        group = run_mode("group")
        commit = run_mode("commit")
    finally:
        shutil.rmtree(root, ignore_errors=True)

    def overhead(m):
        b, w = base["steps_per_sec"], m["steps_per_sec"]
        return round(1.0 - w / b, 4) if b and w else None
    # the end-to-end ratio is noisy at smoke step times (~2 ms);
    # wal_frac_of_step is the DIRECT measurement — WAL append+fsync ms
    # over the measured step period — and is the honest < 5% headline
    wal_frac = None
    if group["wal_ms_per_step"] and group["steps_per_sec"]:
        wal_frac = round(group["wal_ms_per_step"]
                         / (1000.0 / group["steps_per_sec"]), 4)
    return {
        "fsync_policy": "group",
        "wal_ms_per_step": round(group["wal_ms_per_step"] or 0, 4),
        "wal_frac_of_step": wal_frac,
        "steps_per_sec": {
            "journal_off": round(base["steps_per_sec"], 2),
            "group": round(group["steps_per_sec"], 2),
            "commit": round(commit["steps_per_sec"], 2)},
        "overhead_frac": {"group": overhead(group),
                          "commit": overhead(commit)},
    }


def spec_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                     kv_cache_dtype=None):
    """The decode_spec_tokens_per_sec measurement, shared by measure()
    and tools/decode_bench.py so the two sources stay comparable.

    The paged-engine workload with SPECULATIVE decoding on (ISSUE 5):
    n-gram prompt-lookup drafting + the batched greedy verify program,
    over REPETITIVE prompts (a tiled motif behind a unique head token)
    — the proposer needs in-context repetition to draft from, which is
    exactly the workload speculation targets (templated serving
    traffic, code, structured extraction). Rides the same
    :func:`_engine_tier` scaffold as the paged/prefix tiers (identical
    oversubscription and token accounting, so the delta vs
    decode_paged IS the speculation win), snapshotting the speculation
    counters after the warm pass so the record reflects the timed pass
    only. Returns ``(tokens_per_sec, {"acceptance_rate", "drafted",
    "accepted"})`` — the throughput number only means something next
    to the acceptance rate that produced it, so they ride the record
    together. Prefix cache OFF (same reason as the paged tier: the
    warm pass must not convert the timed pass into a hit workload)."""
    import numpy as np
    rngp = np.random.default_rng(7)
    motif = rngp.integers(0, cfg.vocab_size,
                          (max(dp_len // 8, 1),)).astype(np.int32)

    def make_prompts():
        # unique head so rows aren't identical; the motif repeats so the
        # last n-gram has prior in-context occurrences to look up
        reps = -(-dp_len // motif.size) + 1
        return [np.concatenate([
            rngp.integers(0, cfg.vocab_size, (1,)).astype(np.int32),
            np.tile(motif, reps)[:dp_len - 1]]) for _ in range(2 * db)]

    warm = {}

    def snapshot(eng):
        warm.update(d=eng.spec.drafted_total, a=eng.spec.accepted_total)

    tps, eng = _engine_tier(params, cfg, db, dnew, dp_len + dnew,
                            on_tpu, make_prompts,
                            between_passes=snapshot,
                            kv_cache_dtype=kv_cache_dtype,
                            enable_prefix_cache=False, spec_k=4)
    drafted = eng.spec.drafted_total - warm["d"]
    accepted = eng.spec.accepted_total - warm["a"]
    rider = {
        "acceptance_rate": round(accepted / drafted, 3) if drafted
        else 0.0,
        "drafted": drafted, "accepted": accepted,
    }
    # sampled-spec rider (ISSUE 14): the SAME workload at
    # temperature>0 through the rejection-sampled verify commit — the
    # acceptance rate under min(1, p/q) is the realized 1+k·rate
    # multiplier for sampled traffic, the restriction this PR lifts.
    warm_s = {}

    def snap_s(e):
        warm_s.update(d=e.spec.drafted_total,
                      a=e.spec.accepted_total)

    tps_s, eng_s = _engine_tier(
        params, cfg, db, dnew, dp_len + dnew, on_tpu,
        make_prompts, between_passes=snap_s,
        kv_cache_dtype=kv_cache_dtype, enable_prefix_cache=False,
        spec_k=4, temperature=0.7)
    d_s = eng_s.spec.drafted_total - warm_s["d"]
    a_s = eng_s.spec.accepted_total - warm_s["a"]
    rider["sampled"] = {
        "temperature": 0.7,
        "tokens_per_sec": tps_s,
        "acceptance_rate": round(a_s / d_s, 3) if d_s else 0.0,
        "drafted": d_s, "accepted": a_s,
    }
    # non-repetitive scoreboard (ISSUE 20): the SAME geometry over the
    # synth_trace TEXT-mode workload — prompts sampled without
    # replacement, so in-context n-gram lookup finds nothing to draft
    # from by construction. The n-gram proposer's acceptance collapses
    # to ~0 there; the model-based draft path (truncated-layer draft
    # model on the aligned bench target) stays > 0.3 — the number that
    # justifies shipping a draft model at all.
    prompts_nr = _text_prompts(cfg, db, dp_len)

    def accept_on(p, **ekw):
        w = {}

        def snap(e):
            w.update(d=e.spec.drafted_total, a=e.spec.accepted_total)

        _, e = _engine_tier(p, cfg, db, dnew,
                            max(map(len, prompts_nr)) + dnew,
                            on_tpu, lambda: prompts_nr,
                            between_passes=snap,
                            kv_cache_dtype=kv_cache_dtype,
                            enable_prefix_cache=False, **ekw)
        d = e.spec.drafted_total - w["d"]
        a = e.spec.accepted_total - w["a"]
        return round(a / d, 3) if d else 0.0

    dl = max(1, cfg.num_layers // 2)
    rider["nonrepetitive"] = {
        "ngram_acceptance": accept_on(params, spec_k=4),
        "draft_acceptance": accept_on(
            _align_draft_params(params, dl), spec_k=4,
            draft_layers=dl),
        "draft_layers": dl,
    }
    return tps, rider


def _text_prompts(cfg, db, dp_len):
    """2*db NON-repetitive prompts off a ``synth_trace`` text-mode
    trace (ISSUE 20): Zipf marginals, zero in-context token repetition,
    prefix+tail sized to land near ``dp_len`` (shrunk if the model's
    vocab can't cover that many distinct tokens per prompt)."""
    import numpy as np
    from paddle_tpu.serving.traffic import synth_trace
    page = 8
    plen = min(max(page, dp_len // 2 // page * page),
               (cfg.vocab_size - 3) // 2 // page * page)
    tail_hi = min(max(2, dp_len - plen), cfg.vocab_size - 3 - plen)
    trace = synth_trace(11, duration_s=4.0, base_rps=max(6.0, db),
                        page_size=page, prefix_pages=plen // page,
                        vocab=cfg.vocab_size,
                        tail_tokens=(max(1, tail_hi // 2), tail_hi),
                        text=True)
    if not trace:
        raise RuntimeError("text trace came back empty")
    return [trace[i % len(trace)].prompt for i in range(2 * db)]


def _align_draft_params(params, draft_layers, damp=1e-3):
    """Bench-model surgery for the draft/tree tiers (ISSUE 20): damp
    the POST-draft layers' residual output projections so the
    truncated-layer draft is a faithful small model of the bench
    target. The bench weights are near-random (a few train steps), so
    an UN-aligned truncation would measure draft quality of noise —
    the tier measures the speculation MACHINERY (propose/verify/commit
    mechanics and their cost), and alignment is what gives the
    acceptance-rate scoreboard signal, the same way the repetitive
    motif gives the n-gram tier signal. Deployments bring their own
    distilled draft; the rider records the alignment so the record is
    honest."""
    layers = dict(params["layers"])
    for n in ("wo", "wd"):
        layers[n] = layers[n].at[draft_layers:].multiply(damp)
    out = dict(params)
    out["layers"] = layers
    return out


def treespec_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                         kv_cache_dtype=None, tree=(2, 4)):
    """The decode_treespec_tokens_per_sec measurement (ISSUE 20),
    shared by measure() and tools/decode_bench.py so the two sources
    stay comparable.

    Model-based DRAFT + TREE speculation on the paged engine over the
    NON-repetitive text-mode workload (the traffic n-gram lookup can't
    draft from): a truncated-layer shared-embedding draft model
    proposes a (width, depth) token tree per row, the whole tree
    verifies in ONE forward through the tree-masked flash path, and
    the longest accepted root path commits. Same :func:`_engine_tier`
    scaffold as the other serving tiers (so the delta vs decode_spec
    on this trace IS the tree+draft win); the bench target is
    deep-damped so the truncated draft aligns (see
    :func:`_align_draft_params`). Returns ``(tokens_per_sec,
    {"tree_width", "depth", "mean_accepted_path", ...})`` — the
    throughput only means something next to the realized path length,
    so they ride together."""
    w, d = tree
    draft_layers = max(1, cfg.num_layers // 2)
    bench_params = _align_draft_params(params, draft_layers)
    prompts = _text_prompts(cfg, db, dp_len)
    warm = {}

    def snapshot(eng):
        warm.update(d=eng.spec.drafted_total, a=eng.spec.accepted_total,
                    v=eng.spec.verify_steps)

    tps, eng = _engine_tier(bench_params, cfg, db, dnew,
                            max(map(len, prompts)) + dnew,
                            on_tpu, lambda: prompts,
                            between_passes=snapshot,
                            kv_cache_dtype=kv_cache_dtype,
                            enable_prefix_cache=False,
                            draft_layers=draft_layers, spec_tree=tree)
    drafted = eng.spec.drafted_total - warm["d"]
    accepted = eng.spec.accepted_total - warm["a"]
    verifies = eng.spec.verify_steps - warm["v"]
    rider = {
        "tree_width": w, "depth": d, "draft_layers": draft_layers,
        # committed tokens per verify (accepted path nodes + bonus):
        # the realized step-compression factor of the tree
        "mean_accepted_path": (round(1.0 + accepted / verifies, 3)
                               if verifies else None),
        "acceptance_rate": round(accepted / drafted, 3) if drafted
        else 0.0,
        "drafted": drafted, "accepted": accepted,
    }
    return tps, rider


def multilora_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                          kv_cache_dtype=None, adapters=6, slots=None,
                          rank=8):
    """The decode_multilora_tokens_per_sec measurement (ISSUE 14),
    shared by measure() and tools/decode_bench.py so the two sources
    stay comparable.

    MANY-TENANT mixed-adapter workload: ``adapters`` LoRA variants
    (rank ``rank``) over a pool of FEWER slots (``slots``, default
    ``adapters - 2``) so the steady state churns — slot hits for hot
    adapters, LRU demotions to the host store and promotions back for
    the tail. Requests cycle through the variant population (plus the
    id-0 base rows every engine serves for free), same mixed-length /
    oversubscription scaffold as the paged tier. The headline is the
    multi-tenant engine's throughput; the baseline it is judged
    against is the SINGLE-MERGED-MODEL engine (one adapter dense-
    merged into the weights, plain engine — the status-quo deployment
    that can only serve ONE variant), whose ratio rides as
    ``vs_single_merged``. Returns ``(tokens_per_sec,
    {"distinct_adapters", "slot_hits", "promote_count", ...})`` — the
    ``decode_multilora_density`` rider: throughput only means
    something next to how much adapter traffic the pool absorbed."""
    import numpy as np
    from paddle_tpu.serving.adapters import (AdapterRegistry, init_lora,
                                             merge_lora)
    from paddle_tpu.serving import HostPageStore
    slots = slots if slots is not None else max(adapters - 2, 1)
    registry = AdapterRegistry(cfg)
    for aid in range(1, adapters + 1):
        registry.register(aid, init_lora(cfg, rank, seed=300 + aid))
    plens = [dp_len if i % 2 else max(dp_len // 2, 1)
             for i in range(2 * db)]
    rngp = np.random.default_rng(17)
    prompts = [rngp.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in plens]

    # single-merged-model baseline: adapter 1 dense-merged, plain
    # engine — measured FIRST so a multilora failure can't orphan it
    merged = merge_lora(params, cfg, registry.get(1))
    base_tps, _ = _engine_tier(merged, cfg, db, dnew, dp_len + dnew,
                               on_tpu, lambda: prompts,
                               kv_cache_dtype=kv_cache_dtype,
                               enable_prefix_cache=False)

    pool_kw = dict(slots=slots, rank=rank, registry=registry,
                   store=HostPageStore(page_size=16 if on_tpu else 8))
    warm = {}

    def snapshot(eng):
        st = eng.adapters.stats()
        warm.update(h=st["adapter_slot_hits_total"],
                    p=st["adapter_promotions_total"],
                    d=st["adapter_demotions_total"])

    tps, eng = _engine_tier(
        params, cfg, db, dnew, dp_len + dnew, on_tpu,
        lambda: prompts, between_passes=snapshot,
        kv_cache_dtype=kv_cache_dtype, enable_prefix_cache=False,
        adapters=pool_kw,
        # request i serves variant (i mod (adapters+1)): id 0 = base
        per_request_kw=lambda i: {"adapter_id": i % (adapters + 1)})
    st = eng.adapters.stats()
    return tps, {
        "distinct_adapters": adapters,
        "pool_slots": slots,
        "rank": rank,
        "slot_hits": st["adapter_slot_hits_total"] - warm["h"],
        "promote_count": st["adapter_promotions_total"] - warm["p"],
        "demote_count": st["adapter_demotions_total"] - warm["d"],
        "vs_single_merged": (round(tps / base_tps, 3) if base_tps
                             else None),
        "single_merged_tokens_per_sec": base_tps,
    }


def tp_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                   kv_cache_dtype=None, tp=4):
    """The decode_tp_tokens_per_sec measurement, shared by measure()
    and tools/decode_bench.py so the two sources stay comparable.

    The paged-engine MIXED-LENGTH workload (same mix/oversubscription
    as decode_paged — the tier it is deltaed against) on a
    TENSOR-PARALLEL tp=4 serving mesh (ISSUE 7): weights partitioned by
    the regex rules, page pools sharded on the kv-head axis, the
    decode/chunk programs lowered through shard_map with exact
    all-gathers. The ratio vs decode_paged at the same lengths IS the
    tp aggregate-vs-single-chip scaling factor and rides the record as
    ``decode_tp_scaling``. Needs >= tp devices: a single-chip run
    raises."""
    import numpy as np
    import jax
    from paddle_tpu.distributed.mesh import serving_mesh
    ndev = len(jax.devices())
    if ndev < tp:
        raise RuntimeError(
            f"decode_tp tier needs a {tp}-device mesh, found {ndev} "
            f"device(s) — run on a multi-chip slice (or the host-"
            f"platform 8-device CI mesh)")
    plens = [dp_len if i % 2 else max(dp_len // 2, 1)
             for i in range(2 * db)]
    rngp = np.random.default_rng(11)
    prompts = [rngp.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in plens]
    return _engine_tier(params, cfg, db, dnew, dp_len + dnew, on_tpu,
                        lambda: prompts, kv_cache_dtype=kv_cache_dtype,
                        enable_prefix_cache=False,
                        mesh=serving_mesh(tp))[0]


def tp2d_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                     kv_cache_dtype=None, tp=2, dp=2):
    """The decode_tp2d_tokens_per_sec measurement, shared by measure()
    and tools/decode_bench.py so the two sources stay comparable.

    The same MIXED-LENGTH paged workload as the 1-D tp tier, on a 2-D
    ``tp x dp`` serving mesh (ISSUE 17): weights column-sharded over
    tp exactly as before, page pools head-sharded on tp and REPLICATED
    across dp, and the decode batch SPLIT over dp — ``db`` rows per dp
    shard, so ``max_batch = db * dp`` rows advance per step through
    the same per-shard program geometry the 1-D tier runs. The ratio
    vs the 1-D tp tier is the dp batch-scaling factor and rides the
    record as ``decode_tp2d_scaling``. Needs >= tp*dp devices: a
    single-chip run raises."""
    import numpy as np
    import jax
    from paddle_tpu.distributed.mesh import serving_mesh
    ndev = len(jax.devices())
    if ndev < tp * dp:
        raise RuntimeError(
            f"decode_tp2d tier needs a {tp}x{dp}-device mesh, found "
            f"{ndev} device(s) — run on a multi-chip slice (or the "
            f"host-platform 8-device CI mesh)")
    rows = db * dp
    plens = [dp_len if i % 2 else max(dp_len // 2, 1)
             for i in range(2 * rows)]
    rngp = np.random.default_rng(17)
    prompts = [rngp.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in plens]
    return _engine_tier(params, cfg, rows, dnew, dp_len + dnew, on_tpu,
                        lambda: prompts, kv_cache_dtype=kv_cache_dtype,
                        enable_prefix_cache=False,
                        mesh=serving_mesh(tp, dp))[0]


def cluster_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                        kv_cache_dtype=None, replicas=2):
    """The decode_cluster_tokens_per_sec measurement, shared by
    measure() and tools/decode_bench.py so the two sources stay
    comparable.

    TWO engine replicas behind the ISSUE 9
    :class:`~paddle_tpu.serving.ServingCluster` router, serving a
    shared-prefix TENANT workload: one tenant per replica, each with
    its own system prompt (3/4 of the prompt, page-aligned) plus
    per-request unique suffixes — prefix-affinity routing pins each
    tenant to the replica whose trie holds its system prompt, so the
    cluster converts the tenant mix into per-replica prefix-hit
    workloads instead of thrashing every trie with every tenant.
    Suffixes REGENERATE per pass (only the system prefix may hit the
    warm trie, same rule as the prefix tier). The rider is the
    cluster's honest headline: the SAME request set through ONE engine
    (same geometry, prefix cache on), with the cluster-vs-single-engine
    ratio riding the record as ``decode_cluster_scaling`` — on one
    host the replicas timeshare the chip, so the ratio measures router
    + handoff overhead; on a multi-chip deployment each replica owns
    its silicon and the ratio is the scaling win. Returns
    ``(tokens_per_sec, {"replicas", "vs_single_engine",
    "affinity_hit_rate"})``."""
    import numpy as np
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import ServingCluster
    page = 16 if on_tpu else 8
    sys_len = min(max(page, (dp_len * 3 // 4 // page) * page), dp_len)
    rngp = np.random.default_rng(13)
    sys_prompts = [rngp.integers(0, cfg.vocab_size, (sys_len,)).astype(
        np.int32) for _ in range(replicas)]

    def make_jobs():
        jobs = []
        for t in range(replicas):
            for _ in range(2 * db):
                jobs.append((t, np.concatenate([
                    sys_prompts[t],
                    rngp.integers(0, cfg.vocab_size,
                                  (dp_len - sys_len,)).astype(
                                      np.int32)])))
        return jobs

    def engine():
        return ContinuousBatchingEngine(
            params, cfg, max_batch=db, page_size=page,
            max_len=dp_len + dnew, kv_cache_dtype=kv_cache_dtype)

    single = engine()      # persistent, like the cluster's replicas —
    # both sides' warm pass absorbs compiles and seeds the tries

    def run_single():
        reqs = [single.submit(p, max_new_tokens=dnew)
                for _, p in make_jobs()]
        single.run()
        return sum(r.max_new_tokens for r in reqs)

    run_single()                                    # compile/warm pass
    t0 = time.perf_counter()
    toks = run_single()
    single_tps = toks / (time.perf_counter() - t0)

    cluster = ServingCluster(engine, replicas=replicas)

    def run_cluster():
        reqs = [cluster.submit(p, max_new_tokens=dnew,
                               tenant=f"tenant{t}")
                for t, p in make_jobs()]
        cluster.run()
        return sum(r.max_new_tokens for r in reqs)

    run_cluster()                                   # warm (binds affinity)
    t0 = time.perf_counter()
    toks = run_cluster()
    tps = round(toks / (time.perf_counter() - t0), 2)
    scaling = {
        "replicas": replicas,
        "vs_single_engine": round(tps / single_tps, 3) if single_tps
        else None,
        "affinity_hit_rate": round(
            cluster.router.stats()["affinity_hit_rate"], 3),
    }
    # overlap sub-rider (ISSUE 12): the same tenant workload with every
    # supervised replica running the double-buffered scheduler
    cl_ov = ServingCluster(engine, replicas=replicas, overlap=True)

    def run_ov():
        reqs = [cl_ov.submit(p, max_new_tokens=dnew,
                             tenant=f"tenant{t}")
                for t, p in make_jobs()]
        cl_ov.run()
        return sum(r.max_new_tokens for r in reqs)

    run_ov()                                    # warm
    t0 = time.perf_counter()
    toks = run_ov()
    ov_tps = round(toks / (time.perf_counter() - t0), 2)
    scaling["overlap"] = {
        "tokens_per_sec": ov_tps,
        "vs_sync": round(ov_tps / tps, 3) if tps else None,
    }
    return tps, scaling


def offload_decode_tier(params, cfg, db, dp_len, dnew, on_tpu,
                        kv_cache_dtype=None):
    """The decode_offload_tokens_per_sec measurement, shared by
    measure() and tools/decode_bench.py so the two sources stay
    comparable.

    The ISSUE 4 scheduler tier's oversubscribed TWO-PRIORITY bursty
    workload (LOW long-prompt wave fills every slot, then a HIGH burst
    preempts its way in) with the ISSUE 10 HOST TIER enabled: every
    preemption victim SWAPS OUT to host RAM and every resume SWAPS IN
    by one donated scatter instead of the replay prefill. The rider is
    the tier's honest story: ``swap_in_ms_p50`` (the host→device copy
    that replaced the replay) and ``vs_replay_prefill`` — the same
    workload through the same scheduler with the host tier OFF, so the
    ratio IS the swap-vs-replay win at this geometry (PERF_NOTES has
    the crossover model; on CPU smoke shapes the replay is tiny, so
    the ratio mostly prices the swap machinery's overhead — the TPU
    run is where replay FLOPs dominate). Prefix cache OFF (same rule
    as every engine tier: the warm pass must not convert the timed
    pass into a hit workload; the host store holds only swap
    payloads). Returns ``(tokens_per_sec, {"preemptions", "swap_ins",
    "swap_in_ms_p50", "vs_replay_prefill"})``."""
    import numpy as np
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import Priority, ServingScheduler
    page = 16 if on_tpu else 8

    def build(host, overlap=False):
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=db, page_size=page,
            max_len=dp_len + dnew, kv_cache_dtype=kv_cache_dtype,
            enable_prefix_cache=False, host_tier=host, overlap=overlap)
        return eng, ServingScheduler(eng, token_budget=db + 2 * page)

    def one_pass(sched, rngp):
        def mk(n):
            return rngp.integers(0, cfg.vocab_size, (n,)).astype(
                np.int32)
        lows = [sched.submit(mk(dp_len), max_new_tokens=dnew,
                             priority=Priority.LOW) for _ in range(db)]
        for _ in range(4):
            sched.step()
        highs = [sched.submit(mk(max(dp_len // 2, 1)),
                              max_new_tokens=max(dnew // 2, 1),
                              priority=Priority.HIGH)
                 for _ in range(db)]
        while sched.step():
            pass
        return sum(len(r.tokens) for r in lows + highs)

    # replay baseline: the identical workload, host tier OFF — the
    # rider's denominator (every resume pays the replay prefill)
    # every mode replays the IDENTICAL warm+timed prompt stream (one
    # fresh generator per mode) so the rider ratios compare the same
    # request set, not different draws from a shared stream
    rng = np.random.default_rng(19)
    _, sched_replay = build(False)
    one_pass(sched_replay, rng)                     # compile/warm pass
    t0 = time.perf_counter()
    toks = one_pass(sched_replay, rng)
    replay_tps = toks / (time.perf_counter() - t0)

    rng = np.random.default_rng(19)
    eng, sched = build(True)
    one_pass(sched, rng)                            # warm (shares compiles)
    n0 = len(eng.cache.swap_in_ms)
    si0, p0 = eng.cache.swap_ins_total, sched.preemptions_total
    t0 = time.perf_counter()
    toks = one_pass(sched, rng)
    tps = round(toks / (time.perf_counter() - t0), 2)
    lat = eng.cache.swap_in_ms[n0:]
    rider = {
        "preemptions": sched.preemptions_total - p0,
        "swap_ins": eng.cache.swap_ins_total - si0,
        "swap_in_ms_p50": (round(float(np.percentile(lat, 50)), 3)
                           if lat else None),
        "vs_replay_prefill": (round(tps / replay_tps, 3)
                              if replay_tps else None),
    }
    # overlap sub-rider (ISSUE 12): the same swap-heavy workload with
    # the double-buffered scheduler AND async swap-out DMAs (issued
    # under the in-flight decode, fenced at commit)
    rng = np.random.default_rng(19)
    eng_ov, sched_ov = build(True, overlap=True)
    one_pass(sched_ov, rng)                     # warm
    t0 = time.perf_counter()
    toks = one_pass(sched_ov, rng)
    ov_tps = round(toks / (time.perf_counter() - t0), 2)
    rider["overlap"] = {
        "tokens_per_sec": ov_tps,
        "vs_sync": round(ov_tps / tps, 3) if tps else None,
        "host_overhead_fraction": round(sched_ov.host_frac_ema, 4),
    }
    return tps, rider


def slo_goodput_tier(params, cfg, db, dp_len, dnew, on_tpu,
                     kv_cache_dtype=None):
    """The decode_slo_goodput_tokens_per_sec measurement (ISSUE 13),
    shared by measure() and tools/decode_bench.py so the two sources
    stay comparable.

    The trace-driven traffic harness against an AUTOSCALING cluster:
    a fixed-seed open-loop trace (tenant prefix families, one 4x burst
    window, mixed priority/deadline/length — see
    :func:`paddle_tpu.serving.traffic.synth_trace`) drives a cluster
    that starts at ONE replica and breathes with load through the
    :class:`~paddle_tpu.serving.ClusterAutoscaler` (scale-up on
    backlog, scale-down after the burst, through the retire_replica
    drain path). The virtual :class:`~paddle_tpu.serving.FakeClock`
    makes arrival dynamics and SLO accounting deterministic; wall time
    prices the actual serving work. The headline is GOODPUT — tokens
    of deadline-met requests per wall second, not raw throughput:
    overload work that misses its SLO counts for nothing, which is
    exactly the regression this tier gates. The rider carries the
    quantities that explain the number: deadline-met fraction, p99
    TTFT (virtual ms), p99 per-token latency, the autoscaler's
    up/down event counts for the timed pass, and the rejection split
    (the admission machinery's visible work)."""
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import (ClusterAutoscaler, FakeClock,
                                    ServingCluster, run_trace,
                                    synth_trace)
    page = 16 if on_tpu else 8
    prefix_pages = max(1, (dp_len // 2) // page)
    tail_max = max(2, dp_len // 2)
    # the engine must hold the LONGEST trace prompt plus its decode
    # budget (prefix family + unique tail + new tokens)
    max_len = prefix_pages * page + tail_max + dnew

    def factory():
        return ContinuousBatchingEngine(
            params, cfg, max_batch=db, page_size=page,
            max_len=max_len, kv_cache_dtype=kv_cache_dtype)

    clock = FakeClock()
    cluster = ServingCluster(
        factory, replicas=1, clock=clock,
        autoscaler=ClusterAutoscaler(
            min_replicas=1, max_replicas=3,
            up_backlog_per_replica=2.0 * db,
            down_backlog_per_replica=0.5,
            up_after=1, down_after=4, cooldown_ticks=3),
        supervisor_kw=dict(backoff_s=0.0, sleep=lambda s: None))
    trace = synth_trace(
        seed=29, duration_s=3.0, base_rps=4.0 * db, tenants=3,
        page_size=page, prefix_pages=prefix_pages,
        vocab=cfg.vocab_size, tail_tokens=(1, tail_max),
        new_tokens=(max(1, dnew // 2), dnew),
        burst_mult=4.0, deadline_frac=0.5, deadline_s=(0.5, 2.5))
    run_trace(cluster, trace, clock, step_dt=0.05)  # compile/warm pass
    report = run_trace(cluster, trace, clock, step_dt=0.05)
    rider = {
        "requests": report.requests,
        "deadline_met_fraction": round(report.deadline_met_fraction,
                                       4),
        "p99_ttft_ms": (round(report.p99_ttft_s * 1e3, 1)
                        if report.p99_ttft_s is not None else None),
        "p99_per_token_ms": (
            round(report.p99_per_token_s * 1e3, 3)
            if report.p99_per_token_s is not None else None),
        "autoscale_up": report.autoscale_up,
        "autoscale_down": report.autoscale_down,
        "rejected": dict(report.rejected),
    }
    return round(report.goodput_tokens_per_s, 2), rider


def measure():
    """Measure train throughput, then every decode tier, in this process;
    any failure propagates."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import train

    cfg, seq, batch = pick_config()
    on_tpu = jax.devices()[0].platform == "tpu"
    seq_chunk = 512 if on_tpu else None
    step = train.make_train_step(cfg, seq_chunk=seq_chunk)
    state = jax.jit(lambda k: train.init_train_state(k, cfg))(
        jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)

    # warmup / compile
    for _ in range(2):
        state, m = step(state, tokens)
    jax.block_until_ready(m["loss"])

    iters = 10 if on_tpu else 3
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = step(state, tokens)
    lossv = float(m["loss"])
    dt = (time.perf_counter() - t0) / iters

    tps = batch * seq / dt
    # a CPU run has no device utilization to report
    mfu = (tps * cfg.flops_per_token(seq) / peak_flops(jax.devices()[0])
           if on_tpu else None)

    from paddle_tpu.models import generate as gen
    db, dp_len, dnew = (8, 128, 64) if on_tpu else (2, 8, 8)
    prompt = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (db, dp_len)), jnp.int32)
    def decode_rate(pp, kv=None):
        """Prefill-subtracted decode tokens/s for a params tree;
        ``kv="int8"`` also quantizes the KV cache (per-row scales,
        in-kernel dequant)."""
        def make(n):
            f = jax.jit(lambda pr: gen.generate(
                pp, pr, cfg, max_new_tokens=n, temperature=0.0,
                kv_cache_dtype=kv))
            np.asarray(f(prompt))              # compile + host fence
            return f

        def timed(f):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(f(prompt))          # host-transfer fence
                best = min(best, time.perf_counter() - t0)
            return best
        g_full, g_one = make(dnew), make(1)
        ddt = timed(g_full) - timed(g_one)
        if ddt <= 0:  # tiny CPU smoke configs: noise swamps the delta
            ddt = timed(g_full)
        return round(db * (dnew - 1) / ddt, 2)

    decode_tps = decode_rate(state.params)

    # int8 weight-only serving variant (decode is HBM-bound; int8 halves
    # the weight bytes)
    int8_params = gen.quantize_weights(state.params, cfg)
    decode_int8_tps = decode_rate(int8_params)

    # per-group int4 variant on the PAGED ENGINE (ISSUE 11): quarter
    # weight bytes through the serving tower the cluster actually
    # ships, not the dense generate() path the slot used to alias
    decode_int4_tps = lowbit_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu, 4)

    # weight-int8 + KV-int8 on the PAGED ENGINE: the serving sweet spot
    # (both weight AND cache HBM traffic halved)
    decode_w8kv8_tps = lowbit_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu, 8,
        kv_cache_dtype="int8")

    # paged KV + continuous batching at MIXED request lengths: the
    # serving-engine tier (paddle_tpu/serving + ContinuousBatchingEngine)
    # — throughput includes the host scheduling loop, i.e. what a server
    # actually ships; the fused-kernel speedup rider travels with it
    decode_paged_tps, decode_fused = paged_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # shared-system-prompt serving: prefix cache + chunked prefill on
    # top of the paged engine — the ISSUE 3 serving-throughput tier
    decode_prefix_tps = prefix_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # SLO-scheduler control plane: oversubscribed two-priority bursty
    # workload (preempt/evict/resume + token-budgeted steps) — the
    # ISSUE 4 tier, with p50/p99 step latency riding the record
    decode_sched = sched_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # speculative decoding on the paged engine: n-gram draft + batched
    # verify over a repetitive workload — the ISSUE 5 tier, with the
    # acceptance rate riding the record
    decode_spec = spec_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # model-based draft + tree speculation (ISSUE 20): truncated-layer
    # draft model proposing a token tree per row, one-forward tree
    # verify, over the NON-repetitive text-mode trace the n-gram
    # proposer can't draft from — throughput + the {tree_width, depth,
    # mean_accepted_path} rider travel together
    decode_treespec = treespec_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # tensor-parallel paged serving over a tp=4 mesh (ISSUE 7): the
    # mixed-length paged workload sharded across chips, with the
    # aggregate-vs-single-chip scaling factor riding the record. The
    # two mesh tiers do not apply to fewer than four devices and are
    # null there
    decode_tp = decode_tp2d = None
    if len(jax.devices()) < 4:
        print("bench: tp and tp2d tiers not run: fewer than 4 devices",
              file=sys.stderr)
    else:
        tp_tps = tp_decode_tier(
            state.params, cfg, db, dp_len, dnew, on_tpu)
        decode_tp = (tp_tps, {
            "tp": 4,
            "vs_single_chip": (round(tp_tps / decode_paged_tps, 3)
                               if decode_paged_tps else None)})

        # 2-D tp x dp serving mesh (ISSUE 17): the same mixed-length
        # paged workload with the decode batch SPLIT over a dp axis on
        # top of tp=2 — db rows per dp shard, so dp multiplies the rows
        # each step advances; the vs-1-D-tp ratio rides the record
        tp2d_tps = tp2d_decode_tier(
            state.params, cfg, db, dp_len, dnew, on_tpu)
        decode_tp2d = (tp2d_tps, {
            "tp": 2, "dp": 2,
            "vs_1d_tp": (round(tp2d_tps / decode_tp[0], 3)
                         if decode_tp[0] else None)})

    # disaggregated serving cluster (ISSUE 9): two replicas behind the
    # prefix-affinity router on a shared-prefix tenant workload, with
    # the cluster-vs-single-engine ratio riding the record
    decode_cluster = cluster_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # hierarchical KV host tier (ISSUE 10): the scheduler tier's bursty
    # preempt workload with swap-out/swap-in instead of evict/replay —
    # swap-in latency + the vs-replay ratio ride the record
    decode_offload = offload_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # goodput-under-SLO (ISSUE 13): the trace-driven traffic harness
    # against the autoscaling cluster — goodput, deadline-met fraction,
    # p99 TTFT and the autoscale event counts ride the record
    decode_slo = slo_goodput_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    # multi-tenant adapter plane (ISSUE 14): many LoRA variants through
    # one engine's slot pool vs the single-merged-model deployment —
    # throughput + the adapter-density rider travel together
    decode_multilora = multilora_decode_tier(
        state.params, cfg, db, dp_len, dnew, on_tpu)

    phases = _capture_phases(step, state, tokens, cfg)

    return _result(tps, mfu, seq, batch, cfg, lossv, decode_tps,
                   decode_int8_tps, decode_int4_tps, decode_w8kv8_tps,
                   decode_paged_tps, decode_prefix_tps,
                   decode_sched=decode_sched, decode_spec=decode_spec,
                   decode_treespec=decode_treespec,
                   decode_tp=decode_tp, decode_tp2d=decode_tp2d,
                   decode_cluster=decode_cluster,
                   decode_offload=decode_offload, decode_slo=decode_slo,
                   decode_fused=decode_fused,
                   decode_multilora=decode_multilora, phases=phases)


if __name__ == "__main__":
    plat = os.environ.get("PADDLE_TPU_BENCH_PLATFORM")
    if plat:
        import jax
        jax.config.update("jax_platforms", plat)
    from paddle_tpu._core.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(json.dumps(measure()))
