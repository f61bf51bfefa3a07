"""Standalone serving/decode tier bench.

This tool measures ONLY the decode tiers of bench.py — fp bf16, the paged
continuous-batching engine (with the fused-kernel speedup rider), the
prefix-cache + chunked-prefill shared-system-prompt engine, int8
weight-only (dense), and the LOW-BIT PAGED tiers (per-group-int4 weights
and int8-weight+int8-KV on the serving engine itself — ISSUE 11) — on
freshly initialized weights (decode throughput does not depend on weight
values).

Prints one JSON line:
  {"decode_tokens_per_sec": ..., "decode_paged_tokens_per_sec": ...,
   "decode_prefix_tokens_per_sec": ..., "decode_sched_tokens_per_sec": ...,
   "decode_sched_step_ms": {"p50_step_ms": ..., "p99_step_ms": ...},
   "decode_spec_tokens_per_sec": ...,
   "decode_spec_acceptance": {"acceptance_rate": ...,
                              "nonrepetitive": {...}, ...},
   "decode_treespec_tokens_per_sec": ...,
   "decode_treespec_stats": {"tree_width": ..., "depth": ...,
                             "mean_accepted_path": ..., ...},
   "decode_tp_tokens_per_sec": ...,
   "decode_tp_scaling": {"tp": 4, "vs_single_chip": ...},
   "decode_int8_tokens_per_sec": ..., "decode_int4_tokens_per_sec": ...,
   "decode_w8kv8_tokens_per_sec": ..., "device": ...,
   "ratios_vs_fp": {...}}

Runs on the chip, in this process; a tier that fails is a traceback and a
non-zero exit. ``PADDLE_TPU_BENCH_PLATFORM=cpu`` is the explicit CPU smoke
(tiny config; its numbers say nothing about a chip).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp

    plat = os.environ.get("PADDLE_TPU_BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    import bench as bench_mod
    from paddle_tpu._core.compile_cache import enable_compile_cache
    enable_compile_cache()
    from paddle_tpu.models import generate as gen
    from paddle_tpu.models import train

    cfg, seq, _batch = bench_mod.pick_config()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    params = jax.jit(
        lambda k: train.init_train_state(k, cfg).params)(jax.random.key(0))

    db, dp_len, dnew = (8, 128, 64) if on_tpu else (2, 8, 8)
    prompt = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (db, dp_len)), jnp.int32)

    def decode_rate(pp, kv=None):
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
        if ddt <= 0:   # tiny CPU smoke configs: noise swamps the delta
            ddt = timed(g_full)
        return round(db * (dnew - 1) / ddt, 2)

    out = {"device": dev.device_kind if on_tpu else dev.platform,
           "batch": db, "prompt_len": dp_len, "new_tokens": dnew,
           "params": cfg.num_params()}
    tiers = {}

    def run_tier(tag, fn):
        t0 = time.perf_counter()
        tiers[tag] = fn()
        print(f"{tag}: {tiers[tag]} tok/s "
              f"({time.perf_counter() - t0:.0f}s incl. compile)",
              file=sys.stderr)

    run_tier("decode_tokens_per_sec", lambda: decode_rate(params))

    # shared workload with bench.py's tier (same mix, oversubscription,
    # page-size rule) so the two decode_paged sources stay comparable;
    # the fused-kernel speedup rider (ISSUE 11 — per-step ms unfused vs
    # fused + the ratio) rides the record next to the number it explains
    def _paged():
        tps, fused = bench_mod.paged_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        if fused:
            out["decode_fused_speedup"] = fused
        return tps
    run_tier("decode_paged_tokens_per_sec", _paged)
    # shared-system-prompt workload (prefix cache + chunked prefill),
    # also shared with bench.py so both sources stay comparable
    run_tier("decode_prefix_tokens_per_sec",
             lambda: bench_mod.prefix_decode_tier(
                 params, cfg, db, dp_len, dnew, on_tpu))

    # SLO-scheduler control plane (ISSUE 4): oversubscribed
    # two-priority bursty workload with preempt/evict/resume under a
    # token-budgeted step planner — also shared with bench.py; the
    # p50/p99 step-latency dict rides the record separately, and the
    # ISSUE 12 overlap rider (sync vs double-buffered step ms +
    # host_overhead_fraction) rides next to it
    def _sched():
        tps, lat, ov, dur, trc = bench_mod.sched_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        out["decode_sched_step_ms"] = lat
        if ov:
            out["decode_overlap_speedup"] = ov
        if dur:
            # durability rider (ISSUE 15): WAL fsync-ladder overhead
            # vs the journal-off baseline on the same workload
            out["decode_durability_overhead"] = dur
        if trc:
            # trace rider (ISSUE 16): request tracing ON vs the plain
            # run — the measured price of the observability switch
            out["decode_trace_overhead"] = trc
        return tps
    run_tier("decode_sched_tokens_per_sec", _sched)

    # speculative decoding (ISSUE 5): n-gram draft + batched verify on
    # a repetitive workload — acceptance rate rides the record next to
    # the throughput it explains
    def _spec():
        tps, acc = bench_mod.spec_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        out["decode_spec_acceptance"] = acc
        return tps
    run_tier("decode_spec_tokens_per_sec", _spec)

    # model-based draft + tree speculation (ISSUE 20): truncated-layer
    # draft model + one-forward tree verify on the NON-repetitive
    # text-mode trace — the {tree_width, depth, mean_accepted_path}
    # rider rides next to the throughput it explains
    def _treespec():
        tps, stats = bench_mod.treespec_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        out["decode_treespec_stats"] = stats
        return tps
    run_tier("decode_treespec_tokens_per_sec", _treespec)

    # tensor-parallel paged serving (ISSUE 7): the mixed-length paged
    # workload over a tp=4 serving mesh, with the aggregate-vs-single-
    # chip scaling factor riding the record. The two mesh tiers do not
    # apply to fewer than four devices and are null there
    four = len(jax.devices()) >= 4
    if not four:
        print("tp and tp2d tiers not run: fewer than 4 devices",
              file=sys.stderr)

    def _tp():
        tps = bench_mod.tp_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        paged = tiers.get("decode_paged_tokens_per_sec")
        out["decode_tp_scaling"] = {
            "tp": 4,
            "vs_single_chip": round(tps / paged, 3) if paged else None}
        return tps
    if four:
        run_tier("decode_tp_tokens_per_sec", _tp)

    # 2-D tp x dp serving mesh (ISSUE 17): the same workload with the
    # decode batch split over a dp axis on top of tp=2 — db rows per
    # dp shard; the vs-1-D-tp ratio rides the record
    def _tp2d():
        tps = bench_mod.tp2d_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        tp1d = tiers.get("decode_tp_tokens_per_sec")
        out["decode_tp2d_scaling"] = {
            "tp": 2, "dp": 2,
            "vs_1d_tp": round(tps / tp1d, 3) if tp1d else None}
        return tps
    if four:
        run_tier("decode_tp2d_tokens_per_sec", _tp2d)

    # disaggregated serving cluster (ISSUE 9): two replicas behind the
    # prefix-affinity router on a shared-prefix tenant workload — the
    # cluster-vs-single-engine ratio rides the record next to the
    # throughput it explains, same contract as the other riders
    def _cluster():
        tps, scaling = bench_mod.cluster_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        out["decode_cluster_scaling"] = scaling
        return tps
    run_tier("decode_cluster_tokens_per_sec", _cluster)

    # hierarchical KV host tier (ISSUE 10): the bursty preempt workload
    # with swap-out/swap-in resume — swap-in latency p50 and the
    # vs-replay-prefill ratio ride the record next to the throughput
    def _offload():
        tps, resume = bench_mod.offload_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        out["decode_offload_resume"] = resume
        return tps
    run_tier("decode_offload_tokens_per_sec", _offload)

    # goodput-under-SLO (ISSUE 13): the trace-driven traffic harness
    # against the autoscaling cluster — deadline-met fraction, p99
    # TTFT and the autoscale event counts ride the record next to the
    # goodput they explain, same contract as the other riders
    def _slo():
        tps, metrics = bench_mod.slo_goodput_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        out["decode_slo_metrics"] = metrics
        return tps
    run_tier("decode_slo_goodput_tokens_per_sec", _slo)

    # multi-tenant adapter plane (ISSUE 14): many LoRA variants through
    # one engine's slot pool vs the single-merged-model deployment —
    # the adapter-density rider (slot hits, demote/promote churn, the
    # vs-merged ratio) rides next to the throughput it explains
    def _multilora():
        tps, density = bench_mod.multilora_decode_tier(
            params, cfg, db, dp_len, dnew, on_tpu)
        out["decode_multilora_density"] = density
        return tps
    run_tier("decode_multilora_tokens_per_sec", _multilora)
    int8_p = {}

    def _int8():
        int8_p["p"] = gen.quantize_weights(params, cfg)
        return decode_rate(int8_p["p"])
    run_tier("decode_int8_tokens_per_sec", _int8)
    # low-bit PAGED-ENGINE tiers (ISSUE 11): int4 weights and w8/kv8 on
    # the serving tower itself (same workload as decode_paged — the
    # ratio against it IS the low-bit bandwidth win); these two slots
    # had never produced a number while they aliased the dense path
    run_tier("decode_int4_tokens_per_sec",
             lambda: bench_mod.lowbit_decode_tier(
                 params, cfg, db, dp_len, dnew, on_tpu, 4))
    run_tier("decode_w8kv8_tokens_per_sec",
             lambda: bench_mod.lowbit_decode_tier(
                 params, cfg, db, dp_len, dnew, on_tpu, 8,
                 kv_cache_dtype="int8"))

    out.update({k: tiers.get(k) for k in (
        "decode_tokens_per_sec", "decode_paged_tokens_per_sec",
        "decode_prefix_tokens_per_sec", "decode_sched_tokens_per_sec",
        "decode_spec_tokens_per_sec",
        "decode_treespec_tokens_per_sec", "decode_tp_tokens_per_sec",
        "decode_tp2d_tokens_per_sec",
        "decode_cluster_tokens_per_sec",
        "decode_offload_tokens_per_sec",
        "decode_slo_goodput_tokens_per_sec",
        "decode_multilora_tokens_per_sec",
        "decode_int8_tokens_per_sec", "decode_int4_tokens_per_sec",
        "decode_w8kv8_tokens_per_sec")})
    fp = tiers.get("decode_tokens_per_sec")
    if fp:
        out["ratios_vs_fp"] = {
            k.replace("_tokens_per_sec", ""): round(v / fp, 3)
            for k, v in tiers.items() if v}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
