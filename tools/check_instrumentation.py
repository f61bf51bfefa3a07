#!/usr/bin/env python
"""Instrumentation lint: the hot paths must keep their telemetry hooks.

The observability layer only attributes time if the hot-path modules
keep emitting their spans/metrics — a refactor that drops one hook
silently degrades every future BENCH_r*.json breakdown. This lint greps
each known hot-path module for its REQUIRED hook call sites and fails
if any went missing. Wired into the tier-1 run as a fast test
(tests/test_instrumentation_lint.py); runnable standalone:

    python tools/check_instrumentation.py
"""
from __future__ import annotations

import os
import sys

# module (repo-relative) -> [(required substring, min occurrences)]
REQUIRED = {
    "paddle_tpu/distributed/fleet/meta_parallel/pipeline_parallel.py": [
        ('_obs.span("PP.forward"', 1),
        ('_obs.span("PP.backward"', 1),
        ('_obs.span("PP.spmd.step"', 2),      # homogeneous + hetero
        ('_obs.span("PP.spmd.scatter"', 2),
        ("_obs.pp_step(", 3),                 # both SPMD paths + accum
    ],
    "paddle_tpu/inference/predictor.py": [
        ("_obs.predictor_run(", 1),
        ("_obs.active()", 1),
        # continuous-batching engine hot path: block-pool utilization
        # gauge + occupancy histogram (serving_step), admission and
        # eviction counters — the serving dashboard's inputs
        ("_obs.serving_step(", 1),
        ("_obs.serving_admitted(", 1),
        ("_obs.serving_retired(", 1),
        # prefix-cache hit/miss token counters (the live hit rate) and
        # the per-chunk prefill latency histogram (the engine's
        # per-step latency bound) — ISSUE 3's serving telemetry
        ("_obs.serving_prefix(", 1),
        ("_obs.serving_prefill_chunk(", 1),
        # preempt/resume lifecycle counters (ISSUE 4): evictions for
        # higher-priority admissions + the replay cost of resumes;
        # queued-request cancellations stay OUT of the eviction counter
        ("_obs.serving_preempted(", 1),
        ("_obs.serving_resumed(", 1),
        ("_obs.serving_cancelled(", 1),
        # speculative decoding (ISSUE 5): drafted/accepted/rollback
        # token counters + the per-step acceptance-rate histogram the
        # adaptive draft length is judged by — dropping this hook
        # blinds the decode_spec bench tier's acceptance record
        ("_obs.serving_spec_verify(", 1),
        # tensor-parallel serving (ISSUE 7): per-shard pool gauge every
        # step + the timed logits-collective probe — the dashboard's
        # only view of the tp collective bill
        ("_obs.serving_tp_step(", 1),
        ("_obs.serving_tp_logits_gather(", 1),
        # fault-injection sites (ISSUE 8): step execution + the
        # device->host transfers (decode AND spec-verify paths)
        ('_fault_point("decode_step")', 1),
        ('_fault_point("prefill_chunk")', 1),
        ('_fault_point("verify_step")', 1),
        ('_fault_point("transfer")', 2),
        # fused serving kernels (ISSUE 11): per-kernel host-timed step
        # latency on all three fused paths (decode / chunk / verify) —
        # the decode_fused_speedup rider's per-kernel breakdown
        ('_obs.serving_fused_latency("decode_rope_attn"', 1),
        ('_obs.serving_fused_latency("chunk_flash_attn"', 1),
        ('_obs.serving_fused_latency("verify_flash_attn"', 1),
        # async overlapped runtime (ISSUE 12): the dispatch/commit
        # seams — decode AND spec paths each fire both sites, so a
        # fault between program launch and host-state commit is
        # injectable (and chaos-soaked) on every step kind
        ('_fault_point("dispatch")', 2),
        ('_fault_point("commit")', 2),
        # sampled speculation (ISSUE 14): drafted/accepted counters +
        # the accept-rate histogram of the rejection-sampled verify
        # commit — the realized 1+k·rate speedup multiplier
        ("_obs.serving_sample_accept(", 1),
        # constrained decoding (ISSUE 14): mask-latency histogram +
        # violation-avoided counter on BOTH commit paths (the prefill
        # first token and the vectorized decode commit)
        ("_obs.serving_constrain(", 2),
        # request tracing (ISSUE 16): span-close sites on every engine
        # lifecycle edge — admission (swap-in AND replay paths), the
        # per-chunk prefill close, the per-row decode/verify closes,
        # preempt/swap-out, and the retire-side finish — dropping one
        # tears a hole in every TTFT breakdown
        ("_obs.serving_trace_admitted(", 2),
        ("_obs.serving_trace_span(", 5),
        ("_obs.serving_trace_finish(", 2),
        ("_obs.serving_trace_first_token(", 2),
        # 2-D serving mesh (ISSUE 17): per-dp-shard batch gauge on
        # both commit paths (decode AND spec verify) — the only view
        # of planner skew across the dp row blocks
        ("_obs.serving_dp_step(", 2),
        # model-based draft + tree speculation (ISSUE 20): the propose
        # counters (rows/drafted/catch-up tokens), the draft-pool
        # occupancy gauge pair, and the fence-anchored tree-verify span
        # with its path-length/acceptance histograms — the
        # decode_treespec bench tier's only inputs; plus the two new
        # fault sites, both firing BEFORE any state commits (a killed
        # propose or verify must leave lengths/pools untouched)
        ("_obs.serving_draft_propose(", 1),
        ("_obs.serving_draft_pool(", 1),
        ("_obs.serving_tree_verify(", 1),
        ('_fault_point("draft_propose")', 1),
        ('_fault_point("tree_verify")', 1),
        # the serving step's spans on the profiler's clock (ISSUE 26):
        # one dispatch per program launched (chunk, decode, verify,
        # tree verify and its placement, draft catch-up and decode),
        # one wait per place the host blocks on a device value, one
        # commit per read, and the first call of each program key
        ('.span("engine.dispatch"', 7),
        ('.span("engine.wait"', 6),
        ('.span("engine.commit"', 5),
        ('.span("engine.build_program"', 1),
        # the counters fed where the engine already knows the numbers
        ('.count("prompt_tokens_total"', 1),
        ('.count("prefix_hit_tokens_total"', 1),
        # sliding-window layers and routed experts (ISSUE 28): the
        # program's period among build_program's fields; pages of the
        # sliding layers' pool released in the chunk and the decode
        # commit; the expert counters, summed on the device and split
        # off the decode step's one read; both pools' peaks in stats()
        ("period=len(self.cfg.period)", 1),
        ('.count("window_pages_released_total"', 2),
        ('"moe_routed_items_total"', 1),
        ('"moe_experts_hit_total"', 1),
        ('"moe_max_expert_load_total"', 1),
        ('"moe_layer_steps_total"', 1),
        ('s["full_pool_used_peak"]', 1),
        ('s["window_pool_used_peak"]', 1),
    ],
    "paddle_tpu/observability/hooks.py": [
        # the ISSUE 20 hook families themselves: the predictor entries
        # above only prove the CALL sites exist — these prove the hook
        # layer still defines them (a hooks.py refactor that drops one
        # def would turn every call site into an AttributeError only
        # at serve time, with metrics enabled)
        ("def serving_draft_propose(", 1),
        ("def serving_draft_pool(", 1),
        ("def serving_tree_verify(", 1),
        ("serving_tree_path_len", 1),
        ("serving_tree_acceptance_rate", 1),
    ],
    "paddle_tpu/serving/scheduler.py": [
        # SLO-scheduler hot path (ISSUE 4): time-in-queue histogram on
        # every admission, per-class queue-depth gauges + the
        # budget-utilization gauge once per planned step
        ("_obs.serving_queue_wait(", 1),
        ("_obs.serving_sched_step(", 1),
        # async overlapped runtime (ISSUE 12): the per-step host-plane
        # attribution (host_overhead_fraction gauge + the
        # serving_sched_step_ms p99 source) and the idle-fence counter
        # of the busy-spin fix — the scoreboard the overlap refactor
        # is judged by
        ("_obs.serving_overlap_step(", 1),
        ("_obs.serving_sched_idle(", 1),
        # fault-injection site (ISSUE 8): the scheduler tick
        ('fault_point("sched_tick")', 1),
        # request tracing (ISSUE 16): trace minting at submission +
        # the queue-wait open on every (re)enqueue — the trace's first
        # edge; requeue re-attaches recovered/preempted handles so
        # cross-lifecycle stitching survives
        ("_obs.serving_trace_submit(", 1),
        ("_obs.serving_trace_enqueued(", 2),
        # the step's own spans (ISSUE 26): host_overhead_fraction is
        # derived from their totals, so dropping one blinds it too
        ('.span("sched.step"', 1),
        ('.span("sched.admit"', 1),
        ('.span("sched.plan"', 2),            # plan, and trim under overlap
        ('.count("queue_wait_ns_total"', 1),
        ('.count("admissions_total"', 1),
    ],
    "paddle_tpu/serving/resilience.py": [
        # fault-tolerant serving (ISSUE 8): injected + real failure
        # counters (fire + catch sides), the recovery-latency
        # histogram, the degraded-mode gauge, the journal-size gauges
        # and both halves of the drain/restore pair — the supervisor
        # is the unit the multi-engine router will replicate, and a
        # blind supervisor cannot be routed around
        ("_obs.serving_fault(", 2),
        ("_obs.serving_fault_recovery(", 1),
        ("_obs.serving_degraded(", 2),        # ladder moves + dead
        ("_obs.serving_journal(", 1),
        ("_obs.serving_drain_checkpoint(", 1),
        ("_obs.serving_drain_restore(", 1),
        # durable journal plane (ISSUE 15): the cold-restart recovery
        # gauge/counters — a recovery that replays sessions invisibly
        # would make the crash-durability story unauditable
        ("_obs.serving_wal_recovery(", 1),
        # flight recorder (ISSUE 16): the per-tick ring append, the
        # dump counter on every black-box write, and the wal_replay
        # span on each recovered session — a crash with no flight dump
        # is an unauditable crash
        ("_obs.serving_flight_tick(", 1),
        ("_obs.serving_flight_dump(", 1),
        ("_obs.serving_trace_span(", 1),
    ],
    "paddle_tpu/serving/wal.py": [
        # durable WAL (ISSUE 15): per-record append counter/bytes/
        # latency, the fsync-ladder latency pair, and the incremental-
        # checkpoint triple — the fsync-policy overhead model's inputs
        # (PERF_NOTES 'Durability', decode_durability_overhead rider)
        ("_obs.serving_wal_append(", 1),
        ("_obs.serving_wal_fsync(", 1),
        ("_obs.serving_wal_checkpoint(", 1),
        # fault sites: append BEFORE the frame write, fsync before the
        # fsync, checkpoint before the file — none commits anything
        ('fault_point("wal_append")', 1),
        ('fault_point("wal_fsync")', 1),
        ('fault_point("checkpoint_write")', 1),
        # torn-write tamper: half a frame reaches disk and the 'process
        # dies' — recovery's tail truncation is what gets exercised
        ('tamper_point("wal_append")', 1),
    ],
    "paddle_tpu/serving/paged_cache.py": [
        # fault-injection sites (ISSUE 8): allocator alloc/free
        ('fault_point("alloc")', 1),
        ('fault_point("free")', 1),
        # fused page gather/scatter (ISSUE 11): the one donated move
        # program shared by defrag compaction and the direct handoff —
        # its latency histogram is the only visibility into device
        # page-move cost (the host-staged path's bytes counters don't
        # see it)
        ('_obs.serving_fused_latency("pool_move"', 1),
    ],
    "paddle_tpu/serving/traffic.py": [
        # trace-driven traffic harness (ISSUE 13): per-request TTFT +
        # deadline outcome, goodput/badput token split, and the
        # end-of-run summary gauges — the serving_slo_* family the
        # decode_slo_goodput bench tier records
        ("_obs.serving_slo_ttft(", 1),
        ("_obs.serving_slo_tokens(", 1),
        ("_obs.serving_slo_report(", 1),
    ],
    "paddle_tpu/serving/adapters.py": [
        # multi-tenant adapter plane (ISSUE 14): slot residency gauges
        # on every pool mutation, the install latency/bytes pair split
        # by source (fresh load vs host-store promote), the demote
        # counter+bytes of LRU slot reclaim, and the corrupt-payload
        # fallback counter — the serving_adapter_* family the
        # decode_multilora bench rider and the PERF_NOTES
        # adapter-bandwidth model read
        ("_obs.serving_adapter_slots(", 1),
        ("_obs.serving_adapter_load(", 1),
        ("_obs.serving_adapter_demoted(", 1),
        ("_obs.serving_adapter_fallback(", 1),
        # fault-injection sites: fresh load + host-store promotion —
        # both fire BEFORE any install-side mutation
        ('fault_point("adapter_load")', 1),
        ('fault_point("adapter_promote")', 1),
    ],
    "paddle_tpu/serving/host_tier.py": [
        # hierarchical KV tier (ISSUE 10): both halves of the
        # swap pair (bytes/pages + transfer latency — the
        # swap-vs-replay crossover model's inputs), the replay
        # fallback counter (the honest cost of bounding host RAM),
        # the host-pool occupancy gauges, and the demote/promote
        # counters that make the prefix tier's hit economy visible
        ("_obs.serving_swap_out(", 1),
        ("_obs.serving_swap_in(", 1),
        ("_obs.serving_swap_fallback(", 1),
        ("_obs.serving_host_pool(", 1),
        ("_obs.serving_prefix_demoted(", 1),
        ("_obs.serving_prefix_promoted(", 1),
        # fault-injection sites: swap-out BEFORE the gather, swap-in
        # BEFORE the allocation — both commit nothing when they fire
        ('fault_point("swap_out")', 1),
        ('fault_point("swap_in")', 1),
        # disk-bound pruning (ISSUE 15 satellite): the pruned-files/
        # bytes pair next to the corrupt-unlink counter
        ("_obs.serving_host_disk_pruned(", 1),
        # payload integrity (ISSUE 13): detection/quarantine/replay
        # events on the swap and promote paths + the bounded-retry
        # counter — the serving_integrity_* family the integrity gate
        # audits (detected == quarantined + replayed arithmetic)
        ("_obs.serving_integrity(", 4),
        ("_obs.serving_integrity_retry(", 1),
        ('tamper_point("swap_in")', 1),
    ],
    "paddle_tpu/serving/cluster.py": [
        # disaggregated cluster (ISSUE 9): both halves of the
        # prefill→decode handoff pair (bytes/pages moved + latency —
        # the PERF_NOTES cost model's inputs), the failover/rehome
        # counter (zero-lost-requests is only provable if rehomes are
        # visible) and the per-replica load gauges the registry-side
        # signal bus publishes each step
        ("_obs.serving_handoff_export(", 1),
        ("_obs.serving_handoff_import(", 1),
        ("_obs.serving_router_failover(", 1),
        ("_obs.serving_router_replica(", 1),
        # overload hardening (ISSUE 13): the autoscaler's event
        # counter + gauges on BOTH scale directions, the handoff
        # integrity events (a corrupt payload detected before install)
        # and the bounded-retry counter, plus the three cluster-plane
        # fault sites (export/import halves of the handoff and the
        # autoscale control tick — also enforced by check_fault_sites)
        ("_obs.serving_autoscale(", 2),
        ("_obs.serving_integrity(", 2),
        ("_obs.serving_integrity_retry(", 1),
        ('fault_point("handoff_export")', 1),
        ('fault_point("handoff_import")', 1),
        ('fault_point("autoscale_tick")', 1),
        # request tracing (ISSUE 16): router-lane minting at submit,
        # both halves of the handoff span pair (the cross-replica
        # stitch), and the structured-rejection finishes — dropping
        # one breaks the one-trace-per-request contract
        ("_obs.serving_trace_submit(", 1),
        ("_obs.serving_trace_span(", 2),
        ("_obs.serving_trace_finish(", 3),
    ],
    "paddle_tpu/serving/rpc.py": [
        # multi-process control plane (ISSUE 19): the per-call
        # latency/bytes pair on the client side + the served-side
        # decode/dispatch/encode latency, the bounded-retry counter,
        # the timeout counter and the corrupt-frame counter (client
        # CRC/torn detection AND the server's two inbound-frame
        # rejections) — the serving_rpc_* family the
        # decode_multiproc_overhead bench rider reads
        ("_obs.serving_rpc_call(", 1),
        ("_obs.serving_rpc_served(", 1),
        ("_obs.serving_rpc_retry(", 1),
        ("_obs.serving_rpc_timeout(", 1),
        ("_obs.serving_rpc_corrupt(", 4),
        # fault-injection sites: immediately BEFORE the frame send and
        # immediately AFTER the reply recv — both inside the bounded
        # retry loop, so an injected drop exercises the idempotent
        # retry + server dedupe path end to end
        ('fault_point("rpc_send")', 1),
        ('fault_point("rpc_recv")', 1),
    ],
    "paddle_tpu/serving/fabric.py": [
        # shared KV fabric (ISSUE 19): demote (put) latency/bytes,
        # promote (get) latency/bytes split by hit/miss, and the
        # quarantine counter on all three corruption seams — the
        # server's inbound CRC gate, the client's post-fetch verify
        # and the explicit peer-initiated quarantine RPC
        ("_obs.serving_fabric_demote(", 1),
        ("_obs.serving_fabric_promote(", 4),
        ("_obs.serving_fabric_quarantine(", 3),
        # fault sites: put BEFORE the demote RPC, get BEFORE the
        # promote RPC — neither commits anything when it fires
        ('fault_point("fabric_put")', 1),
        ('fault_point("fabric_get")', 1),
    ],
    "paddle_tpu/serving/node.py": [
        # replica worker (ISSUE 19): trace lanes must re-open node-side
        # on BOTH ingress edges (fresh dispatch submit and the decode
        # half of a cross-process handoff adopt) or the stitched trace
        # the controller folds together loses every worker-side span
        ("_obs.serving_trace_submit(", 2),
    ],
    "paddle_tpu/serving/router.py": [
        # cluster router (ISSUE 9): per-dispatch replica + affinity
        # hit/miss counters (the live prefix-affinity hit rate), the
        # shed-work retry counter and the rate-limit rejection counter
        ("_obs.serving_router_dispatch(", 1),
        ("_obs.serving_router_retry(", 1),
        ("_obs.serving_router_ratelimited(", 1),
        # ISSUE 13: the SLO-guarded admission rejection counter
        # (deadline-infeasible at the door) and the retry-budget
        # exhaustion counter (counted separately from first-try
        # rejection — the satellite's whole point)
        ("_obs.serving_slo_rejected(", 1),
        ("_obs.serving_router_retry_exhausted(", 1),
    ],
    "paddle_tpu/models/generate.py": [
        ("_obs.generate_begin()", 1),
        ('_obs.generate_phase("prefill"', 1),
        ('_obs.generate_phase("decode"', 1),
        # tensor-parallel serving (ISSUE 7): every traced all-gather in
        # the tp decode/prefill/verify programs counts its calls +
        # per-shard payload bytes (once per compile, like hooks.
        # collective) — dropping it blinds the tp collective counters
        ("_obs.serving_tp_allgather(", 1),
        # fused serving kernels (ISSUE 11): trace-time dispatch +
        # bytes-saved counters on BOTH fused branches (the decode
        # rope+attn fusion and the chunk/verify flash fusion) —
        # dropping one silently un-counts every launch of that kernel
        ("_obs.serving_fused_dispatch(", 2),
        # multi-LoRA serving (ISSUE 14): the trace-time adapter factor
        # gather counter — the per-step adapter bytes every compiled
        # adapter-augmented program bills (the rank-r bytes/token
        # model's live input; the serving_tp_allgather contract)
        ("_obs.serving_adapter_gather(", 1),
        # expert-parallel MoE decode (ISSUE 17): the trace-time
        # all-to-all dispatch counter at the EP branch of _moe_ffn —
        # calls, per-shard payload bytes and the routed-tokens
        # histogram (the serving_tp_allgather contract)
        ("_obs.serving_moe_dispatch(", 1),
    ],
    "paddle_tpu/io/dataloader.py": [
        ("_obs.dataloader_next(", 2),         # single-process + prefetch
        ("_obs.active()", 2),
    ],
    "paddle_tpu/distributed/collective.py": [
        ("_obs.collective(", 12),             # one per collective entry
        ('_obs.collective("all_reduce"', 1),
        ('_obs.collective("all_gather"', 1),
        ('_obs.collective("send_recv"', 1),
    ],
    "paddle_tpu/distributed/watchdog.py": [
        ("_obs.watchdog_tick(", 1),
        ("_obs.watchdog_fired(", 1),
    ],
    "paddle_tpu/profiler/utils.py": [
        ('RecordEvent("Optimizer.step"', 1),
    ],
    "bench.py": [
        ("phase_summary()", 1),
        ('"phases"', 1),
    ],
}


#: modules allowed to host fault-injection call sites (the serving hot
#: path) — the site-coverage rule greps these
_FAULT_SITE_MODULES = (
    "paddle_tpu/serving/paged_cache.py",
    "paddle_tpu/serving/scheduler.py",
    "paddle_tpu/serving/host_tier.py",
    "paddle_tpu/serving/cluster.py",
    "paddle_tpu/serving/adapters.py",
    "paddle_tpu/serving/wal.py",
    "paddle_tpu/serving/rpc.py",
    "paddle_tpu/serving/fabric.py",
    "paddle_tpu/inference/predictor.py",
)


def check_fault_sites(root: str) -> list:
    """ISSUE 8 rule: every FaultInjector site name declared in
    ``serving/resilience.py``'s ``SITES`` tuple must have a matching
    ``fault_point("<site>")`` call threaded through a hot-path module —
    a declared-but-unthreaded site would silently produce NO
    ``serving_fault_*{site=...}`` counter label, and chaos coverage of
    that site would be a no-op that still claims the site was
    exercised."""
    import re
    problems = []
    res_path = os.path.join(root, "paddle_tpu/serving/resilience.py")
    if not os.path.exists(res_path):
        return [f"paddle_tpu/serving/resilience.py: file missing"]
    with open(res_path, encoding="utf-8") as f:
        src = f.read()
    # SITES is composed from the engine-plane and cluster-plane
    # tuples (ISSUE 13) — collect the declared names from both
    sites = []
    for name in ("ENGINE_SITES", "CLUSTER_SITES"):
        m = re.search(rf"^{name}\s*=\s*\(([^)]*)\)", src, re.M)
        if not m:
            return [f"paddle_tpu/serving/resilience.py: {name} "
                    f"tuple missing"]
        sites += re.findall(r"\"([a-z_]+)\"", m.group(1))
    if not sites:
        return ["paddle_tpu/serving/resilience.py: SITES tuples empty"]
    hot = ""
    for rel in _FAULT_SITE_MODULES:
        path = os.path.join(root, rel)
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                hot += f.read()
    for site in sites:
        if f'fault_point("{site}")' not in hot:
            problems.append(
                f"paddle_tpu/serving/resilience.py: SITES declares "
                f"{site!r} but no hot-path module calls "
                f"fault_point(\"{site}\") — the serving_fault_* "
                f"counters would never carry that site label")
    return problems


#: the sync-point discipline of the overlapped runtime (ISSUE 12):
#: module -> function names whose bodies must stay FREE of device→host
#: sync idioms (single-argument ``np.asarray(...)`` fetches and
#: ``block_until_ready``). None = the whole module. The scheduler's
#: host plane and the engine's DISPATCH-path functions plan and launch
#: only — every fetch of a step result belongs in the commit helpers
#: (_decode_commit / _spec_commit / _commit_chunk), or the overlap
#: pipeline silently degrades back to a synchronous chain.
_SYNC_FREE = {
    "paddle_tpu/serving/scheduler.py": None,
    # _tree_dispatch launches the one-forward tree verify and must not
    # fetch its logits or KV rows (both ride the InFlightStep to
    # _tree_commit); _propose_model_drafts is deliberately NOT listed —
    # the draft loop is sequential by construction (each draft token
    # feeds the next step), so its per-step logits fetch is the design,
    # not a regression
    "paddle_tpu/inference/predictor.py": (
        "decode_dispatch", "spec_dispatch", "prefill_dispatch",
        "ready_mask", "propose_drafts", "spec_plan_widths",
        "_tree_dispatch"),
    # the tracing layer (ISSUE 16) runs INSIDE the hot path on every
    # span close — it must never fetch a device value or fence; its
    # zero-device-syncs contract is what lets call sites fire between
    # dispatch and commit
    "paddle_tpu/observability/tracing.py": None,
    # the RPC layer (ISSUE 19) frames host bytes only — it must never
    # import jax or fetch a device value; KV payloads reach it already
    # exported as host numpy views, and keeping it device-blind is
    # what lets the fabric server run as a jax-free process
    "paddle_tpu/serving/rpc.py": None,
}

#: device-sync idioms: a bare one-argument np.asarray (dtype-annotated
#: conversions of host arrays pass — they never touch device values on
#: these paths) and any block_until_ready
_SYNC_RE = (r"(?<!j)np\.asarray\([^,()]*(\([^()]*\))?[^,()]*\)(?!\s*,)",
            r"block_until_ready")


def _function_bodies(src: str, names) -> str:
    """Concatenate the bodies of the named top-level-in-class defs
    (selected by indentation: a body line is any line more indented
    than its ``def``)."""
    import re
    out = []
    lines = src.splitlines()
    for name in names:
        for i, line in enumerate(lines):
            m = re.match(rf"(\s*)def {re.escape(name)}\(", line)
            if not m:
                continue
            indent = len(m.group(1))
            j = i + 1
            while j < len(lines):
                ln = lines[j]
                if ln.strip() and (len(ln) - len(ln.lstrip())) <= indent:
                    break
                out.append(ln)
                j += 1
    return "\n".join(out)


def check_sync_points(root: str) -> list:
    """ISSUE 12 rule: no ``np.asarray`` / ``block_until_ready`` on
    step results outside the commit helpers in the scheduler/predictor
    hot paths. The textual heuristic flags single-argument
    ``np.asarray(x)`` (the device-fetch idiom) and any
    ``block_until_ready`` inside the :data:`_SYNC_FREE` scopes —
    dtype-annotated conversions (``np.asarray(x, np.int32)``) are
    host-side and pass."""
    import re
    problems = []
    for rel, names in _SYNC_FREE.items():
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            problems.append(f"{rel}: file missing")
            continue
        with open(path, encoding="utf-8") as f:
            src = f.read()
        scope = src if names is None else _function_bodies(src, names)
        where = ("module" if names is None
                 else "dispatch-path functions " + "/".join(names))
        for pat in _SYNC_RE:
            for m in re.finditer(pat, scope):
                problems.append(
                    f"{rel}: device-sync idiom {m.group(0)!r} in the "
                    f"{where} — step results must be fetched only in "
                    f"the commit helpers (the overlapped runtime's "
                    f"single-fence contract, ISSUE 12)")
    return problems


def check(root: str) -> list:
    """Returns a list of human-readable violation strings (empty = ok)."""
    problems = check_fault_sites(root) + check_sync_points(root)
    for rel, rules in REQUIRED.items():
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            problems.append(f"{rel}: file missing")
            continue
        with open(path, encoding="utf-8") as f:
            src = f.read()
        for needle, min_count in rules:
            n = src.count(needle)
            if n < min_count:
                problems.append(
                    f"{rel}: expected >= {min_count} occurrence(s) of "
                    f"{needle!r}, found {n} — a telemetry hook was "
                    f"dropped (see paddle_tpu/observability/hooks.py)")
    return problems


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    problems = check(root)
    if problems:
        for p in problems:
            print(f"check_instrumentation: {p}", file=sys.stderr)
        return 1
    print(f"check_instrumentation: {len(REQUIRED)} hot-path modules ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
