#!/usr/bin/env python
"""Chaos soak for the fault-tolerant serving supervisor (ISSUE 8).

Runs a SEEDED mixed workload — chunked prefill, plain decode,
speculative verify, priority preemption — through an
:class:`~paddle_tpu.serving.EngineSupervisor` while a deterministic
:class:`~paddle_tpu.serving.FaultInjector` fires at least ``--faults``
faults across EVERY hot-path site (allocator alloc/free, decode /
prefill-chunk / verify execution, device→host transfer, scheduler
tick, host-tier swap out/in, the overlapped runtime's dispatch/commit
seams — ISSUE 12 — the adapter plane's load/promote sites with
multi-LoRA traffic live — ISSUE 14 — and the draft-model tree
speculation plane's propose/verify sites via a second supervised
engine — ISSUE 20; raise + stall + corrupt modes),
then asserts the invariants that make recovery trustworthy:

- **zero lost requests** — every submitted request finishes with a
  structured reason (eos / max_len / rejected_overload when the
  degraded ladder sheds LOW traffic);
- **zero duplicated requests** — every completed request's token
  stream is EXACTLY the uninterrupted reference (bit-identical; a
  double-committed or replayed-twice token would show here);
- **balanced allocator** — the final engine drains to zero pages in
  use with ``allocs_total == frees_total`` once the prefix trie drops
  its references;
- **every fault visible** — the ``serving_fault_injected_total``
  counters account for every injector firing, per site.

Usage (seeded, CPU-friendly; also wired into tier-1 through
tests/test_resilience.py):

    JAX_PLATFORMS=cpu python tools/chaos_soak.py --seed 0 --faults 50
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


class SoakError(AssertionError):
    """A soak invariant failed (the tool's single failure type)."""


def _speculator(spec_k):
    """Deterministic always-draft speculator: proposes the last history
    token repeated — verify runs every step (exercising the
    verify/transfer sites) and drafts are accepted exactly when the
    model truly repeats, so greedy output stays bit-identical by the
    standard acceptance rule."""
    from paddle_tpu.serving import Speculator

    class _RepeatLast(Speculator):
        def propose(self, slot, rid, history, cap=None):
            k = self.max_k if cap is None else min(self.max_k, int(cap))
            if k <= 0 or len(history) == 0:
                return np.zeros((0,), np.int32)
            return np.full((k,), history[-1], np.int32)

    return _RepeatLast(spec_k)


def run_soak(seed: int = 0, faults: int = 50, requests: int = 24,
             max_steps: int = 20000, stall_faults: int = 2,
             tp: int = None, dp: int = 1) -> dict:
    """One seeded soak; returns the report dict (raises
    :class:`SoakError` on any invariant violation).

    ``tp``/``dp`` (ISSUE 17) put the SOAKED engine on a
    ``serving_mesh(tp, dp)`` while the per-request references stay
    single-chip — the parity gate then doubles as the 2-D-mesh
    identity gate under fault fire: every recovery rebuild, swap
    round-trip and journal replay must reproduce the single-chip
    token streams exactly."""
    import tempfile

    import jax
    from paddle_tpu import observability as obs
    from paddle_tpu.models import llama
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving import (AdapterPool, AdapterRegistry,
                                    EngineDead, EngineSupervisor,
                                    FaultInjector, HostPageStore,
                                    InjectedFault, Priority, init_lora)
    from paddle_tpu.serving.resilience import ENGINE_SITES as SITES

    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = None
    if tp:
        from paddle_tpu.distributed.mesh import serving_mesh
        if len(jax.devices()) < tp * dp:
            raise RuntimeError(
                f"soak tp={tp} x dp={dp} needs {tp * dp} devices")
        mesh = serving_mesh(tp, dp)
    rs = np.random.RandomState(seed)
    spec_k = 2
    # adapter plane (ISSUE 14): three LoRA variants over a TWO-slot
    # pool with a host store below it — cycling adapter ids through
    # the workload forces loads, LRU evictions (demote) and
    # promotions, so the adapter_load / adapter_promote fault sites
    # get organic visits under the same zero-lost/zero-duplicated
    # gate. One registry describes the population; the supervisor's
    # pool is SHARED across recovery rebuilds (the host-tier pattern:
    # pool state commits at admission, never mid-step) while the
    # reference engine gets its own pool so reference runs never
    # touch the soaked pool's residency.
    registry = AdapterRegistry(cfg)
    for aid in (1, 2, 3):
        registry.register(aid, init_lora(cfg, 4, seed=100 + aid))

    def make_pool(reference=False):
        # the pool's B factors shard with the weights, so the soaked
        # pool is built on the soak mesh (if any) and the reference
        # pool stays single-chip like its engine
        return AdapterPool(cfg, slots=2, rank=4, registry=registry,
                           store=HostPageStore(page_size=8),
                           mesh=None if reference else mesh)

    soak_pool = make_pool()

    def factory(pool=None, reference=False):
        # host tier ON (ISSUE 10): preemptions swap out / resumes swap
        # in, so the soak's fault stream also exercises the swap_out /
        # swap_in sites under the same zero-lost/zero-duplicated gate.
        # overlap ON (ISSUE 12): the supervisor's scheduler runs the
        # double-buffered pipeline — faults at the new dispatch/commit
        # seams (and between them) must recover token-identically via
        # journal replay, and preemption swap-outs go through the
        # async DMA + commit-fence path. The per-request references
        # run through engine.generate(), which is synchronous
        # regardless of the knob — so the soak's parity gate is ALSO
        # the overlap-vs-sync identity gate, under fault fire.
        # On a 2-D mesh (ISSUE 17) the soaked engine scales its batch
        # to 3 rows PER dp shard while references stay single-chip at
        # max_batch=3: per-shard geometry matches the reference's, so
        # the parity check below is exactly the 2-D identity gate.
        mb = 3 if (reference or mesh is None) else 3 * dp
        return ContinuousBatchingEngine(
            params, cfg, max_batch=mb, page_size=8, max_len=48,
            prefill_chunk=8, spec_k=spec_k,
            speculator=_speculator(spec_k), host_tier=True,
            overlap=True, mesh=None if reference else mesh,
            adapters=pool if pool is not None else soak_pool)

    # mixed workload: long prompts (multi-chunk prefill), short ones,
    # repetitive motifs (accepted drafts), three priority classes
    # (HIGH admissions preempt LOW runners); every request cycles
    # through adapter ids 0..3 (0 = base) so the 2-slot pool churns
    jobs = []
    for i in range(requests):
        # the motif (draftable) job leads: spec verify only runs at
        # degraded level 0, and the armed-fault ramp starts escalating
        # the ladder within a few admissions — the first verify must
        # happen before that (ISSUE 15 widened the armed set, which
        # pushed the old ordering's first verify past the first rung)
        kind = (i + 2) % 4
        aid = i % 4                                # adapter id 0..3
        if kind == 0:
            n = int(rs.randint(18, 30))            # chunked prefill
        elif kind == 1:
            n = int(rs.randint(3, 8))              # short
        elif kind == 2:
            motif = rs.randint(3, cfg.vocab_size, (3,))
            jobs.append((np.tile(motif, 5).astype(np.int32)[:14],
                         int(rs.randint(4, 7)),
                         Priority(int(rs.randint(0, 3))), aid))
            continue
        else:
            n = int(rs.randint(8, 16))
        jobs.append((rs.randint(3, cfg.vocab_size, (n,)).astype(np.int32),
                     int(rs.randint(4, 7)),
                     Priority(int(rs.randint(0, 3))), aid))

    # uninterrupted references, one engine run per request (per-row
    # greedy decode is independent of batch composition — the PR 2-5
    # parity gates — so per-request references are exact)
    ref_engine = factory(pool=make_pool(reference=True),
                         reference=True)

    def ref_run(p, m, aid=0):
        r = ref_engine.submit(p, max_new_tokens=m, adapter_id=aid)
        ref_engine.run()
        return np.asarray(r.output)

    refs = [ref_run(p, m, aid) for p, m, _, aid in jobs]

    was = obs.metrics_enabled()
    obs.REGISTRY.clear()
    obs.enable()
    t_start = time.perf_counter()
    try:
        inj = FaultInjector(
            seed=seed, rate=0.02, modes=("raise", "corrupt"),
            max_faults=faults, stall_s=2.5)
        # guarantee coverage: arm one fault at EVERY site up front
        # (the rate-based stream fills in the rest), plus a couple of
        # watchdog stalls. The swap sites are visited far less often
        # than the per-step sites (once per preemption/resume, not per
        # step), so their armed shots sit on early calls: the FIRST
        # swap-out succeeds (a payload must exist for any swap-in to
        # run at all — and a recovery rebuilds a fresh engine with
        # every slot free, so a faulted swap-out is not re-attempted
        # until the next drill round preempts again), the second
        # faults; the first swap-in faults and its retry proves the
        # payload survived the recovery.
        for i, site in enumerate(SITES):
            if site == "swap_out":
                inj.arm(site, "raise", nth=2)
            elif site == "swap_in":
                inj.arm(site, "raise", nth=1)
            elif site == "tree_verify":
                # visited only by the ISSUE 20 tree interlude below:
                # the FIRST one-forward tree verify eats the shot —
                # it fires BEFORE the verify launches, so nothing
                # committed and recovery rebuilds the draft pool cold
                inj.arm(site, "raise", nth=1)
            elif site == "draft_propose":
                # the first propose must succeed (the interlude needs
                # at least one full propose->verify->commit round and
                # a rejection cascade against a LIVE draft pool before
                # a fault tears it down); the recover_after=2 tree
                # supervisor climbs back to healthy fast enough for
                # the second propose to eat the shot
                inj.arm(site, "raise", nth=2)
            elif site == "adapter_load":
                # fires once per FRESH registry load (a handful per
                # soak, not per step): the first load must succeed so
                # an eviction/demotion can ever happen, the second
                # eats the shot — the re-admission after recovery
                # retries against an intact registry
                inj.arm(site, "raise", nth=2)
            elif site == "adapter_promote":
                # fires once per host-store promotion (needs a prior
                # LRU demotion): the first promotion faults, and the
                # retried admission proves the demoted payload
                # survived the fault un-installed
                inj.arm(site, "raise", nth=1)
            elif site == "verify_step":
                # spec verify only runs at degraded level 0 — the
                # first recovery shelves it (no_spec) and every armed
                # fault elsewhere costs a recovery, so the verify shot
                # must land on the FIRST call or the site may never be
                # visited again before the soak drains (the ISSUE 15
                # wal sites joined the rate stream, which reshuffled
                # the seeded recovery timing that nth=2 relied on)
                inj.arm(site, "raise", nth=1)
            elif site == "checkpoint_write":
                # one visit per checkpoint_every steps — a deep nth
                # may never be reached in a short soak; the first
                # checkpoint is expendable (it commits nothing when it
                # faults, and the next period retries)
                inj.arm(site, "raise", nth=1)
            else:
                inj.arm(site, "raise", nth=3 + 2 * i)
        for i in range(stall_faults):
            inj.arm("transfer", "stall", nth=30 + 40 * i)
        # durable journal ON (ISSUE 15): per-step delta cadence
        # (group_interval_s=0) + a small checkpoint period so the
        # wal_append / wal_fsync / checkpoint_write sites get organic
        # per-step visits under the same zero-lost/duplicated gate
        sup = EngineSupervisor(
            factory, watchdog_s=2.0, backoff_s=0.0,
            sleep=lambda s: None, circuit_threshold=10,
            recover_after=8,
            wal_dir=tempfile.mkdtemp(prefix="chaos_wal_"),
            checkpoint_every=16, wal_kw=dict(group_interval_s=0.0))

        def submit(p, m, prio=Priority.NORMAL, aid=0):
            # a fault at the write-ahead append rejects the submission
            # BEFORE the ack — the client's move is a plain retry, and
            # nothing was half-accepted (the append rolls back)
            while True:
                try:
                    return sup.submit(p, max_new_tokens=m,
                                      priority=prio, adapter_id=aid)
                except InjectedFault:
                    continue
        reqs = []
        steps = 0
        with inj:
            # TRICKLE the submissions (two steps between arrivals)
            # instead of batching them up front: strictly-by-class
            # admission would otherwise drain every HIGH before any
            # LOW ever holds a slot, and the preemption path — and
            # with it the host tier's swap_out/swap_in sites
            # (ISSUE 10) — would never execute. Arrival dynamics are
            # what make HIGH-preempts-running-LOW happen.
            for p, m, prio, aid in jobs:
                reqs.append(submit(p, m, prio=prio, aid=aid))
                for _ in range(2):
                    try:
                        sup.step()
                    except EngineDead:
                        raise SoakError(
                            "circuit breaker opened mid-soak — raise "
                            "circuit_threshold or lower the fault rate")
                    steps += 1
            while True:
                try:
                    if not sup.step():
                        break
                except EngineDead:
                    raise SoakError(
                        "circuit breaker opened mid-soak — raise "
                        "circuit_threshold or lower the fault rate")
                steps += 1
                if steps >= max_steps:
                    raise SoakError(f"soak did not drain within "
                                    f"{max_steps} steps")
            # deterministic SWAP DRILL (ISSUE 10): two rounds of
            # fill-slots-then-HIGH-preempts, so the swap_out/swap_in
            # sites get guaranteed visits (and their armed shots
            # guaranteed firings) even at small --requests where the
            # organic arrival mix may preempt only once. The fillers
            # are NORMAL class — the degraded ladder may be shedding
            # LOW by now, and a shed filler never occupies the slot a
            # preemption needs. References for these requests are
            # computed after the injector uninstalls, like the
            # top-ups'.
            # ROUND COUNT IS ADAPTIVE (ISSUE 13): a round's HIGH can
            # land just as a filler retires (admitting into the freed
            # slot, no preemption), and the bounded swap-in retry
            # absorbed a recovery that used to reshape the dynamics —
            # so loop until the swap_out site has genuinely been
            # visited twice (first call succeeds, second eats the
            # armed shot) instead of assuming two rounds suffice
            topup_jobs = []
            # decode-heavy fillers on a dp-widened batch (ISSUE 17):
            # chunked prefill admits ~one filler per step (the chunk
            # budget), so the LAST slot starts decoding ~max_batch
            # steps after the first — the first filler must still be
            # decoding then (even at full spec acceptance, 3
            # tokens/step) or the all-slots-swappable window the HIGH
            # preemption needs never opens
            fill_new = 6 if mesh is None else 6 + 9 * dp
            drill_rounds = 0
            while inj.calls["swap_out"] < 2 and drill_rounds < 8:
                drill_rounds += 1
                lows = []
                # fill EVERY slot with decode-phase NORMAL work, topping
                # up as earlier fillers finish (or recoveries churn the
                # slots): the HIGH below must find no free slot and only
                # swappable victims, or the admission would not preempt
                # and the swap sites would go unvisited — the organic
                # phase's preemption count depends on the seeded fault
                # sequence, which shifts whenever SITES grows (ISSUE 12
                # added dispatch/commit), so the drill must not rely on it
                while True:
                    eng = sup.engine       # recoveries swap the engine
                    running = eng.running_requests()
                    if (len(running) == eng.max_batch
                            and all(eng.swap_candidate(r)
                                    for r in running)):
                        break
                    # top up the FULL deficit, not one per step: at
                    # dp-widened max_batch a filler's lifetime is
                    # fewer steps than there are slots, so
                    # one-per-step arrivals can never have every slot
                    # occupied at once
                    while sum(1 for r in lows
                              if not r.done) < eng.max_batch:
                        p = rs.randint(3, cfg.vocab_size, (6,)).astype(
                            np.int32)
                        lows.append(submit(p, fill_new))
                        reqs.append(lows[-1])
                        topup_jobs.append((p, fill_new))
                    try:
                        sup.step()
                    except EngineDead:
                        raise SoakError("circuit opened in swap drill")
                    steps += 1
                    if steps >= max_steps:
                        raise SoakError("swap drill did not settle")
                p = rs.randint(3, cfg.vocab_size, (4,)).astype(np.int32)
                reqs.append(submit(p, 2, prio=Priority.HIGH))
                topup_jobs.append((p, 2))
                while True:
                    try:
                        if not sup.step():
                            break
                    except EngineDead:
                        raise SoakError("circuit opened in swap drill")
                    steps += 1
                    if steps >= max_steps:
                        raise SoakError("swap drill did not drain")
            # ---- draft-model TREE speculation interlude (ISSUE 20):
            # a SECOND supervised engine on the same injector — the
            # truncated-layer draft model proposes token trees, one
            # forward verifies them, and the armed draft_propose /
            # tree_verify shots (both fire BEFORE any commit) land
            # mid-traffic. recover_after=2 so the no_spec rung the
            # first fault buys climbs off fast enough for the second
            # armed site to be visited again before the drain.
            # References are computed after the injector uninstalls,
            # on the plain reference engine: tree speculation is
            # token-identical to plain decode, so the standing parity
            # gate doubles as the tree-identity gate under fault fire.
            def tree_factory():
                return ContinuousBatchingEngine(
                    params, cfg, max_batch=3, page_size=8, max_len=48,
                    prefill_chunk=8, draft_layers=1, spec_tree=(2, 2),
                    overlap=True)

            tsup = EngineSupervisor(
                tree_factory, watchdog_s=2.0, backoff_s=0.0,
                sleep=lambda s: None, circuit_threshold=10,
                recover_after=2,
                wal_dir=tempfile.mkdtemp(prefix="chaos_tree_wal_"),
                checkpoint_every=16, wal_kw=dict(group_interval_s=0.0))
            tree_jobs, tree_reqs = [], []
            for i in range(8):
                if i % 2:
                    motif = rs.randint(3, cfg.vocab_size, (3,))
                    p = np.tile(motif, 5).astype(np.int32)[:12]
                else:
                    p = rs.randint(3, cfg.vocab_size, (int(
                        rs.randint(4, 14)),)).astype(np.int32)
                m = int(rs.randint(4, 7))
                while True:
                    try:
                        tree_reqs.append(tsup.submit(
                            p, max_new_tokens=m))
                        break
                    except InjectedFault:
                        continue
                tree_jobs.append((p, m))
                for _ in range(2):
                    try:
                        tsup.step()
                    except EngineDead:
                        raise SoakError(
                            "circuit opened in tree interlude")
                    steps += 1
            while True:
                try:
                    if not tsup.step():
                        break
                except EngineDead:
                    raise SoakError("circuit opened in tree interlude")
                steps += 1
                if steps >= max_steps:
                    raise SoakError("tree interlude did not drain")
            # keep injecting until the fault budget is spent: top up
            # with fresh NORMAL traffic so every site stays hot (the
            # top-ups' uninterrupted references are computed AFTER the
            # injector uninstalls — a faulted reference run would gate
            # parity against a poisoned oracle)
            topup = 0
            while inj.fired_total < faults:
                p = rs.randint(3, cfg.vocab_size,
                               (int(rs.randint(3, 20)),)).astype(np.int32)
                m = int(rs.randint(3, 6))
                r = submit(p, m)
                jobs.append((p, m, Priority.NORMAL, 0))
                reqs.append(r)
                topup_jobs.append((p, m))
                topup += 1
                while True:
                    try:
                        if not sup.step():
                            break
                    except EngineDead:
                        raise SoakError("circuit breaker opened during "
                                        "fault-budget top-up")
                    steps += 1
                    if steps >= max_steps:
                        raise SoakError(f"top-up did not drain within "
                                        f"{max_steps} steps")
                if topup > 8 * faults:
                    raise SoakError(
                        f"fault budget not spent after {topup} top-up "
                        f"requests ({inj.fired_total}/{faults}) — the "
                        f"rate is too low for the workload")
        for p, m in topup_jobs:
            # the ONE reference engine serves every reference run (its
            # compiled programs amortize across the whole soak)
            refs.append(ref_run(p, m))
        tree_refs = [ref_run(p, m) for p, m in tree_jobs]
        snap = obs.REGISTRY.to_json()
    finally:
        obs.REGISTRY.clear()
        if not was:
            obs.disable()

    # ---- invariants ----
    lost = [r.rid for r in reqs if not r.done or r.finish_reason is None]
    if lost:
        raise SoakError(f"lost requests (not done after drain): {lost}")
    shed = [r for r in reqs if r.finish_reason == "rejected_overload"]
    ok_reasons = {"eos", "max_len", "rejected_overload"}
    bad = [(r.rid, r.finish_reason) for r in reqs
           if r.finish_reason not in ok_reasons]
    if bad:
        raise SoakError(f"unstructured finish reasons: {bad}")
    mismatched = []
    for r, ref in zip(reqs, refs):
        if r.finish_reason == "rejected_overload":
            if r.tokens:
                mismatched.append((r.rid, "shed request has tokens"))
            continue
        if not np.array_equal(r.output, ref):
            mismatched.append((r.rid, "token stream != uninterrupted"))
    if mismatched:
        raise SoakError(
            f"duplicated/diverged token streams: {mismatched}")
    alloc = sup.engine.cache.allocator
    if sup.engine.cache.prefix is not None:
        sup.engine.cache.prefix.drop_all(alloc)
    astats = alloc.stats()
    if astats["num_used"] != 0 or \
            astats["allocs_total"] != astats["frees_total"]:
        raise SoakError(f"allocator unbalanced after drain: {astats}")
    # ---- ISSUE 20 tree-interlude invariants: zero lost, streams
    # token-identical to plain decode, and BOTH pools balanced — the
    # draft pool drained through admits, rejection cascades, faults
    # and cold recovery rebuilds, so a leaked draft page shows here
    tlost = [r.rid for r in tree_reqs
             if not r.done or r.finish_reason not in ("eos", "max_len")]
    if tlost:
        raise SoakError(f"tree interlude lost requests: {tlost}")
    tmism = [r.rid for r, ref in zip(tree_reqs, tree_refs)
             if not np.array_equal(r.output, ref)]
    if tmism:
        raise SoakError(f"tree-speculated streams diverged from plain "
                        f"decode under fault fire: {tmism}")
    talloc = tsup.engine.cache.allocator
    if tsup.engine.cache.prefix is not None:
        tsup.engine.cache.prefix.drop_all(talloc)
    tstats = talloc.stats()
    dstats = tsup.engine.draft_cache.allocator.stats()
    if tstats["num_used"] != 0 or dstats["num_used"] != 0 or \
            dstats["allocs_total"] != dstats["frees_total"]:
        raise SoakError(f"tree engine pools unbalanced after drain: "
                        f"main={tstats} draft={dstats}")
    if inj.fired_total < faults:
        raise SoakError(f"only {inj.fired_total}/{faults} faults fired")
    missing = [s for s in SITES if not inj.fired.get(s)]
    if missing:
        raise SoakError(f"sites never faulted: {missing}")
    counted = sum(
        snap.get("serving_fault_injected_total", {})
        .get("values", {}).values())
    if counted != inj.fired_total:
        raise SoakError(
            f"metrics saw {counted} injected faults, injector fired "
            f"{inj.fired_total} — a fault escaped the counters")
    labeled_sites = {
        k.split("site=")[1].split(",")[0]
        for k in snap["serving_fault_injected_total"]["values"]}
    if set(SITES) - labeled_sites:
        raise SoakError(f"sites missing from serving_fault_* labels: "
                        f"{sorted(set(SITES) - labeled_sites)}")

    return {
        "seed": seed,
        "requests": len(reqs),
        **({"tp": tp, "dp": dp} if mesh is not None else {}),
        "shed_rejected_overload": len(shed),
        "faults_fired": inj.fired_total,
        "faults_by_site": {s: n for s, n in inj.fired.items() if n},
        "recoveries": sup.recoveries,
        "tree_interlude": {
            "requests": len(tree_reqs),
            "recoveries": tsup.recoveries,
            "draft_propose_fired": int(inj.fired.get(
                "draft_propose", 0)),
            "tree_verify_fired": int(inj.fired.get("tree_verify", 0)),
            "draft_pool": {k: dstats[k] for k in
                           ("allocs_total", "frees_total", "num_used")},
        },
        "supervised_steps": sup.stats()["supervised_steps"],
        "final_degraded_mode": sup.degraded_mode,
        "allocator": {k: astats[k] for k in
                      ("allocs_total", "frees_total", "num_used")},
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }


def run_cluster_soak(seed: int = 0, requests: int = 18,
                     replicas: int = 3, max_steps: int = 20000) -> dict:
    """Cluster-mode soak (ISSUE 9): a multi-tenant shared-prefix
    workload through a :class:`~paddle_tpu.serving.ServingCluster`
    while a deterministic :class:`~paddle_tpu.serving.FaultInjector`
    KILLS a random replica mid-soak — ``circuit_threshold``
    consecutive armed faults at the ``sched_tick`` site blow whichever
    replica steps next straight through its circuit breaker (the same
    hot-path sites the single-engine soak exercises). Invariants:

    - **zero lost / duplicated requests cluster-wide** — every request
      finishes with a structured reason and a token stream EXACTLY
      equal to its uninterrupted single-engine reference (the dead
      replica's sessions rehome and resume token-identically);
    - **prefix-affinity recovers** — after the replica rebuilds, fresh
      same-tenant traffic produces prefix HITs again (counter-gated:
      the hit-token counter and the router's affinity-hit counter both
      advance post-rebuild);
    - **balanced allocators** — every surviving replica drains to zero
      pages in use with ``allocs_total == frees_total``.

    Wired into tier-1 via tests/test_cluster.py::TestClusterChaosSoak.
    """
    import jax
    from paddle_tpu import observability as obs
    from paddle_tpu.models import llama
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving import (FaultInjector, Priority,
                                    ServingCluster)

    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
    params = llama.init_params(jax.random.key(0), cfg)
    rs = np.random.RandomState(seed)
    circuit = 3

    def factory():
        # host tier ON (ISSUE 10); the cluster shares ONE HostPageStore
        # across replicas (share_host_tier default), so sessions the
        # killed replica swapped out SWAP IN on the replica they rehome
        # to — the failover path exercises the cross-replica host tier.
        # overlap ON (ISSUE 12): every supervised replica runs the
        # double-buffered scheduler, so the replica kill lands with a
        # step in flight and the rehomed sessions' resumes gate the
        # overlapped cluster against the synchronous references.
        return ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=48,
            prefill_chunk=8, host_tier=True, overlap=True)

    # multi-tenant workload: each tenant has its own system prompt
    # (affinity + prefix hits) plus a unique tail, three priorities
    tenants = [f"tenant{i}" for i in range(3)]
    sys_prompts = {t: rs.randint(3, cfg.vocab_size, (16,)).astype(
        np.int32) for t in tenants}

    def make_job():
        t = tenants[int(rs.randint(len(tenants)))]
        tail = rs.randint(3, cfg.vocab_size,
                          (int(rs.randint(2, 8)),)).astype(np.int32)
        return (t, np.concatenate([sys_prompts[t], tail]),
                int(rs.randint(3, 6)),
                Priority(int(rs.randint(0, 3))))

    jobs = [make_job() for _ in range(requests)]
    ref_engine = factory()
    refs = [np.asarray(ref_engine.generate([p], max_new_tokens=m)[0])
            for _, p, m, _ in jobs]

    was = obs.metrics_enabled()
    obs.REGISTRY.clear()
    obs.enable()
    t_start = time.perf_counter()
    try:
        cluster = ServingCluster(
            factory, replicas=replicas,
            supervisor_kw=dict(backoff_s=0.0, sleep=lambda s: None,
                               circuit_threshold=circuit,
                               recover_after=4))
        inj = FaultInjector(seed=seed)
        reqs = []
        with inj:
            for t, p, m, prio in jobs:
                reqs.append(cluster.submit(p, max_new_tokens=m,
                                           tenant=t, priority=prio))
            # let traffic occupy every replica, then KILL one: arm
            # circuit_threshold consecutive sched_tick faults — the
            # next replica to step burns through its whole retry
            # budget and opens its circuit (EngineDead -> failover)
            steps = 0
            for _ in range(3):
                cluster.step()
                steps += 1
            for _ in range(circuit):
                inj.arm("sched_tick", "raise", nth=1)
            failovers_before = cluster.failovers_total
            hits_before = cluster.router.affinity_hits
            while cluster.step():
                steps += 1
                if steps >= max_steps:
                    raise SoakError(f"cluster soak did not drain "
                                    f"within {max_steps} steps")
        if cluster.failovers_total <= failovers_before:
            raise SoakError("the armed fault burst did not kill a "
                            "replica — nothing failed over")
        # post-rebuild traffic: the SAME tenants return; affinity and
        # prefix hits must recover (references computed with the
        # injector uninstalled)
        hit0 = sum(obs.REGISTRY.to_json()
                   .get("serving_prefix_hit_tokens_total", {})
                   .get("values", {}).values())
        post_jobs = [make_job() for _ in range(6)]
        for t, p, m, prio in post_jobs:
            reqs.append(cluster.submit(p, max_new_tokens=m, tenant=t,
                                       priority=prio))
            jobs.append((t, p, m, prio))
        while cluster.step():
            steps += 1
            if steps >= max_steps:
                raise SoakError("post-rebuild traffic did not drain")
        for _, p, m, _ in post_jobs:
            refs.append(np.asarray(
                ref_engine.generate([p], max_new_tokens=m)[0]))
        snap = obs.REGISTRY.to_json()
    finally:
        obs.REGISTRY.clear()
        if not was:
            obs.disable()

    # ---- invariants ----
    lost = [r.rid for r in reqs if not r.done or r.finish_reason is None]
    if lost:
        raise SoakError(f"lost requests (not done after drain): {lost}")
    ok_reasons = {"eos", "max_len", "rejected_overload"}
    bad = [(r.rid, r.finish_reason) for r in reqs
           if r.finish_reason not in ok_reasons]
    if bad:
        raise SoakError(f"unstructured finish reasons: {bad}")
    mismatched = []
    for r, ref in zip(reqs, refs):
        if r.finish_reason == "rejected_overload":
            if r.tokens:
                mismatched.append((r.rid, "shed request has tokens"))
            continue
        if not np.array_equal(r.output, ref):
            mismatched.append((r.rid, "token stream != uninterrupted"))
    if mismatched:
        raise SoakError(
            f"duplicated/diverged token streams: {mismatched}")
    hit1 = sum(snap.get("serving_prefix_hit_tokens_total", {})
               .get("values", {}).values())
    if hit1 <= hit0:
        raise SoakError(
            f"prefix hit-rate did not recover after the replica "
            f"rebuild (hit tokens {hit0} -> {hit1})")
    if cluster.router.affinity_hits <= hits_before:
        raise SoakError("router affinity hits did not advance after "
                        "the failover")
    unbalanced = {}
    for i, sup in enumerate(cluster.replicas):
        alloc = sup.engine.cache.allocator
        if sup.engine.cache.prefix is not None:
            sup.engine.cache.prefix.drop_all(alloc)
        st = alloc.stats()
        if st["num_used"] != 0 or \
                st["allocs_total"] != st["frees_total"]:
            unbalanced[i] = st
    if unbalanced:
        raise SoakError(f"allocator unbalanced after drain: "
                        f"{unbalanced}")

    return {
        "seed": seed,
        "mode": "cluster",
        "replicas": replicas,
        "requests": len(reqs),
        "shed_rejected_overload": len(
            [r for r in reqs if r.finish_reason == "rejected_overload"]),
        "failovers": cluster.failovers_total,
        "handoffs": cluster.handoffs_total,
        "rehomed_sessions": int(
            sum(snap.get("serving_router_rehomed_sessions_total", {})
                .get("values", {}).values())),
        "affinity_hit_rate": round(
            cluster.router.stats()["affinity_hit_rate"], 3),
        "prefix_hit_tokens": int(hit1),
        "cluster_steps": cluster.stats()["cluster_steps"],
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }


def run_traffic_soak(seed: int = 0, duration_s: float = 3.0,
                     base_rps: float = 8.0,
                     max_steps: int = 40000) -> dict:
    """Traffic-mode soak (ISSUE 13): the trace-driven open-loop
    generator (:func:`paddle_tpu.serving.traffic.synth_trace` — tenant
    prefix families, a 4x burst window, mixed priority/deadline/length)
    against an AUTOSCALING, prefill/decode-disaggregated cluster with
    corruption and handoff faults armed:

    - a TAMPER shot on ``handoff_export`` flips real payload bytes —
      the import-side CRC must detect them before install (the request
      then keeps decoding on the prefill replica, token-identically);
    - a TAMPER shot on ``swap_in`` corrupts the first swap payload the
      burst's preemptions produce — detected, quarantined, replayed;
    - an armed raise on ``handoff_import`` is absorbed by the bounded
      idempotent retry (no engine recovery, no double-install);
    - an armed raise on ``autoscale_tick`` skips exactly one scaling
      decision and the loop recovers on the next step.

    Invariants: ZERO lost requests and ZERO duplicated/diverged token
    streams on the surviving (served) request set — gated against
    uninterrupted single-engine references, which the PR 9 cluster
    gates already prove equivalent to any fixed-size cluster; the
    replica count both GREW and SHRANK during the soak (the
    autoscaler's two transitions); every detected corruption was
    quarantined; every surviving replica's allocator drains balanced
    (a retried import that double-installed pages would show here).

    Wired into tier-1 via tests/test_traffic.py::TestTrafficChaosSoak.
    """
    import jax
    from paddle_tpu import observability as obs
    from paddle_tpu.models import llama
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving import (AdmissionController,
                                    ClusterAutoscaler, FakeClock,
                                    FaultInjector, ServingCluster,
                                    run_trace, synth_trace)
    from paddle_tpu.serving.traffic import REJECTED_REASONS

    from paddle_tpu.serving import AdapterRegistry, init_lora

    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
    params = llama.init_params(jax.random.key(0), cfg)
    # adapter traffic (ISSUE 14): one shared registry, one fresh
    # 2-slot pool per replica engine — the trace's Zipf-assigned
    # tenant adapters exercise router adapter-affinity, cross-replica
    # loads and slot churn under the same fault/parity gates
    registry = AdapterRegistry(cfg)
    for aid in (1, 2, 3):
        registry.register(aid, init_lora(cfg, 4, seed=200 + aid))

    def factory():
        # host tier + overlap ON: the burst's preemptions swap through
        # the async DMA path, so the armed swap tamper lands on real
        # payload bytes; references stay sync (engine.generate), so
        # the parity gate is also an overlap-identity gate under fire
        return ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=48,
            prefill_chunk=8, host_tier=True, overlap=True,
            adapters=dict(slots=2, rank=4, registry=registry))

    # priority-heavy mix + long decodes: the burst's HIGH arrivals
    # must find decode-phase NORMAL/LOW victims in full slots, or the
    # preemption path — and the armed swap-in tamper — never runs
    trace = synth_trace(
        seed=seed, duration_s=duration_s, base_rps=base_rps,
        tenants=3, page_size=8, prefix_pages=2, vocab=cfg.vocab_size,
        burst_mult=5.0, new_tokens=(6, 12),
        priority_weights=(0.3, 0.4, 0.3),
        deadline_frac=0.3, deadline_s=(1.5, 4.0),
        adapters=3)

    was = obs.metrics_enabled()
    obs.REGISTRY.clear()
    obs.enable()
    t_start = time.perf_counter()
    try:
        clock = FakeClock()
        auto = ClusterAutoscaler(
            min_replicas=1, max_replicas=3,
            up_backlog_per_replica=3.0, down_backlog_per_replica=0.5,
            up_after=1, down_after=4, cooldown_ticks=3)
        cluster = ServingCluster(
            factory, replicas=2, prefill_replicas=1, clock=clock,
            autoscaler=auto,
            admission=AdmissionController(tokens_per_s=None),
            retry_sleep=lambda s: None,
            supervisor_kw=dict(backoff_s=0.0, sleep=lambda s: None,
                               circuit_threshold=8, recover_after=8))
        inj = FaultInjector(seed=seed)
        inj.arm_tamper("handoff_export", nth=1)
        inj.arm_tamper("swap_in", nth=1)
        inj.arm("handoff_import", "raise", nth=2)
        inj.arm("autoscale_tick", "raise", nth=4)
        submitted = []
        with inj:
            report = run_trace(
                cluster, trace, clock, step_dt=0.05,
                max_steps=max_steps,
                on_submit=lambda tr, req: submitted.append((tr, req)))
        snap = obs.REGISTRY.to_json()
    finally:
        obs.REGISTRY.clear()
        if not was:
            obs.disable()

    # references AFTER the injector uninstalls (a faulted reference
    # run would gate parity against a poisoned oracle); one engine
    # serves every reference so compiles amortize
    ref_engine = factory()

    # ---- invariants ----
    if report.lost:
        raise SoakError(f"lost requests: {report.lost} finished "
                        f"without a structured reason")
    # door rejections (the one source of truth run_trace scores by)
    # + the scheduler's own expiry: structured DECLINES, no tokens owed
    declined = set(REJECTED_REASONS) | {"deadline_exceeded"}
    mismatched = []
    for tr, req in submitted:
        if not req.done or req.finish_reason is None:
            raise SoakError(f"request {req.rid} not done after drain")
        if req.finish_reason in declined:
            if req.tokens:
                mismatched.append((req.rid, "declined request has "
                                   "tokens"))
            continue
        ref_req = ref_engine.submit(
            tr.prompt, max_new_tokens=tr.max_new_tokens,
            adapter_id=getattr(tr, "adapter_id", 0))
        ref_engine.run()
        ref = np.asarray(ref_req.output)
        if not np.array_equal(req.output, ref):
            mismatched.append((req.rid,
                               "token stream != uninterrupted"))
    if mismatched:
        raise SoakError(f"duplicated/diverged token streams: "
                        f"{mismatched}")
    if not (auto.up_events >= 1 and auto.down_events >= 1):
        raise SoakError(
            f"autoscaler did not breathe: up={auto.up_events} "
            f"down={auto.down_events} (need both transitions)")
    for site in ("handoff_export", "handoff_import", "autoscale_tick"):
        if not inj.fired.get(site):
            raise SoakError(f"cluster site never fired: {site}")
    if cluster.handoff_corruptions_total < 1:
        raise SoakError("the armed handoff tamper was never detected "
                        "by the import-side checksum")
    if cluster.handoff_retries_total < 1:
        raise SoakError("the armed handoff_import fault was never "
                        "absorbed by the bounded retry")
    if cluster.autoscale_faults_total < 1:
        raise SoakError("the armed autoscale_tick fault never fired")
    store = cluster._host_store
    swap_tampers = sum(1 for s, m, _ in inj.log
                       if s == "swap_in" and m == "tamper")
    if swap_tampers and (store is None
                         or store.quarantined_total < swap_tampers):
        raise SoakError(
            f"swap-in tamper fired {swap_tampers}x but only "
            f"{store and store.quarantined_total} payload(s) were "
            f"quarantined — corrupt bytes may have been served")
    unbalanced = {}
    for i, sup in enumerate(cluster.replicas):
        if sup.health == "dead" or sup._draining:
            continue            # drained husks already released
        alloc = sup.engine.cache.allocator
        if sup.engine.cache.prefix is not None:
            sup.engine.cache.prefix.drop_all(alloc)
        st = alloc.stats()
        if st["num_used"] != 0 or \
                st["allocs_total"] != st["frees_total"]:
            unbalanced[i] = st
    if unbalanced:
        raise SoakError(f"allocator unbalanced after drain "
                        f"(double-installed pages?): {unbalanced}")

    return {
        "seed": seed,
        "mode": "traffic",
        "requests": len(submitted),
        "report": report.as_dict(),
        "autoscale": auto.stats(),
        "faults_by_site": {s: n for s, n in inj.fired.items() if n},
        "handoff_corruptions": cluster.handoff_corruptions_total,
        "handoff_retries": cluster.handoff_retries_total,
        "swap_tampers_detected": swap_tampers,
        "quarantined": (store.quarantined_total
                        if store is not None else 0),
        "injected_total": int(sum(
            snap.get("serving_fault_injected_total", {})
            .get("values", {}).values())),
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }


class _ProcessDied(RuntimeError):
    """The crash harness's simulated ``kill -9``: raised instead of the
    supervisor's in-process recovery, the supervisor object is then
    ABANDONED (no cleanup, no drain — host memory 'gone') and a fresh
    process recovers from the journal directory alone."""


def _crashy(sup):
    """Make ``sup`` die instead of recovering: any step fault now
    escapes as :class:`_ProcessDied` — the harness abandons the object
    and calls ``EngineSupervisor.recover_from_disk``."""
    def die(err):
        raise _ProcessDied(f"{type(err).__name__}: {err}") from err
    sup._on_failure = die
    return sup


def _sweep_env(kv_cache_dtype=None, tp=None, constrained=False,
               spec_k=2, tree=False):
    """One crash-sweep environment: config/params (optionally
    tp-sharded), an engine factory (host tier + adapters + either
    speculation or constrained decoding — the two compose everywhere
    except spec×constraints, which the engine rejects), the job list
    that visits every engine fault site, and per-job uninterrupted
    references. ``tree=True`` (ISSUE 20) swaps the host-speculator
    engine for a draft-model TREE-speculation one, so the
    ``draft_propose``/``tree_verify`` sites get organic per-step
    visits — its references are still exact for every site's recovery
    because tree speculation is token-identical to plain decode."""
    import jax
    from paddle_tpu.models import llama
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving import (AdapterRegistry, HostPageStore,
                                    Priority, init_lora)
    from paddle_tpu.serving.constraints import dfa_from_sequences

    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = None
    if tp:
        from paddle_tpu.distributed.mesh import serving_mesh
        if len(jax.devices()) < tp:
            raise RuntimeError(f"crash sweep tp={tp} needs {tp} devices")
        mesh = serving_mesh(tp)
    registry = AdapterRegistry(cfg)
    for aid in (1, 2, 3):
        registry.register(aid, init_lora(cfg, 4, seed=300 + aid))
    dfa = (dfa_from_sequences(
        [[4, 5, 6, 7, 8, 9], [4, 5, 6, 3, 3, 3]], cfg.vocab_size)
        if constrained else None)

    def factory():
        kw = dict(max_batch=2, page_size=8, max_len=48,
                  prefill_chunk=8, kv_cache_dtype=kv_cache_dtype,
                  host_tier=True, mesh=mesh,
                  adapters=dict(slots=2, rank=4, registry=registry,
                                store=HostPageStore(page_size=8)))
        if constrained:
            kw["constraints"] = True
        elif tree:
            kw.update(spec_k=2, draft_layers=1, spec_tree=(2, 2))
        else:
            kw.update(spec_k=spec_k, speculator=_speculator(spec_k))
        return ContinuousBatchingEngine(params, cfg, **kw)

    rs = np.random.RandomState(7)
    motif = rs.randint(3, cfg.vocab_size, (3,))
    # (prompt, max_new, priority, adapter_id, constraint): a long
    # chunked prefill, a speculative motif, adapter churn over the
    # 2-slot pool (load → demote → promote), then a HIGH burst that
    # preempts decode-phase victims through the swap pair
    jobs = [
        (rs.randint(3, cfg.vocab_size, (18,)).astype(np.int32), 4,
         Priority.NORMAL, 1, None),
        (np.tile(motif, 5).astype(np.int32)[:14], 5,
         Priority.NORMAL, 2, dfa),
        (rs.randint(3, cfg.vocab_size, (6,)).astype(np.int32), 5,
         Priority.NORMAL, 3, None),
        (rs.randint(3, cfg.vocab_size, (5,)).astype(np.int32), 4,
         Priority.NORMAL, 1, None),
        (rs.randint(3, cfg.vocab_size, (4,)).astype(np.int32), 2,
         Priority.HIGH, 0, None),
        (rs.randint(3, cfg.vocab_size, (7,)).astype(np.int32), 4,
         Priority.NORMAL, 0, None),
    ]
    ref_engine = factory()
    refs = []
    for p, m, _prio, aid, con in jobs:
        r = ref_engine.submit(p, max_new_tokens=m, adapter_id=aid,
                              constraint=con)
        ref_engine.run()
        refs.append(np.asarray(r.output))
    return factory, jobs, refs, dfa


def run_crash_sweep(sites=None, kv_cache_dtype=None, tp=None,
                    constrained=False, checkpoint_every=3,
                    max_steps: int = 4000, wal_root=None) -> dict:
    """The HEADLINE crash-point sweep (ISSUE 15): for each engine
    fault site, arm one raise, drive a crash-on-fault supervisor until
    the 'process dies' at that exact site, abandon it, and
    ``recover_from_disk`` — every acked request must finish
    TOKEN-IDENTICAL to its uninterrupted reference, zero
    lost/duplicated, allocator balanced, and the armed site must have
    actually fired. ``constrained=True`` swaps the speculative engine
    for a constrained+adapter one (spec×constraints is rejected by the
    engine), covering mid-grammar sessions on the same gate."""
    import tempfile

    from paddle_tpu.serving import (EngineSupervisor, FaultInjector,
                                    InjectedFault)
    from paddle_tpu.serving.resilience import ENGINE_SITES

    # the draft_propose / tree_verify sites (ISSUE 20) only execute on
    # a draft-model tree-speculation engine, so the sweep swaps in the
    # tree environment for exactly those sites (built lazily — a
    # sites= list that never names them pays nothing); everything else
    # keeps the host-speculator env. References are interchangeable:
    # both engines are token-identical to plain decode.
    tree_sites = ("draft_propose", "tree_verify")
    envs = {False: _sweep_env(
        kv_cache_dtype=kv_cache_dtype, tp=tp, constrained=constrained)}
    if sites is None:
        sites = list(ENGINE_SITES)
        if constrained:
            # a constrained engine rejects spec_k > 0, so neither the
            # verify program nor the draft/tree path ever runs — the
            # speculative sweep owns those sites
            sites = [s for s in sites
                     if s not in ("verify_step",) + tree_sites]
    root = wal_root or tempfile.mkdtemp(prefix="crash_sweep_")
    per_site = {}
    for site in sites:
        tree = site in tree_sites
        if tree and tree not in envs:
            envs[tree] = _sweep_env(kv_cache_dtype=kv_cache_dtype,
                                    tp=tp, tree=True)
        factory, jobs, refs, _dfa = envs[tree]
        wd = os.path.join(root, f"{site}-{kv_cache_dtype or 'fp'}"
                          + (f"-tp{tp}" if tp else "")
                          + ("-con" if constrained else ""))
        sup_kw = dict(backoff_s=0.0, sleep=lambda s: None,
                      circuit_threshold=50, wal_dir=wd,
                      checkpoint_every=checkpoint_every,
                      wal_kw=dict(group_interval_s=0.0))
        sup = _crashy(EngineSupervisor(factory, **sup_kw))
        inj = FaultInjector(seed=0)
        # sites behind a bounded in-place retry (the ISSUE 13 swap-in
        # retry) absorb a single shot without the process ever dying —
        # arm enough consecutive shots to exhaust the retry budget so
        # the kill actually lands
        shots = (sup.engine.cache.swap_in_retries + 1
                 if site == "swap_in" else 1)
        for k in range(shots):
            inj.arm(site, "raise", nth=k + 1)
        job_of = {}                 # rid -> job index (set at ack)
        cur = {}                    # rid -> live handle (recoveries
        #                             supersede the dead object)
        deaths = 0
        steps = 0

        def recover():
            nonlocal sup, deaths
            deaths += 1
            sup = _crashy(EngineSupervisor.recover_from_disk(
                factory, wd, **{k: v for k, v in sup_kw.items()
                                if k != "wal_dir"}))
            cur.update(sup.restored)

        with inj:
            for i, (p, m, prio, aid, con) in enumerate(jobs):
                while True:
                    try:
                        r = sup.submit(p, max_new_tokens=m,
                                       priority=prio, adapter_id=aid,
                                       constraint=con)
                        job_of[r.rid] = i
                        cur[r.rid] = r
                        break
                    except (InjectedFault, _ProcessDied):
                        # write-ahead append died BEFORE the ack: the
                        # client never got a handle — recover and
                        # resubmit, like any client retry
                        recover()
                for _ in range(2):
                    try:
                        sup.step()
                    except _ProcessDied:
                        recover()
                    steps += 1
            while True:
                try:
                    if not sup.step():
                        break
                except _ProcessDied:
                    recover()
                steps += 1
                if steps >= max_steps:
                    raise SoakError(f"[{site}] sweep did not drain "
                                    f"within {max_steps} steps")
        by_job = {j: cur[rid] for rid, j in job_of.items()}
        if not inj.fired.get(site):
            raise SoakError(f"[{site}] armed site never fired — the "
                            f"sweep workload does not visit it")
        if deaths < 1:
            raise SoakError(
                f"[{site}] the site fired but the process never died "
                f"— the kill was absorbed before it could land")
        # flight-recorder gate (ISSUE 16): every simulated kill must
        # leave a parseable CRC-framed black box next to the WAL
        from paddle_tpu.observability import flight as _flight
        dumps = _flight.find_dumps(wd)
        if len(dumps) < deaths:
            raise SoakError(
                f"[{site}] {deaths} death(s) but only {len(dumps)} "
                f"flight dump(s) in {wd} — a kill left no black box")
        for dp in dumps:
            _flight.load(dp)    # raises on CRC mismatch / torn dump
        for j, req in by_job.items():
            if not req.done or req.finish_reason not in ("eos",
                                                         "max_len"):
                raise SoakError(
                    f"[{site}] job {j} lost: done={req.done} "
                    f"reason={req.finish_reason}")
            if not np.array_equal(np.asarray(req.output), refs[j]):
                raise SoakError(
                    f"[{site}] job {j} diverged after recovery: "
                    f"{req.output} vs {refs[j]}")
        if len(by_job) != len(jobs):
            raise SoakError(f"[{site}] {len(jobs) - len(by_job)} "
                            f"job(s) never acked")
        alloc = sup.engine.cache.allocator
        if sup.engine.cache.prefix is not None:
            sup.engine.cache.prefix.drop_all(alloc)
        st = alloc.stats()
        if st["num_used"] != 0:
            raise SoakError(f"[{site}] allocator unbalanced after "
                            f"drain: {st}")
        if sup.engine.draft_cache is not None:
            dst = sup.engine.draft_cache.allocator.stats()
            if dst["num_used"] != 0:
                raise SoakError(f"[{site}] DRAFT pool unbalanced "
                                f"after drain: {dst}")
        per_site[site] = {"deaths": deaths,
                          "fired": int(inj.fired[site]),
                          "flight_dumps": len(dumps),
                          "last_flight_dump": dumps[-1]}
    return {"mode": "crash_sweep", "tier": kv_cache_dtype or "fp",
            "tp": tp, "constrained": constrained,
            "sites": per_site}


def run_crash_soak(seed: int = 0, kills: int = 4,
                   max_steps: int = 8000, wal_root=None) -> dict:
    """Randomized crash soak (ISSUE 15 CI satellite): a seeded
    workload against a WAL-backed supervisor, the 'process' killed
    after a RANDOM armed site (one kill is a torn-write tamper — half
    a frame reaches disk), recovered from the journal directory each
    time, with the standing zero-lost/zero-duplicated +
    token-identity + balanced-allocator gates at the end. Wired into
    tier-1 via tests/test_wal.py::TestCrashSoak."""
    import tempfile

    from paddle_tpu.serving import (EngineSupervisor, FaultInjector,
                                    InjectedFault)
    from paddle_tpu.serving.resilience import ENGINE_SITES

    factory, jobs, refs, _dfa = _sweep_env()
    rs = np.random.RandomState(seed)
    wd = os.path.join(wal_root or tempfile.mkdtemp(prefix="crash_soak_"),
                      "journal")
    sup_kw = dict(backoff_s=0.0, sleep=lambda s: None,
                  circuit_threshold=50, wal_dir=wd, checkpoint_every=4,
                  wal_kw=dict(group_interval_s=0.0))
    sup = _crashy(EngineSupervisor(factory, **sup_kw))
    inj = FaultInjector(seed=seed)
    # frequently-visited sites so every armed kill actually lands;
    # the per-site sweep (run_crash_sweep) owns exhaustive coverage
    kill_sites = [s for s in ENGINE_SITES
                  if s in ("decode_step", "prefill_chunk", "sched_tick",
                           "transfer", "dispatch", "commit",
                           "wal_append", "wal_fsync",
                           "checkpoint_write")]
    job_of = {}                     # rid -> job index (set at ack)
    cur = {}                        # rid -> live handle
    deaths = 0
    steps = 0

    def recover():
        nonlocal sup, deaths
        deaths += 1
        sup = _crashy(EngineSupervisor.recover_from_disk(
            factory, wd, **{k: v for k, v in sup_kw.items()
                            if k != "wal_dir"}))
        cur.update(sup.restored)

    job_stream = [jobs[i % len(jobs)] for i in range(3 * len(jobs))]
    armed = 0
    with inj:
        for i, (p, m, prio, aid, con) in enumerate(job_stream):
            if armed < kills and i % 4 == 1:
                if armed == kills - 1:
                    inj.arm_tamper("wal_append",
                                   nth=int(rs.randint(1, 4)))
                else:
                    inj.arm(str(rs.choice(kill_sites)), "raise",
                            nth=int(rs.randint(1, 6)))
                armed += 1
            while True:
                try:
                    r = sup.submit(p, max_new_tokens=m, priority=prio,
                                   adapter_id=aid, constraint=con)
                    job_of[r.rid] = i % len(jobs)
                    cur[r.rid] = r
                    break
                except (InjectedFault, _ProcessDied):
                    recover()
            for _ in range(2):
                try:
                    sup.step()
                except _ProcessDied:
                    recover()
                steps += 1
        while True:
            try:
                if not sup.step():
                    break
            except _ProcessDied:
                recover()
            steps += 1
            if steps >= max_steps:
                raise SoakError(f"crash soak did not drain within "
                                f"{max_steps} steps")
    if deaths < 1:
        raise SoakError("no armed kill ever landed — the soak "
                        "exercised nothing")
    # flight-recorder gate (ISSUE 16): every kill left a black box,
    # and every box loads back CRC-clean
    from paddle_tpu.observability import flight as _flight
    flight_dumps = _flight.find_dumps(wd)
    if len(flight_dumps) < deaths:
        raise SoakError(
            f"{deaths} death(s) but only {len(flight_dumps)} flight "
            f"dump(s) in {wd} — a kill left no black box")
    for dp in flight_dumps:
        _flight.load(dp)        # raises on CRC mismatch / torn dump
    final = {rid: (cur[rid], j) for rid, j in job_of.items()}
    lost = [rid for rid, (req, _j) in final.items()
            if not req.done or req.finish_reason not in ("eos",
                                                         "max_len")]
    if lost:
        raise SoakError(f"lost requests after crash soak: {lost}")
    mism = [rid for rid, (req, j) in final.items()
            if not np.array_equal(np.asarray(req.output), refs[j])]
    if mism:
        raise SoakError(f"duplicated/diverged token streams: {mism}")
    alloc = sup.engine.cache.allocator
    if sup.engine.cache.prefix is not None:
        sup.engine.cache.prefix.drop_all(alloc)
    st = alloc.stats()
    if st["num_used"] != 0:
        raise SoakError(f"allocator unbalanced after drain: {st}")
    return {"seed": seed, "mode": "crash", "deaths": deaths,
            "requests": len(final), "steps": steps,
            "faults_by_site": {s: n for s, n in inj.fired.items()
                               if n},
            "flight_dumps": len(flight_dumps),
            "last_flight_dump": flight_dumps[-1],
            "wal_stats": sup.wal.stats()}


def run_multiproc_soak(seed: int = 0, requests: int = 6,
                       max_steps: int = 600, workdir=None) -> dict:
    """Multi-process soak (ISSUE 19): a REAL process tree — one
    prefill worker, one decode worker, one shared KV fabric server —
    driven by :class:`~paddle_tpu.serving.MultiProcessCluster` with
    chaos armed at the controller's wire seams:

    - a TAMPER shot on ``handoff_export`` flips real payload bytes in
      a cross-process KV handoff — the decode-side CRC verifier must
      refuse the install (nothing committed) and the request must
      finish on its prefill replica token-identically;
    - armed ``rpc_send`` / ``rpc_recv`` transport faults drop frames
      mid-call — the bounded idempotent retry plus the server-side
      dedupe cache must absorb them with zero duplicate execution;
    - the decode worker is ``SIGKILL``ed once it owns decoded tokens —
      failover spawns a replacement on the same WAL dir and the
      recovered sessions resume mid-stream.

    Invariants: zero lost / duplicated requests (every token stream
    EXACTLY equals its uninterrupted in-process single-engine
    reference), the corruption was detected (never installed), every
    armed transport fault actually fired, the fabric served demotes,
    and both surviving workers drain to balanced allocators
    (``num_used == 0`` once the standing prefix pages are dropped).
    Wired into tier-1 via tests/test_multiproc.py (conftest-ordered
    dead last; spawn count budgeted for the 870s watchdog).
    """
    import signal
    import tempfile

    from paddle_tpu.serving import FaultInjector
    from paddle_tpu.serving.multiproc import (FabricProcess,
                                              MultiProcessCluster)
    from paddle_tpu.serving.node import tiny_llama_engine

    rs = np.random.RandomState(seed)
    sys_prompt = rs.randint(3, 256, (12,)).astype(np.int32)
    jobs = []
    for _ in range(requests):
        tail = rs.randint(3, 256,
                          (int(rs.randint(2, 7)),)).astype(np.int32)
        jobs.append((np.concatenate([sys_prompt, tail]),
                     int(rs.randint(3, 6))))
    # uninterrupted single-engine references: the factory builds
    # bit-identical weights from the seed in every process, and
    # per-request greedy decode is batch-composition-independent, so
    # routing cannot change any stream
    ref_engine = tiny_llama_engine()()
    refs = [np.asarray(ref_engine.generate([p], max_new_tokens=m)[0])
            for p, m in jobs]

    wd = workdir or tempfile.mkdtemp(prefix="mp_soak_")
    t_start = time.perf_counter()
    fp = None
    mc = None
    inj = FaultInjector(seed=seed)
    try:
        fp = FabricProcess(wd, page_size=8)
        mc = MultiProcessCluster(
            replicas=2, prefill_replicas=1,
            workdir=os.path.join(wd, "cluster"), fabric=fp.endpoint)
        reqs = [mc.submit(p, max_new_tokens=m) for p, m in jobs]
        with inj:
            # first handoff export ships corrupt bytes; a mid-run send
            # and recv each drop a frame (site counts are RPC calls,
            # so double-digit nth lands a few steps in)
            inj.arm_tamper("handoff_export", nth=1)
            inj.arm("rpc_send", "raise", nth=7)
            inj.arm("rpc_recv", "raise", nth=19)
            killed = False
            steps = 0
            while mc.step():
                steps += 1
                if not killed and any(
                        len(r.tokens) >= 2
                        and mc._owner.get(r.rid) == 1
                        for r in reqs if not r.done):
                    os.kill(mc.nodes[1].proc.pid, signal.SIGKILL)
                    killed = True
                if steps >= max_steps:
                    raise SoakError(f"multiproc soak did not drain "
                                    f"within {max_steps} steps")

        # ---- invariants ----
        if not killed:
            raise SoakError("the decode worker never owned tokens — "
                            "the SIGKILL gate was not exercised")
        if mc.failovers_total < 1:
            raise SoakError("SIGKILL did not surface as a failover")
        if mc.handoff_corruptions_total < 1:
            raise SoakError("the tampered handoff payload was not "
                            "detected by the decode-side CRC gate")
        for site in ("rpc_send", "rpc_recv"):
            if not inj.fired.get(site):
                raise SoakError(f"armed {site} fault never fired — "
                                f"the transport retry path was not "
                                f"exercised")
        lost = [r.rid for r in reqs
                if not r.done or r.finish_reason not in ("eos",
                                                         "max_len")]
        if lost:
            raise SoakError(f"lost requests after drain: {lost}")
        mism = [r.rid for r, ref in zip(reqs, refs)
                if not np.array_equal(np.asarray(r.output), ref)]
        if mism:
            raise SoakError(
                f"duplicated/diverged token streams: {mism}")
        unbalanced = {}
        for i in range(len(mc.nodes)):
            st, _ = mc.nodes[i].call("tier_stats",
                                     {"drop_prefix": True})
            alloc = st["allocator"]
            if alloc["num_used"] != 0 or \
                    alloc["allocs_total"] != alloc["frees_total"]:
                unbalanced[i] = alloc
        if unbalanced:
            raise SoakError(f"allocator unbalanced after drain: "
                            f"{unbalanced}")
        fc = fp.client()
        fab_stats, _ = fc.call("stats")
        fc.close()
        if fab_stats["puts_total"] < 1:
            raise SoakError("the fabric never saw a demote — the "
                            "shared tier was not exercised")
        return {"seed": seed, "mode": "multiproc",
                "requests": len(reqs), "steps": steps,
                "failovers": mc.failovers_total,
                "handoffs": mc.handoffs_total,
                "handoff_corruptions": mc.handoff_corruptions_total,
                "faults_by_site": {s: n for s, n in inj.fired.items()
                                   if n},
                "fabric": {k: fab_stats[k]
                           for k in ("puts_total", "hits_total",
                                     "misses_total", "entries")},
                "elapsed_s": round(time.perf_counter() - t_start, 1)}
    finally:
        if mc is not None:
            mc.close()
        if fp is not None:
            fp.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--faults", type=int, default=50,
                    help="minimum injected faults across all sites")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--cluster", action="store_true",
                    help="cluster mode: kill a random replica "
                         "mid-soak, assert zero lost/duplicated "
                         "requests cluster-wide + affinity recovery")
    ap.add_argument("--replicas", type=int, default=3,
                    help="cluster-mode replica count")
    ap.add_argument("--crash", action="store_true",
                    help="crash mode (ISSUE 15): seeded workload, "
                         "process-death simulation after random armed "
                         "sites (incl. a torn WAL write), "
                         "recover-from-disk each time; asserts zero "
                         "lost/duplicated + token identity")
    ap.add_argument("--kills", type=int, default=4,
                    help="crash-mode simulated process deaths")
    ap.add_argument("--tp2d", action="store_true",
                    help="single-engine soak on a tp=2 x dp=2 serving "
                         "mesh (ISSUE 17); references stay "
                         "single-chip, so the parity gate doubles as "
                         "the 2-D-mesh identity gate under fault "
                         "fire (needs 4 devices)")
    ap.add_argument("--multiproc", action="store_true",
                    help="multi-process mode (ISSUE 19): a real "
                         "2-replica + fabric process tree; SIGKILL "
                         "the decode worker mid-soak, tamper a wire "
                         "handoff, drop RPC frames; asserts zero "
                         "lost/duplicated requests, every corruption "
                         "detected, balanced allocators")
    ap.add_argument("--traffic", action="store_true",
                    help="traffic mode (ISSUE 13): trace-driven "
                         "open-loop load against an autoscaling "
                         "cluster with corruption + handoff faults "
                         "armed; asserts zero lost/duplicated "
                         "requests and that the replica count both "
                         "grew and shrank")
    args = ap.parse_args()
    if args.multiproc:
        report = run_multiproc_soak(seed=args.seed,
                                    requests=args.requests)
        print(json.dumps(report, indent=2))
        print("chaos_soak: OK — decode worker SIGKILLed and replaced "
              "from its WAL dir, corrupt wire handoff detected, "
              "dropped RPC frames absorbed by bounded retry, zero "
              "lost/duplicated requests, balanced allocators",
              file=sys.stderr)
        return 0
    if args.crash:
        report = run_crash_soak(seed=args.seed, kills=args.kills)
        print(json.dumps(report, indent=2))
        print("chaos_soak: OK — process died and recovered from disk "
              f"{report['deaths']}x, zero lost/duplicated requests, "
              "token-identical streams, balanced allocator",
              file=sys.stderr)
        return 0
    if args.traffic:
        report = run_traffic_soak(seed=args.seed)
        print(json.dumps(report, indent=2))
        print("chaos_soak: OK — autoscaled up and down under the "
              "trace, every corruption detected+quarantined, zero "
              "lost/duplicated requests", file=sys.stderr)
        return 0
    if args.cluster:
        report = run_cluster_soak(seed=args.seed,
                                  requests=args.requests,
                                  replicas=args.replicas)
        print(json.dumps(report, indent=2))
        print("chaos_soak: OK — replica killed and rebuilt, zero "
              "lost/duplicated requests cluster-wide, affinity "
              "recovered", file=sys.stderr)
        return 0
    kw = dict(tp=2, dp=2) if args.tp2d else {}
    report = run_soak(seed=args.seed, faults=args.faults,
                      requests=args.requests, **kw)
    print(json.dumps(report, indent=2))
    print("chaos_soak: OK — zero lost/duplicated requests, balanced "
          "allocator, all sites faulted"
          + (" (tp=2 x dp=2 mesh)" if args.tp2d else ""),
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
