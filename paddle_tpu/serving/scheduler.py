"""SLO-aware serving scheduler: the control plane over the
continuous-batching engine.

PRs 2–3 built the data plane — paged KV pool, refcounted prefix cache,
chunked prefill, one static-shape ragged decode program — but admission
stayed FIFO and best-effort: a burst of long prompts starves in-flight
decodes, and under :class:`~paddle_tpu.serving.PoolExhausted` the engine
can only back-pressure, never reclaim. :class:`ServingScheduler` closes
that gap (design shape: Orca/vLLM-style schedulers on page-granular
preemption):

- **Priority queues** — requests carry a priority class
  (:class:`~paddle_tpu.serving.policy.Priority`; lower = more
  important) and admit strictly by class, FIFO within a class.
- **Token-budgeted step planning** — per step a
  :class:`~paddle_tpu.serving.policy.TokenBudgetPlanner` packs decode
  slots (1 token each) and prefill chunks (page-rounded widths) in
  priority order under ``token_budget``, bounding the latency of every
  engine step; ready work the budget defers runs on later steps.
- **Preempt / resume over paged KV** — when a higher-priority admission
  cannot be satisfied, a
  :class:`~paddle_tpu.serving.policy.PreemptionPolicy` victim's pages
  are evicted back to the pool
  (:meth:`~paddle_tpu.serving.PagedKVCache.evict_for_preempt`; pages
  shared with the prefix trie survive under the trie's references and
  reclaim via the allocator's evict-on-pressure path) and the victim
  requeues at the FRONT of its class. Resume replays ``prompt +
  tokens[:-1]`` through the PR-3 continuation-prefill program
  (:func:`~paddle_tpu.models.generate.paged_prefill_chunk`) — prefix
  pages still in the trie map straight back in — and continues decoding
  from the last sampled token, TOKEN-IDENTICAL to an uninterrupted run
  (gated in ``tests/test_scheduler.py`` at fp and int8-KV).
- **Deadlines** — a queued request whose ``deadline_s`` lapses before
  admission is cancelled with the structured finish reason
  ``deadline_exceeded`` instead of silently aging in the queue. The
  deadline is an ADMISSION SLO: a request that was admitted in time
  and later preempted already met it, so preempted requeues resume
  instead of being cancelled.

Telemetry (paddle_tpu.observability): per-class queue-depth gauges,
preemption/resume counters, a time-in-queue histogram, and a per-step
budget-utilization gauge — zero-cost when metrics are disabled.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Deque, Dict, Optional

import numpy as np

from ..observability import hooks as _obs
from .adapters import AdapterPoolExhausted
from .paged_cache import PoolExhausted
from .policy import (FinishReason, PreemptionPolicy, Priority, StepPlan,
                     TokenBudgetPlanner)
from .resilience import DEGRADED_MODES, fault_point


#: the spans of a step that run under a program in flight when the
#: step is pipelined (host_overhead_fraction leaves them out then)
_HIDDEN_SPANS = ("sched.admit", "sched.plan", "engine.dispatch",
                 "engine.commit")
#: the span a prefill chunk's commit opens (engine._commit_chunk): a step
#: in which its count grew committed a chunk's program
_CHUNK_COMMIT = "engine.wait/chunk"


class ServingScheduler:
    """Request-lifecycle scheduler between callers and a
    :class:`~paddle_tpu.inference.ContinuousBatchingEngine`.

    The scheduler OWNS the engine: callers submit through
    :meth:`submit` (never ``engine.submit``) and drive :meth:`step` /
    :meth:`run`; the engine's own FIFO queue stays empty. ``clock`` is
    injectable (monotonic seconds) so deadline behavior is testable.
    """

    def __init__(self, engine, *, token_budget: Optional[int] = None,
                 enable_preemption: bool = True,
                 planner: Optional[TokenBudgetPlanner] = None,
                 preemption_policy: Optional[PreemptionPolicy] = None,
                 clock: Callable[[], float] = time.monotonic,
                 mesh=None, overlap: Optional[bool] = None):
        if not engine.idle:
            raise ValueError(
                "ServingScheduler requires a fresh engine: it owns "
                "admission, and requests already queued or running "
                "through the engine's FIFO path would bypass priority")
        if mesh is not None and getattr(engine, "mesh", None) is not mesh:
            # the scheduler is pure host logic and shards NOTHING
            # itself — the tensor-parallel data plane lives in the
            # engine (ISSUE 7). The knob exists so a deployment that
            # wires the mesh at the scheduler surface fails loudly on a
            # mismatch instead of silently scheduling a single-chip
            # engine it believed was sharded.
            raise ValueError(
                "ServingScheduler(mesh=...) does not match the "
                "engine's mesh — pass the mesh to "
                "ContinuousBatchingEngine(mesh=...); the scheduler's "
                "host logic is mesh-agnostic (identical plans, "
                "replicated block tables)")
        self.mesh = mesh if mesh is not None else getattr(
            engine, "mesh", None)
        self.engine = engine
        self.planner = planner or TokenBudgetPlanner(
            token_budget, engine.cache.page_size)
        self.preemption = (preemption_policy or PreemptionPolicy()
                           if enable_preemption else None)
        self.clock = clock
        self._queues: Dict[int, Deque] = {}
        self._drafts: Dict = {}      # this step's speculative proposals
        self.last_plan: Optional[StepPlan] = None
        self._steps = 0
        self.preemptions_total = 0
        self.resumes_total = 0
        self.deadline_cancels_total = 0
        self._swap_debt = 0     # host-tier swap-in tokens not yet charged
        # the engine's degraded-mode rung, mirrored here by whoever
        # owns the ladder (EngineSupervisor._apply_degraded) so
        # load_stats() is a complete health snapshot — previously the
        # rung was only observable through the metrics registry, which
        # a router cannot read when metrics are disabled
        self.degraded_level = 0
        # --- the decode pipeline: unless ``overlap`` is False, step()
        # launches decode step k+1 BEHIND step k, before k's tokens are
        # read (the device holds them and feeds them on), and reads
        # step k-1 meanwhile: the host's planning, launching and
        # bookkeeping leave the device's critical path. Where the
        # engine cannot run ahead (engine.pipeline_depth() == 0:
        # speculation, grammar constraints) the same loop commits
        # before it plans. ``overlap=False`` is the synchronous chain,
        # each program committed in place: the token-identity
        # reference the pipelined path is gated against.
        self.overlap = overlap is None or bool(overlap)
        # launch_seq of the last program launched BEFORE the newest
        # step: what the next step() reads and commits first
        self._mark = 0
        # deadline fast path: _expire_deadlines scans every queue each
        # step — pointless host work when no live request ever carried
        # a deadline (the common case); one counter skips it
        self._deadlines_live = 0
        #: committed units (tokens/slots) of the last step — the
        #: busy-spin detector's input alongside last_plan
        self.last_committed = 0
        #: the engine's span totals: the step's phases land beside the
        #: engine's own (dispatch, wait, commit) and come out in stats()
        self.spans = engine.spans
        for name in ("steps_committing_chunk_total",
                     "steps_committing_chunk_ns_total"):
            self.spans.count(name, 0)
        #: host-overhead telemetry mirrors (readable without the
        #: metrics registry): fraction of
        #: the last step's wall time spent on EXPOSED host work (host
        #: bookkeeping not hidden under an in-flight device program),
        #: derived from the span totals in :meth:`step`
        self.last_host_frac: Optional[float] = None
        self.host_frac_ema: Optional[float] = None
        self.idle_fences_total = 0

    # ---- identity (ISSUE 16) ----
    @property
    def replica_id(self) -> int:
        """The replica id trace spans carry — one source of truth (the
        engine's), stamped by the cluster/supervisor; -1 = unplaced."""
        return getattr(self.engine, "replica_id", -1)

    @replica_id.setter
    def replica_id(self, value: int) -> None:
        self.engine.replica_id = int(value)

    # ---- intake ----
    def submit(self, prompt, max_new_tokens: int = 16, *,
               priority=Priority.NORMAL,
               deadline_s: Optional[float] = None, eos_token_id=None,
               adapter_id: int = 0, constraint=None):
        """Queue a prompt with a priority class and an optional
        admission deadline (seconds from now; a request still queued
        when it lapses is cancelled with ``deadline_exceeded``).
        Returns the request handle (``.done`` / ``.tokens`` /
        ``.output`` / ``.finish_reason`` fill in as steps run).

        ``adapter_id`` / ``constraint`` (ISSUE 14) pass through to the
        engine's request intake; an admission whose adapter slot pool
        is fully pinned defers exactly like one the page pool can't
        cover (:class:`~paddle_tpu.serving.adapters.
        AdapterPoolExhausted` is a :class:`PoolExhausted`)."""
        req = self.engine.create_request(
            prompt, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id, adapter_id=adapter_id,
            constraint=constraint)
        req.priority = int(priority)
        req.submitted_at = req.enqueued_at = self.clock()
        if deadline_s is not None:
            req.deadline_at = req.submitted_at + float(deadline_s)
            self._deadlines_live += 1
        # trace minted HERE (ISSUE 16): it rides the handle through
        # every lifecycle edge from this point on
        _obs.serving_trace_submit(req, replica=self.replica_id)
        _obs.serving_trace_enqueued(req)
        self._queues.setdefault(int(priority), deque()).append(req)
        return req

    def requeue(self, req, *, front: bool = False):
        """Re-enqueue an EXISTING request handle into its priority
        class — the supervisor's recovery/restore path
        (:class:`~paddle_tpu.serving.resilience.EngineSupervisor`
        re-seats journaled sessions through the normal admission
        machinery so the resume replay stays the one gated code path).
        ``front`` requeues ahead of the class (a preemption-style
        requeue)."""
        req.enqueued_at = self.clock()
        if req.submitted_at is None:
            req.submitted_at = req.enqueued_at
        if req.deadline_at is not None:
            self._deadlines_live += 1
        # attach is idempotent: a handle that already rides a trace
        # (handoff import, failover rehome) keeps it — stitching; a
        # recovered handle minted fresh gets one here
        _obs.serving_trace_submit(req, replica=self.replica_id)
        _obs.serving_trace_enqueued(req)
        q = self._queues.setdefault(int(req.priority), deque())
        if front:
            q.appendleft(req)
        else:
            q.append(req)

    # ---- per-step phases ----
    def _expire_deadlines(self, now: float):
        """Cancel requests whose deadline lapsed before they produced a
        token. The deadline is a FIRST-TOKEN SLO in two phases:

        - QUEUED requests that lapse cancel with ``deadline_exceeded``
          (never admitted, never held pages).
        - MID-PREFILL admissions that lapse cancel BEFORE their next
          chunk is planned, releasing their reserved pages back to the
          pool (previously expiry only fired between queue scans, so a
          long chunked prefill kept burning budget and pages for a
          request that could never meet its SLO). Pages shared with the
          prefix trie survive under the trie's references, exactly as
          on any retirement.

        A request the scheduler admitted in time and then preempted
        (``preemptions > 0``) already met the SLO — cancelling would
        discard finished work because of the scheduler's own eviction,
        so preempted requeues (and their resume replays) are exempt and
        simply resume."""
        if not self._deadlines_live:
            # vectorized-bookkeeping fast path (ISSUE 12 satellite c):
            # no deadline-bearing request was ever (re)enqueued, so the
            # per-queue scans below can never find work — skip the
            # whole pass instead of walking every queue every step
            return

        def expired(r):
            return (r.deadline_at is not None and now >= r.deadline_at
                    and r.preemptions == 0)
        for prio, q in self._queues.items():
            if not any(expired(r) for r in q):
                continue
            keep: Deque = deque()
            for req in q:
                if expired(req):
                    self.engine.cancel_request(
                        req, FinishReason.DEADLINE_EXCEEDED.value)
                    self.deadline_cancels_total += 1
                else:
                    keep.append(req)
            self._queues[prio] = keep
        # mid-prefill expiry (ISSUE 8 satellite): tokens are only
        # sampled once prefill completes, so a pending admission past
        # its deadline has produced nothing worth keeping — cancel it
        # and free its reserved pages before planning its next chunk
        for slot, (req, _rem) in list(
                self.engine.pending_prefills().items()):
            if expired(req) and not req.tokens:
                self.engine.cancel_request(
                    req, FinishReason.DEADLINE_EXCEEDED.value)
                self.deadline_cancels_total += 1

    def _preempt_for(self, req, candidates=None) -> bool:
        """Evict one strictly-lower-class running request to make room
        for ``req``; the victim requeues at the FRONT of its class (it
        already waited its turn once). Under the host tier (ISSUE 10)
        the policy PREFERS victims whose eviction swaps to host RAM
        (near-free swap-in resume) over mid-prefill victims that would
        pay a replay. ``candidates`` restricts the victim set (the
        adapter-slot shortfall path: only victims that pin a slot can
        relieve it). Returns False when no eligible victim exists."""
        if self.preemption is None:
            return False
        running = self.engine.running_requests()
        if candidates is not None:
            running = [r for r in running if r in candidates]
        victim = self.preemption.pick_victim(
            running, req.priority,
            swappable=getattr(self.engine, "swap_candidate", None))
        if victim is None:
            return False
        if self.engine.has_inflight():
            # there is somebody to evict: read the tokens in flight
            # first (a slot may free by itself in there) and let the
            # caller try again against the committed state
            self.engine.fence()
            return True
        self.engine.preempt_request(victim)
        self.preemptions_total += 1
        victim.enqueued_at = self.clock()   # queue wait restarts here
        _obs.serving_trace_enqueued(victim)
        self._queues.setdefault(int(victim.priority),
                                deque()).appendleft(victim)
        return True

    def _preemption_feasible(self, req) -> bool:
        """Optimistic feasibility bound before evicting ANYONE for a
        pool shortfall: every usable page not pinned by an
        equal-or-higher-class table is reclaimable in principle (free
        pages, strictly-lower-class victims' pages, trie-held pages —
        the allocator's evict-on-pressure path reaches the last). If
        even that bound can't cover the request, preempting would cost
        each victim an eviction + full resume replay and the admission
        would STILL fail — bail out with zero casualties instead."""
        cache = self.engine.cache
        pinned = set()
        for r in self.engine.running_requests():
            if r.priority <= int(req.priority):
                pinned.update(cache.pages_held(r.slot))
        need = cache.pages_for(req.prompt.shape[1] + req.max_new_tokens)
        return need <= cache.allocator.num_usable - len(pinned)

    def _adapter_feasible(self, req) -> bool:
        """Can ``req``'s adapter be seated AT ALL right now? False
        when the pool needs a new slot, none is free or reclaimable,
        and no strictly-lower-class running request pins one — in that
        state every preemption (seat- or page-motivated) is pointless,
        so the admission defers with zero casualties."""
        aid = getattr(req, "adapter_id", 0)
        pool = getattr(self.engine, "adapters", None)
        if not aid or pool is None or pool.resident(aid):
            return True                 # base row / pin-in-place hit
        if pool.slot_available():
            return True
        return any(getattr(r, "adapter_id", 0) != 0
                   and int(r.priority) > int(req.priority)
                   for r in self.engine.running_requests())

    def _admit_one(self, req) -> bool:
        eng = self.engine
        while True:
            if not self._adapter_feasible(req):
                return False
            if not eng.cache.free_slots():
                # no slot: preempt only when the POOL side can work out
                # too (feasibility), else the victim pays for nothing
                if not (self._preemption_feasible(req)
                        and self._preempt_for(req)):
                    return False
                continue                # preemption freed a slot; retry
            try:
                return eng.admit_request(req)
            except AdapterPoolExhausted:
                # every ADAPTER slot is pinned: page reclaim cannot
                # help, so only a strictly-lower-class victim that
                # itself pins a slot is worth evicting — with none,
                # defer (back-pressure) instead of thrashing base-model
                # victims whose preemption frees no adapter slot
                pinning = [r for r in eng.running_requests()
                           if getattr(r, "adapter_id", 0) != 0]
                if not (pinning
                        and self._preempt_for(req, candidates=pinning)):
                    return False
            except PoolExhausted:
                # a slot is free but the POOL can't cover the request:
                # evict a lower-class victim's pages and retry. Each
                # round removes one running request, so this terminates.
                if not (self._preemption_feasible(req)
                        and self._preempt_for(req)):
                    return False

    def _admit(self, now: float):
        """Admit strictly by class (FIFO within a class). A blocked
        head-of-class blocks everything below it — admitting a smaller
        lower-class request around a starved higher-class one would be
        priority inversion by another name."""
        for prio in sorted(self._queues):
            q = self._queues[prio]
            while q:
                req = q[0]
                if req.done:
                    # cancelled while queued (e.g. a caller's direct
                    # engine.cancel_request): admitting would decode it
                    # anyway and overwrite the cancellation
                    q.popleft()
                    continue
                if not self._admit_one(req):
                    return
                q.popleft()
                if req.preemptions > 0:
                    self.resumes_total += 1
                # time-in-queue since the LATEST enqueue: a resumed
                # request's prior running time is not queue wait. The
                # clamp covers a victim preempted and re-admitted
                # within this same pass (its requeue stamp postdates
                # ``now``) — that wait is zero, not negative.
                wait = max(0.0, now - req.enqueued_at)
                _obs.serving_queue_wait(wait, prio)
                self.spans.count("queue_wait_ns_total", wait * 1e9)
                self.spans.count("admissions_total", 1)

    def _plan(self, reserved: int = 0) -> StepPlan:
        eng = self.engine
        ready = eng.ready_mask()
        decode = [(r.priority, r.rid, r.slot)
                  for r in eng.running_requests() if ready[r.slot]]
        pending = [(req.priority, req.rid, slot, remaining)
                   for slot, (req, remaining)
                   in eng.pending_prefills().items()]
        # speculative engines draft at PLAN time so each row's verify
        # width (1 + drafts) is charged against the budget before
        # anything executes; the proposals are stashed for this step's
        # execution (the engine must not re-propose under a different
        # history). Such an engine runs at pipeline depth 0: step()
        # has committed everything before it plans, so the history the
        # proposer reads is final on both paths.
        self._drafts = (eng.propose_drafts(ready)
                        if getattr(eng, "spec", None) is not None else {})
        widths = {s: d.size for s, d in self._drafts.items()} or None
        # 2-D serving mesh (ISSUE 17): slots split into contiguous
        # per-dp-shard row blocks, and the step's wall time is the max
        # over shards — tell the planner which block each slot rides
        # so a budget-truncated decode set spreads across shards
        dpg = None
        if int(getattr(eng, "dp", 1) or 1) > 1:
            rows = eng.max_batch // eng.dp
            dpg = {s: s // rows for s in range(eng.max_batch)}
        return self.planner.plan(
            decode, pending, chunk_cap=eng.prefill_chunk,
            spec_drafts=widths, reserved_tokens=reserved, dp_group=dpg)

    def _decode_args(self, plan: StepPlan) -> tuple:
        """The plan's decode rows as the engine takes them: the mask,
        and behind it, on a speculating step, the proposals trimmed to
        the planner's per-row draft allowance (a row the budget
        degraded to plain decode rides the verify batch with zero
        drafts — it commits exactly its greedy token)."""
        mask = np.zeros((self.engine.max_batch,), bool)
        mask[plan.decode_slots] = True
        if not plan.spec_drafts:
            return (mask,)
        return mask, {s: self._drafts[s][:k]
                      for s, k in plan.spec_drafts.items()}

    def _dispatch_plan(self, plan: StepPlan) -> bool:
        """Launch the plan's programs WITHOUT committing: prefill
        chunks first (the decode program chains behind them on
        device), then the masked decode/verify step. True where
        something was launched."""
        eng = self.engine
        seq = eng.launch_seq
        for slot, cap in plan.prefills:
            eng.prefill_dispatch(slot, max_tokens=cap)
        if plan.decode_slots:
            (eng.spec_dispatch if plan.spec_drafts
             else eng.decode_dispatch)(*self._decode_args(plan))
        return eng.launch_seq != seq

    def _execute_plan(self, plan: StepPlan) -> int:
        """The synchronous reference execution: each program dispatches
        and commits in place (prefill chunks, then the masked
        decode/verify program). Returns committed units."""
        eng = self.engine
        n = 0
        for slot, cap in plan.prefills:
            eng.prefill_step(slot, max_tokens=cap)
            n += 1
        if plan.decode_slots:
            n += (eng.spec_step if plan.spec_drafts
                  else eng.decode_step)(*self._decode_args(plan))
        return n

    def step(self) -> bool:
        """One scheduler step: expire deadlines, admit (preempting if
        needed), plan under the token budget, then execute.

        With ``overlap=False`` execution is the synchronous chain
        (prefill chunks, then the masked decode program, each committed
        in place): a token is on its handle when the call that computed
        it returns.

        Otherwise the step is PIPELINED one decode step deep. With
        steps k-1 and k on the device, the call (1) waits for and
        commits step k-1 — its tokens are on the request handles from
        here on, in this call, before anything else is done; (2)
        admits and plans against the PREDICTED state: lengths, token
        counts, ``max_len`` finishes and chunk cursors as they stand
        once everything launched has run, none of which needs a
        token's value; (3) launches step k+1 behind k — a row's input
        token is k's output on the device — and returns with k and k+1
        in flight. So a token is visible at the start of the SECOND
        call after the one that launched it (about when the device
        finishes it: the loop runs at the device's pace), a row whose
        token turns out to be ``eos`` has one more row computed and
        dropped, and a slot it frees is refilled one step later than
        on the synchronous chain. Served tokens are the same. Where
        the engine cannot run ahead (``engine.pipeline_depth() == 0``:
        speculation, grammar constraints) or a seated request is
        preempted, swapped out or cancelled, everything in flight is
        committed first (``pipeline_fences_total``); a call that
        launches nothing commits everything too, so a caller that
        steps until its handles are done never spins.

        Returns False when no work remains — nothing queued, seated or
        in flight. ``last_plan`` holds the step's
        :class:`~paddle_tpu.serving.policy.StepPlan`."""
        fault_point("sched_tick")
        eng = self.engine
        if eng.queued_requests():
            # engine.submit() after attach would sit in the engine's
            # FIFO queue forever (the scheduler only drains its own
            # priority queues) — step() would spin reporting work
            # remains while never decoding it. Fail loudly instead.
            raise ValueError(
                "requests were queued through engine.submit() after "
                "the scheduler attached — submit through "
                "ServingScheduler.submit so priority admission is "
                "not bypassed")
        sp = self.spans
        step0, wait0 = sp.ns("sched.step"), sp.ns("engine.wait")
        busy0 = sum(sp.ns(n) for n in _HIDDEN_SPANS)
        chunks0 = sp.calls(_CHUNK_COMMIT)
        sp.step_begins(self._steps)
        with sp.span("sched.step", step=self._steps):
            committed = 0
            if self.overlap:
                committed = eng.commit_inflight(upto=self._mark)
                if not eng.pipeline_depth():
                    committed += eng.fence()
            # host work done while a step is in flight on the device is
            # HIDDEN (off the critical path); the same work with the
            # device idle is EXPOSED — the host_overhead_fraction
            # gauge's numerator. The synchronous path never overlaps,
            # so all its host time is exposed by construction.
            hidden = eng.has_inflight()
            now = self.clock()
            with sp.span("sched.admit", queued=sum(
                    len(q) for q in self._queues.values())):
                self._expire_deadlines(now)
                self._admit(now)
            with sp.span("sched.plan"):
                # host tier (ISSUE 10): admissions that SWAPPED IN
                # during _admit already wrote KV bytes this step (one
                # scatter per resume) — charge them against the step
                # budget at the prefill rate (page_size tokens per
                # page). A single swap-in larger than the whole budget
                # AMORTIZES: the debt carries into later steps'
                # reserves, so every step's (planned + reserved) stays
                # under the ceiling and the average per-step KV-write
                # bound the budget promises holds through swap-heavy
                # bursts.
                consume = getattr(eng.cache, "consume_swap_charge", None)
                if consume is not None:
                    self._swap_debt += consume()
                budget = self.planner.token_budget
                reserved = (min(self._swap_debt, budget) if budget
                            else self._swap_debt)
                self._swap_debt -= reserved
                plan = self._plan(reserved)
            if self.overlap:
                # what is in flight now is step k: the next call reads
                # it only after it has launched its own
                self._mark = eng.launch_seq
                if not self._dispatch_plan(plan):
                    committed += eng.commit_inflight()
            else:
                committed = self._execute_plan(plan)
            self.last_plan = plan
            self.last_committed = committed
            self._steps += 1
        # the step less device-wait less what ran under a program in
        # flight, from the span totals: no second set of stamps
        wall = max(1, sp.ns("sched.step") - step0)
        if sp.calls(_CHUNK_COMMIT) > chunks0:
            # a pipelined iteration waits for what it commits, so its
            # wall is the device's time for a step that carried a chunk
            sp.count("steps_committing_chunk_total", 1)
            sp.count("steps_committing_chunk_ns_total", wall)
        exposed = max(0, wall - (sp.ns("engine.wait") - wait0)
                      - (sum(sp.ns(n) for n in _HIDDEN_SPANS) - busy0
                         if hidden else 0))
        frac = min(1.0, exposed / wall)
        self.last_host_frac = frac
        self.host_frac_ema = (frac if self.host_frac_ema is None
                              else 0.9 * self.host_frac_ema + 0.1 * frac)
        _obs.serving_sched_step(
            {p: len(q) for p, q in self._queues.items()},
            # swap-in reserves are spent budget: the utilization gauge
            # reports what the step actually consumed, plan + reserve
            plan.scheduled_tokens + plan.reserved_tokens, plan.budget)
        _obs.serving_overlap_step(exposed, wall, committed, self.overlap)
        more = (any(self._queues.values()) or not eng.idle
                or eng.has_inflight())
        sp.step_ends(more)
        return more

    def _idle_fence(self) -> None:
        """The busy-spin fix (ISSUE 12 satellite): a step that planned
        nothing and committed nothing means every remaining obligation
        is waiting on device or swap completion — re-planning empty
        steps would burn host CPU re-scanning queues (visible as
        zero-token steps in ``serving_sched_step``). Instead: commit
        whatever is in flight (a real fence — the blocked work becomes
        plannable next step), else flush pending async swap-out DMAs,
        else yield the thread."""
        eng = self.engine
        self.idle_fences_total += 1
        fenced = False
        if eng.has_inflight():
            self.last_committed = eng.commit_inflight()
            fenced = True
        else:
            fence = getattr(eng.cache, "fence_swaps", None)
            if fence is not None and fence():
                fenced = True
            else:
                time.sleep(0)           # yield: no fence to make progress on
        _obs.serving_sched_idle(fenced)

    def run(self) -> None:
        """Drive steps until every submitted request finished (or was
        cancelled by its deadline). A step that planned zero tokens and
        committed nothing fences/yields instead of immediately
        re-planning (see :meth:`_idle_fence`)."""
        while self.step():
            plan = self.last_plan
            if (plan is not None and plan.scheduled_tokens == 0
                    and plan.reserved_tokens == 0
                    and self.last_committed == 0):
                self._idle_fence()

    def flush(self) -> int:
        """Commit everything in flight now — both steps of the
        pipeline — for a caller that needs every computed token on its
        handle before the next :meth:`step` (which would show only the
        older step's). No-op on the synchronous path, where a token is
        visible when the step that computed it returns."""
        return self.engine.commit_inflight()

    def load_stats(self) -> Dict:
        """One structured load/health snapshot — the PUBLIC surface a
        multi-replica router reads (ISSUE 9): per-class queue depths,
        the tightest queued deadline's remaining slack, slot and page
        occupancy, and the degraded-mode rung. Everything here is host
        bookkeeping (no device sync); the router never reaches into
        engine internals."""
        now = self.clock()
        eng = self.engine
        alloc = eng.cache.allocator
        depths = {int(p): len(q) for p, q in self._queues.items() if q}
        slack = None
        # backlog in TOKENS (ISSUE 13): what the queued requests will
        # actually cost to serve — the autoscaler's scale signal and
        # the admission controller's TTFT-feasibility denominator
        # (request counts hide the long-prompt/short-prompt mix)
        queued_tokens = 0
        for q in self._queues.values():
            for r in q:
                if not r.done:
                    queued_tokens += (r.prompt.shape[1]
                                      + r.max_new_tokens
                                      - len(r.tokens))
                if r.deadline_at is not None and not r.done:
                    s = r.deadline_at - now
                    slack = s if slack is None else min(slack, s)
        inflight_tokens = int(sum(
            r.max_new_tokens - len(r.tokens)
            for r in eng.running_requests() if not r.done))
        level = self.degraded_level
        s = {
            "queue_depths": depths,
            "queued_total": sum(depths.values()),
            "queued_tokens": int(queued_tokens),
            "inflight_tokens": inflight_tokens,
            "running": len(eng.running_requests()),
            "pending_prefills": len(eng.pending_prefills()),
            "free_slots": len(eng.cache.free_slots()),
            "oldest_deadline_slack_s": slack,
            "pool_occupancy": alloc.utilization(),
            "pool_free_pages": alloc.num_free,
            "degraded_level": level,
            "degraded_mode": (DEGRADED_MODES[level]
                              if level < len(DEGRADED_MODES) else "dead"),
        }
        host = getattr(eng.cache, "host", None)
        if host is not None:
            # hierarchical KV (ISSUE 10): the host tier's residency is
            # part of a replica's load picture — a router can prefer
            # replicas with host headroom for swap-heavy tenants
            s["host_pool_pages"] = host.pages_resident
            s["host_pool_bytes"] = host.bytes_resident
        pool = getattr(eng, "adapters", None)
        if pool is not None:
            # adapter plane (ISSUE 14): slot headroom + residency — the
            # router's adapter-affinity tie-breaker signal (a replica
            # already holding a tenant's adapter serves it with zero
            # load/promote cost)
            s["adapter_slots_free"] = pool.slots - pool.used_slots
            s["adapter_slots_used"] = pool.used_slots
        return s

    def stats(self) -> Dict:
        s = self.engine.stats()
        s["sched_steps"] = self._steps
        s["sched_queued"] = {int(p): len(q)
                             for p, q in self._queues.items() if q}
        s["preemptions_total"] = self.preemptions_total
        s["resumes_total"] = self.resumes_total
        s["deadline_cancels_total"] = self.deadline_cancels_total
        s["overlap"] = self.overlap
        s["idle_fences_total"] = self.idle_fences_total
        if self.host_frac_ema is not None:
            s["host_overhead_fraction"] = round(self.host_frac_ema, 4)
        if self.last_plan is not None:
            s["last_step_tokens"] = self.last_plan.scheduled_tokens
            s["token_budget"] = self.last_plan.budget
        return s
