"""SLO-aware serving scheduler tests (ISSUE 4 acceptance gates).

The control plane over the continuous-batching engine: priority-class
admission, token-budgeted step planning, deadline expiry, and
preempt->evict->resume over the paged KV pool. The two hard gates:

- a preempted-then-resumed request's output tokens are BIT-IDENTICAL
  to the same request decoded uninterrupted (fp and int8-KV);
- the step planner never schedules more than its configured token
  budget in one engine step, and a high-priority admission succeeds at
  100% pool occupancy via preemption.
"""
import types

import numpy as np
import jax
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.serving import (FinishReason, PreemptionPolicy, Priority,
                                ServingScheduler, StepPlan,
                                TokenBudgetPlanner)


def _setup(seed=0, **kw):
    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64, **kw)
    params = llama.init_params(jax.random.key(seed), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _req(priority, ntokens, rid):
    return types.SimpleNamespace(priority=int(priority),
                                 tokens=[0] * ntokens, rid=rid)


class TestTokenBudgetPlanner:
    """Pure host-side planner: the budget is a hard ceiling."""

    def test_budget_is_hard_ceiling(self):
        """ACCEPTANCE: across a sweep of mixed workloads the plan's
        token debit never exceeds the configured budget."""
        rs = np.random.RandomState(0)
        page = 8
        for budget in (8, 16, 24, 40):
            planner = TokenBudgetPlanner(budget, page)
            for trial in range(50):
                nd, npf = rs.randint(0, 6), rs.randint(0, 4)
                decode = [(rs.randint(0, 3), i, i) for i in range(nd)]
                pending = [(rs.randint(0, 3), 100 + i, 10 + i,
                            int(rs.randint(1, 64)))
                           for i in range(npf)]
                plan = planner.plan(decode, pending, chunk_cap=16)
                assert plan.scheduled_tokens <= budget
                # prefill widths stay page multiples (no rounding
                # through the ceiling)
                assert all(c % page == 0 and c >= page
                           for _, c in plan.prefills)

    def test_priority_order_high_prefill_beats_low_decode(self):
        planner = TokenBudgetPlanner(8, 8)
        plan = planner.plan([(Priority.LOW, 0, 0)],
                            [(Priority.HIGH, 1, 1, 16)], chunk_cap=8)
        assert plan.prefills == [(1, 8)]
        assert plan.decode_slots == []
        assert plan.deferred_decodes == 1
        assert plan.scheduled_tokens == 8

    def test_decode_uses_budget_tail(self):
        """A decode costs 1 and can use the sub-page tail a prefill
        can't."""
        planner = TokenBudgetPlanner(10, 8)
        plan = planner.plan([(Priority.LOW, 2, 0), (Priority.LOW, 3, 1)],
                            [(Priority.HIGH, 1, 1, 32)], chunk_cap=32)
        assert plan.prefills == [(1, 8)]       # one page affordable
        assert plan.decode_slots == [0, 1]     # 2 tokens of tail
        assert plan.scheduled_tokens == 10

    def test_no_budget_plans_all_decodes_one_chunk(self):
        planner = TokenBudgetPlanner(None, 8)
        plan = planner.plan([(1, 5, 3), (0, 2, 1)],
                            [(1, 7, 2, 20), (0, 4, 0, 12)], chunk_cap=16)
        assert plan.decode_slots == [1, 3]     # sorted, all ready slots
        assert plan.prefills == [(0, 16)]      # single best-class chunk
        assert plan.budget is None

    def test_chunk_cap_respected(self):
        planner = TokenBudgetPlanner(64, 8)
        plan = planner.plan([], [(0, 0, 0, 60)], chunk_cap=16)
        assert plan.prefills == [(0, 16)]

    def test_sub_page_budget_rejected(self):
        with pytest.raises(ValueError, match="smaller than one"):
            TokenBudgetPlanner(7, 8)
        with pytest.raises(ValueError, match="page_size"):
            TokenBudgetPlanner(None, 0)


class TestPreemptionPolicy:
    def test_strictly_lower_class_only(self):
        pol = PreemptionPolicy()
        running = [_req(Priority.HIGH, 4, 0), _req(Priority.NORMAL, 2, 1)]
        assert pol.pick_victim(running, Priority.NORMAL) is None
        assert pol.pick_victim(running, Priority.HIGH).rid == 1

    def test_victim_order_class_then_cheapest_then_youngest(self):
        pol = PreemptionPolicy()
        running = [_req(Priority.NORMAL, 1, 0), _req(Priority.LOW, 9, 1),
                   _req(Priority.LOW, 2, 2), _req(Priority.LOW, 2, 3)]
        # lowest class first, then fewest generated tokens (cheapest
        # replay), then highest rid (preserve older requests' work)
        assert pol.pick_victim(running, Priority.HIGH).rid == 3


class TestSchedulerLifecycle:
    def test_requires_fresh_engine(self):
        cfg, params = _setup()
        eng = ContinuousBatchingEngine(params, cfg, max_batch=1,
                                       page_size=8, max_len=16)
        eng.submit(_prompts(cfg, [4])[0], max_new_tokens=2)
        with pytest.raises(ValueError, match="fresh engine"):
            ServingScheduler(eng)

    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_preempt_resume_token_parity(self, kv):
        """ACCEPTANCE: preempt->evict->resume reproduces the
        uninterrupted decode BIT-FOR-BIT (fp and int8-KV)."""
        cfg, params = _setup(seed=1)
        p = _prompts(cfg, [6], seed=2)[0]
        new = 8

        ref_eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=32,
            kv_cache_dtype=kv)
        ref = ref_eng.generate([p], max_new_tokens=new)[0]

        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=32,
            kv_cache_dtype=kv)
        sched = ServingScheduler(eng)
        a = sched.submit(p, max_new_tokens=new, priority=Priority.LOW)
        while len(a.tokens) < 3:           # mid-decode, KV pages live
            sched.step()
        b = sched.submit(_prompts(cfg, [4], seed=3)[0],
                         max_new_tokens=2, priority=Priority.HIGH)
        sched.step()                       # admits b by preempting a
        assert sched.preemptions_total == 1 and a.preemptions == 1
        assert a.slot is None and b.slot is not None
        # transient structured reason while evicted; not done
        assert a.finish_reason == "preempted" == FinishReason.PREEMPTED
        assert not a.done
        sched.run()
        assert b.done and a.done
        assert sched.resumes_total == 1
        assert a.finish_reason == "max_len"
        np.testing.assert_array_equal(a.output, ref)

    def test_high_priority_admitted_at_full_pool(self):
        """ACCEPTANCE: at 100% pool occupancy a HIGH admission succeeds
        in one step via preemption instead of queueing behind
        PoolExhausted."""
        cfg, params = _setup(seed=2)
        # 2 slots x 2 pages fill the whole usable pool (trash + 4)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=16,
            num_pages=1 + 4, enable_prefix_cache=False)
        sched = ServingScheduler(eng)
        lows = [sched.submit(q, max_new_tokens=6, priority=Priority.LOW)
                for q in _prompts(cfg, [5, 6], seed=4)]
        for _ in range(4):
            sched.step()
        assert eng.cache.allocator.num_free == 0          # 100% occupied
        assert all(r.slot is not None for r in lows)
        hi = sched.submit(_prompts(cfg, [4], seed=5)[0],
                          max_new_tokens=4, priority=Priority.HIGH)
        sched.step()
        assert hi.slot is not None                        # admitted NOW
        assert sched.preemptions_total >= 1
        victims = [r for r in lows if r.preemptions > 0]
        assert victims and victims[0].finish_reason == "preempted"
        sched.run()
        assert all(r.done and r.finish_reason in ("eos", "max_len")
                   for r in lows + [hi])
        assert all(len(r.tokens) > 0 for r in lows + [hi])

    def test_preempt_mid_prefill_resume_parity(self):
        """Preempting a victim that has NOT produced a token yet (still
        mid-chunked-prefill) takes the other resume branch: the replay
        is just the prompt and the FIRST token samples from the final
        replay chunk's logits — still bit-identical."""
        cfg, params = _setup(seed=1)
        p = _prompts(cfg, [20], seed=17)[0]
        kw = dict(max_batch=1, page_size=8, max_len=32, prefill_chunk=8,
                  enable_prefix_cache=False)
        ref = ContinuousBatchingEngine(params, cfg, **kw).generate(
            [p], max_new_tokens=5)[0]
        eng = ContinuousBatchingEngine(params, cfg, **kw)
        sched = ServingScheduler(eng)
        a = sched.submit(p, max_new_tokens=5, priority=Priority.LOW)
        sched.step()                # first chunk only (8 of 20 tokens)
        assert a.slot is not None and len(a.tokens) == 0
        b = sched.submit(_prompts(cfg, [4], seed=18)[0],
                         max_new_tokens=2, priority=Priority.HIGH)
        sched.step()                # evicts a mid-prefill
        assert a.preemptions == 1 and b.slot is not None
        sched.run()
        assert b.done
        np.testing.assert_array_equal(a.output, ref)

    def test_equal_class_never_preempts(self):
        cfg, params = _setup(seed=3)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=16,
            enable_prefix_cache=False)
        sched = ServingScheduler(eng)
        a = sched.submit(_prompts(cfg, [4], seed=6)[0], max_new_tokens=4)
        sched.step()
        b = sched.submit(_prompts(cfg, [4], seed=7)[0], max_new_tokens=4)
        sched.step()
        assert a.slot is not None and b.slot is None      # b waits
        assert sched.preemptions_total == 0
        sched.run()
        assert a.done and b.done

    def test_deadline_expiry_cancels_queued_request(self):
        """A queued request whose deadline lapses is cancelled with the
        structured ``deadline_exceeded`` reason; running requests are
        untouched."""
        cfg, params = _setup(seed=4)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=16,
            enable_prefix_cache=False)
        t = [0.0]
        sched = ServingScheduler(eng, clock=lambda: t[0])
        a = sched.submit(_prompts(cfg, [4], seed=8)[0], max_new_tokens=6)
        b = sched.submit(_prompts(cfg, [4], seed=9)[0], max_new_tokens=6,
                         deadline_s=5.0)    # same class: queues behind a
        sched.step()
        assert a.slot is not None and b.slot is None
        t[0] = 10.0                         # past b's deadline
        sched.step()
        assert b.done and b.tokens == []
        assert b.finish_reason == "deadline_exceeded"
        assert b.finish_reason == FinishReason.DEADLINE_EXCEEDED
        assert sched.deadline_cancels_total == 1
        sched.run()
        assert a.done and a.finish_reason == "max_len"
        assert sched.stats()["deadline_cancels_total"] == 1

    def test_deadline_spares_preempted_requests(self):
        """The deadline is an ADMISSION SLO: a request admitted in time
        and then preempted by the scheduler's own eviction resumes past
        its lapsed deadline instead of losing its generated tokens."""
        cfg, params = _setup(seed=4)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=32,
            enable_prefix_cache=False)
        t = [0.0]
        sched = ServingScheduler(eng, clock=lambda: t[0])
        a = sched.submit(_prompts(cfg, [5], seed=19)[0],
                         max_new_tokens=6, priority=Priority.LOW,
                         deadline_s=1.0)     # admitted well within it
        while len(a.tokens) < 2:
            sched.step()
        b = sched.submit(_prompts(cfg, [4], seed=20)[0],
                         max_new_tokens=2, priority=Priority.HIGH)
        sched.step()                         # evicts a; a requeues
        assert a.preemptions == 1
        t[0] = 5.0                           # far past a's deadline
        sched.run()
        assert sched.deadline_cancels_total == 0
        assert a.done and a.finish_reason == "max_len"
        assert len(a.tokens) == 6 and b.done

    def test_resume_clears_preempted_reason_mid_prefill_victim(self):
        """The transient ``preempted`` reason clears when the resume
        replay completes, including for victims evicted before their
        first token (the replay ends in the sample-first branch)."""
        cfg, params = _setup(seed=1)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=32,
            prefill_chunk=8, enable_prefix_cache=False)
        sched = ServingScheduler(eng)
        a = sched.submit(_prompts(cfg, [20], seed=21)[0],
                         max_new_tokens=6, priority=Priority.LOW)
        sched.step()                         # first chunk only
        assert len(a.tokens) == 0
        b = sched.submit(_prompts(cfg, [4], seed=22)[0],
                         max_new_tokens=2, priority=Priority.HIGH)
        sched.step()
        assert a.finish_reason == "preempted"
        while not (len(a.tokens) > 0 and not a.done):
            sched.step()
        assert a.finish_reason is None       # decoding again, not evicted
        sched.run()
        assert a.finish_reason == "max_len"

    def test_deadline_expires_mid_prefill_frees_pages(self):
        """BUGFIX (ISSUE 8 satellite): a request whose deadline passes
        MID-prefill-chunk — admitted, pages reserved, no token sampled
        yet — cancels with ``deadline_exceeded`` before its next chunk
        is planned, and its reserved pages return to the pool. Pages
        shared with the prefix TRIE survive under the trie's
        references, like any other retirement."""
        cfg, params = _setup(seed=3)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=48,
            prefill_chunk=8)
        t = [0.0]
        sched = ServingScheduler(eng, clock=lambda: t[0])
        rs = np.random.RandomState(40)
        sys_p = rs.randint(3, cfg.vocab_size, (16,)).astype(np.int32)
        # warm the trie: a completes and publishes its prompt pages
        a = sched.submit(sys_p, max_new_tokens=4)
        sched.run()
        assert a.done
        alloc = eng.cache.allocator
        trie_held = alloc.num_used          # trie references only
        assert trie_held > 0
        # b shares the 16-token prefix, then needs 2 more chunks of
        # fresh prefill — and its deadline lapses after the first
        b = sched.submit(
            np.concatenate([sys_p, rs.randint(
                3, cfg.vocab_size, (16,)).astype(np.int32)]),
            max_new_tokens=8, deadline_s=5.0)
        sched.step()                        # admit + first fresh chunk
        assert b.slot is not None and len(b.tokens) == 0
        assert b.slot in dict(eng.pending_prefills())
        reserved = alloc.num_used
        assert reserved > trie_held         # fresh pages reserved
        t[0] = 10.0                         # deadline lapses mid-prefill
        sched.step()                        # cancels BEFORE next chunk
        assert b.done and b.tokens == []
        assert b.finish_reason == "deadline_exceeded"
        assert sched.deadline_cancels_total == 1
        assert not eng.pending_prefills()   # no further chunk planned
        # the fresh pages came back; the trie-shared prefix survived
        assert alloc.num_used == trie_held
        # the survivors are still servable: a prefix-sharing admission
        # after the cancel maps them straight back in
        c = sched.submit(np.concatenate(
            [sys_p, rs.randint(3, cfg.vocab_size, (4,)
                               ).astype(np.int32)]), max_new_tokens=4)
        sched.run()
        assert c.done and len(c.tokens) == 4

    def test_deadline_spares_mid_prefill_resume_replay(self):
        """A PREEMPTED victim resuming through the continuation-prefill
        replay is exempt from mid-prefill expiry (it met its admission
        SLO before the scheduler's own eviction)."""
        cfg, params = _setup(seed=1)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=32,
            prefill_chunk=8, enable_prefix_cache=False)
        t = [0.0]
        # the synchronous chain: the eviction below is timed by the
        # tokens a has when b arrives (pipelined, the fence before the
        # eviction would read a's last two and free the slot by itself)
        sched = ServingScheduler(eng, clock=lambda: t[0], overlap=False)
        a = sched.submit(_prompts(cfg, [20], seed=41)[0],
                         max_new_tokens=4, priority=Priority.LOW,
                         deadline_s=1.0)    # admitted well within it
        while len(a.tokens) < 2:
            sched.step()
        b = sched.submit(_prompts(cfg, [4], seed=42)[0],
                         max_new_tokens=2, priority=Priority.HIGH)
        sched.step()                        # evicts a
        assert a.preemptions == 1
        t[0] = 9.0                          # far past a's deadline
        sched.run()                         # a's replay is mid-prefill
        assert sched.deadline_cancels_total == 0
        assert a.done and a.finish_reason == "max_len"
        assert len(a.tokens) == 4 and b.done

    def test_infeasible_preemption_evicts_no_one(self):
        """When even evicting EVERY lower-class victim could not cover
        the admission (equal-class tables pin too much of the pool),
        the scheduler defers it without preempting — no eviction +
        replay paid for an admission that fails anyway."""
        cfg, params = _setup(seed=2)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=2, page_size=8, max_len=32,
            num_pages=1 + 4, enable_prefix_cache=False)
        sched = ServingScheduler(eng)
        peer = sched.submit(_prompts(cfg, [5], seed=23)[0],
                            max_new_tokens=4, priority=Priority.HIGH)
        low = sched.submit(_prompts(cfg, [5], seed=24)[0],
                           max_new_tokens=4, priority=Priority.LOW)
        for _ in range(3):
            sched.step()
        assert peer.slot is not None and low.slot is not None
        # needs 4 pages; the equal-class peer pins 2 of the 4 usable,
        # so even evicting `low` leaves only 2 — infeasible
        big = sched.submit(_prompts(cfg, [20], seed=25)[0],
                           max_new_tokens=8, priority=Priority.HIGH)
        sched.step()
        assert sched.preemptions_total == 0
        assert big.slot is None and low.preemptions == 0
        sched.run()                          # admits once runners retire
        assert big.done and len(big.tokens) == 8
        assert low.done and len(low.tokens) == 4

    def test_queue_wait_measures_latest_enqueue(self):
        """A resumed request's prior RUNNING time is not time-in-queue:
        the histogram observes waits since the latest (re)enqueue."""
        from paddle_tpu import observability as obs
        cfg, params = _setup(seed=7)
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng = ContinuousBatchingEngine(
                params, cfg, max_batch=1, page_size=8, max_len=32,
                enable_prefix_cache=False)
            t = [0.0]
            sched = ServingScheduler(eng, clock=lambda: t[0])
            a = sched.submit(_prompts(cfg, [5], seed=26)[0],
                             max_new_tokens=6, priority=Priority.LOW)
            while len(a.tokens) < 2:
                sched.step()
                t[0] += 10.0                 # a RUNS for tens of seconds
            b = sched.submit(_prompts(cfg, [4], seed=27)[0],
                             max_new_tokens=2, priority=Priority.HIGH)
            while not b.done:
                t[0] += 0.5
                sched.step()
            sched.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert a.preemptions == 1 and a.done
        waits = snap["serving_time_in_queue_seconds"]["values"]
        # a's resume waited only b's short run (a few 0.5s ticks), not
        # the tens of seconds a spent decoding before the preemption
        assert waits["priority=2"]["sum"] < 5.0
        assert waits["priority=2"]["count"] == 2   # admit + resume

    def test_budget_bounds_every_engine_step(self):
        """ACCEPTANCE (end to end): with a configured budget, every
        executed step's debit (decode slots + prefill widths) stays
        under it, and deferred work still completes (no starvation)."""
        cfg, params = _setup(seed=5)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=4, page_size=8, max_len=16,
            enable_prefix_cache=False)
        budget = 16                          # two prefill pages
        # the synchronous chain: ``deferred`` below counts on the LOW
        # rows having three tokens to go when the HIGH one arrives
        # (pipelined, two of them are on the device already)
        sched = ServingScheduler(eng, token_budget=budget, overlap=False)
        reqs = [sched.submit(q, max_new_tokens=4, priority=Priority.LOW)
                for q in _prompts(cfg, [4, 5, 6], seed=10)]
        while not all(r.slot is not None and len(r.tokens) > 0
                      for r in reqs):
            sched.step()
            assert sched.last_plan.scheduled_tokens <= budget
        # a HIGH admission's TWO-page prefill consumes the whole
        # budget, deferring every ready LOW decode to a later step
        reqs.append(sched.submit(_prompts(cfg, [9], seed=16)[0],
                                 max_new_tokens=4,
                                 priority=Priority.HIGH))
        deferred = 0
        while sched.step():
            plan = sched.last_plan
            assert plan.scheduled_tokens <= budget
            assert (len(plan.decode_slots)
                    + sum(c for _, c in plan.prefills)) <= budget
            deferred += plan.deferred_decodes
        assert all(r.done and len(r.tokens) == 4 for r in reqs)
        assert deferred >= 3                 # the budget actually bit

    def test_budgeted_tokens_match_unbudgeted(self):
        """Deferring decodes under a tight budget must not change any
        request's tokens — only WHEN they are produced."""
        cfg, params = _setup(seed=6)
        prompts = _prompts(cfg, [4, 6], seed=11)

        def run(budget):
            eng = ContinuousBatchingEngine(
                params, cfg, max_batch=2, page_size=8, max_len=16,
                enable_prefix_cache=False)
            sched = ServingScheduler(eng, token_budget=budget)
            reqs = [sched.submit(q, max_new_tokens=5) for q in prompts]
            sched.run()
            return [np.asarray(r.tokens) for r in reqs]

        for got, ref in zip(run(8), run(None)):
            np.testing.assert_array_equal(got, ref)

    def test_scheduler_metrics_emitted(self):
        """The scheduler hot-path hooks fire: per-class queue-depth
        gauges, preemption/resume counters, time-in-queue histogram,
        budget-utilization gauge."""
        from paddle_tpu import observability as obs
        cfg, params = _setup(seed=7)
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng = ContinuousBatchingEngine(
                params, cfg, max_batch=1, page_size=8, max_len=32)
            sched = ServingScheduler(eng, token_budget=16)
            a = sched.submit(_prompts(cfg, [5], seed=12)[0],
                             max_new_tokens=6, priority=Priority.LOW)
            while len(a.tokens) < 2:
                sched.step()
            sched.submit(_prompts(cfg, [4], seed=13)[0],
                         max_new_tokens=2, priority=Priority.HIGH)
            sched.submit(_prompts(cfg, [3], seed=14)[0],
                         max_new_tokens=2, deadline_s=0.0)  # lapses
            sched.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert snap["serving_preemptions_total"]["values"][""] == 1
        assert snap["serving_resumes_total"]["values"][""] == 1
        # a queued-request deadline cancel is a CANCELLATION, never an
        # eviction (admissions - evictions derives occupancy)
        assert snap["serving_cancellations_total"]["values"][
            "reason=deadline_exceeded"] == 1
        assert "reason=deadline_exceeded" not in snap[
            "serving_evictions_total"]["values"]
        # admissions count FRESH entries only (a + b, not a's resume,
        # not the cancelled request), so the drained occupancy identity
        # admissions - evictions == 0 holds under preemption churn
        assert snap["serving_admissions_total"]["values"][""] == 2
        assert sum(snap["serving_evictions_total"]["values"]
                   .values()) == 2
        assert snap["serving_resume_replay_tokens_total"][
            "values"][""] > 0
        # one wait observation per admission (2 fresh + 1 resume)
        waits = snap["serving_time_in_queue_seconds"]["values"]
        assert sum(v["count"] for v in waits.values()) == 3
        assert set(waits) == {"priority=0", "priority=2"}
        depths = snap["serving_queue_depth"]["values"]
        assert all(v == 0 for v in depths.values())   # drained
        assert (snap["serving_sched_steps_total"]["values"][""]
                == sched.stats()["sched_steps"])
        util = snap["serving_step_budget_utilization"]["values"][""]
        assert 0.0 <= util <= 1.0


class TestFinishReasons:
    def test_eos_and_max_len_structured(self):
        cfg, params = _setup(seed=8)
        p = _prompts(cfg, [4], seed=14)[0]
        probe = ContinuousBatchingEngine(params, cfg, max_batch=1,
                                         page_size=8, max_len=16)
        r = probe.submit(p, max_new_tokens=4)
        probe.run()
        eos = int(r.tokens[1])              # force a step-2 eos hit
        eng = ContinuousBatchingEngine(params, cfg, max_batch=1,
                                       page_size=8, max_len=16)
        sched = ServingScheduler(eng)
        req = sched.submit(p, max_new_tokens=4, eos_token_id=eos)
        sched.run()
        assert req.finish_reason == "eos" == FinishReason.EOS
        assert len(req.tokens) == 2
        assert r.finish_reason == "max_len" == FinishReason.MAX_LEN

    def test_cancelled_while_queued_is_never_admitted(self):
        """A request cancelled while waiting in the scheduler's queue
        must not be resurrected by admission (which would decode it and
        overwrite the cancellation's finish reason)."""
        cfg, params = _setup(seed=9)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=16,
            enable_prefix_cache=False)
        sched = ServingScheduler(eng)
        a = sched.submit(_prompts(cfg, [4], seed=28)[0],
                         max_new_tokens=3)
        b = sched.submit(_prompts(cfg, [4], seed=29)[0],
                         max_new_tokens=3)   # queues behind a
        sched.step()
        eng.cancel_request(b, "cancelled")
        sched.run()
        assert a.done and a.finish_reason == "max_len"
        assert b.finish_reason == "cancelled" and b.tokens == []

    def test_cancel_preempted_request_finalizes_retirement(self):
        """Cancelling a request that sits EVICTED awaiting resume must
        count as a retirement (it was admitted; its pages already freed
        at preempt time) so admissions - evictions drains to zero — not
        as a never-admitted cancellation."""
        from paddle_tpu import observability as obs
        cfg, params = _setup(seed=9)
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng = ContinuousBatchingEngine(
                params, cfg, max_batch=1, page_size=8, max_len=32,
                enable_prefix_cache=False)
            sched = ServingScheduler(eng)
            a = sched.submit(_prompts(cfg, [5], seed=30)[0],
                             max_new_tokens=6, priority=Priority.LOW)
            while len(a.tokens) < 2:
                sched.step()
            b = sched.submit(_prompts(cfg, [4], seed=31)[0],
                             max_new_tokens=2, priority=Priority.HIGH)
            sched.step()                     # a evicted, awaiting resume
            assert a.finish_reason == "preempted" and a.slot is None
            eng.cancel_request(a, "cancelled")
            sched.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert a.done and a.finish_reason == "cancelled"
        assert b.done
        evi = snap["serving_evictions_total"]["values"]
        assert evi["reason=cancelled"] == 1
        assert "serving_cancellations_total" not in snap
        assert (snap["serving_admissions_total"]["values"][""]
                == sum(evi.values()) == 2)

    def test_cancel_running_request_releases_pages(self):
        cfg, params = _setup(seed=9)
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=1, page_size=8, max_len=16,
            enable_prefix_cache=False)
        sched = ServingScheduler(eng)
        req = sched.submit(_prompts(cfg, [4], seed=15)[0],
                           max_new_tokens=8)
        sched.step()
        assert eng.cache.allocator.num_used > 0
        eng.cancel_request(req, "deadline_exceeded")
        assert req.done
        assert req.finish_reason == "deadline_exceeded"
        assert eng.cache.allocator.num_used == 0
        eng.cancel_request(req)             # idempotent on finished
        assert req.finish_reason == "deadline_exceeded"


class TestStepPlan:
    def test_scheduled_tokens_property(self):
        plan = StepPlan(decode_slots=[0, 2], prefills=[(1, 16)],
                        budget=32)
        assert plan.scheduled_tokens == 18
