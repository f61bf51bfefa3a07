"""The decode pipeline (ISSUE 12, rebuilt in ISSUE 31).

``ServingScheduler.step()`` launches decode step k+1 behind step k, before
k's tokens are read: a row's next input token stays on the device, the
bookkeeping that does not depend on a token's value (lengths, counts, the
``max_len`` finish, the sliding pool's pages, chunk cursors) moves to the
dispatch, and step k-1 is read and committed meanwhile. The pipelined path
must be TOKEN-IDENTICAL to the synchronous chain (``overlap=False``) on
every tier and scenario the serving tower supports:

- fp, int8-KV, int4 and w8/kv8 engines (mixed-priority bursty workload
  with chunked prefill and a preemption, which fences the pipeline);
- a tp=2 sharded engine (8 virtual host devices, conftest);
- preempt→swap→resume through the host tier (async swap-out DMAs fenced at
  commit);
- a windowed expert config (two pools, the counters packed behind the
  tokens) through chunked prefill and page release;
- a prefix hit; ``eos`` in mid-stream with the freed slot re-seated at
  once; a ``max_len`` finish; sampling with a fixed key; a preemption with
  two steps in flight;
- speculative verify, which runs at depth 0 (everything commits before the
  proposer reads the history);
- supervisor crash recovery with faults at the dispatch/commit seams.

Plus the runtime's own contracts: the token budget stays a hard ceiling
under the predicted-state planner, when a token becomes visible, the four
pipeline counters, the rid/seat guard (steady since the host's arrays go
to the device as copies: ROADMAP D10), the benchmark's own start
sequence, and the ``check_sync_points`` lint.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.predictor import InFlightStep
from paddle_tpu.distributed.mesh import serving_mesh
from paddle_tpu.serving import (EngineSupervisor, FaultInjector,
                                Priority, ServingScheduler)

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(0), _CFG)
_REF = {}      # scenario key -> synchronous reference outputs


def _prompts(lens, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, _CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _engine(overlap=False, params=_PARAMS, cfg=_CFG, **kw):
    """``overlap`` on the ENGINE is the host tier's non-blocking swap-out
    alone; how a step runs is the scheduler's ``overlap``."""
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 48)
    if kw.get("mesh") == "tp2":
        kw["mesh"] = serving_mesh(2)
    return ContinuousBatchingEngine(params, cfg, overlap=overlap, **kw)


def _sched(eng, pipelined, budget=20):
    return ServingScheduler(eng, token_budget=budget,
                            overlap=None if pipelined else False)


def _decodes_in_flight(eng):
    return sum(isinstance(h, InFlightStep) for _, h in eng._inflight)


def _run_workload(pipelined, *, budget=20, prompts=None, max_new=5,
                  burst=True, eos=None, **engine_kw):
    """Mixed-priority workload through a scheduler: a wave of LOW/
    NORMAL requests, then (optionally) a HIGH burst that preempts.
    Returns (per-request outputs, scheduler). Prompt lengths are kept
    to two page buckets so every test in this file shares the same
    compiled chunk/decode programs (tier-1 wall-clock discipline)."""
    prompts = prompts if prompts is not None else _prompts(
        (5, 11, 3), seed=3)
    eng = _engine(pipelined, **engine_kw)
    sched = _sched(eng, pipelined, budget)
    reqs = [sched.submit(p, max_new_tokens=max_new, eos_token_id=eos,
                         priority=Priority.LOW if i % 2 else
                         Priority.NORMAL)
            for i, p in enumerate(prompts[:-1])]
    if burst:
        for _ in range(4):
            sched.step()
        reqs.append(sched.submit(prompts[-1], max_new_tokens=max_new,
                                 eos_token_id=eos,
                                 priority=Priority.HIGH))
    else:
        reqs.append(sched.submit(prompts[-1], max_new_tokens=max_new,
                                 eos_token_id=eos))
    sched.run()
    assert all(r.done for r in reqs), \
        [(r.rid, r.finish_reason) for r in reqs]
    eng = sched.engine
    assert not eng.has_inflight() and eng.idle
    return [r.output.tolist() for r in reqs], sched


def _gate_identity(key, **kw):
    """Run the workload synchronous and pipelined; the token streams
    must match request for request (the synchronous reference is kept
    per scenario). Returns both schedulers' stats and the pipelined
    scheduler."""
    if key not in _REF:
        out, sched = _run_workload(False, **kw)
        assert not sched.overlap
        _REF[key] = out, sched.stats()
    ov, sched = _run_workload(True, **kw)
    assert sched.overlap
    assert ov == _REF[key][0], f"pipelined != synchronous for {key}"
    return _REF[key][1], sched.stats(), sched


def _window_moe():
    """The windowed expert config of tests/test_window_moe.py."""
    import test_window_moe as wm
    c, cfg = wm.small_config(), wm.program(wm.small_config())
    return wm.arch.weights(jax.random.key(7), c, dtype=jnp.float32), cfg


# every case: (workload and engine keywords, check(sync stats, pipelined
# stats, pipelined scheduler))
def _check_pipelined(sync, pipe, sched):
    assert sync["decode_launches_pipelined_total"] == 0
    assert pipe["decode_launches_pipelined_total"] > 0
    # the HIGH burst preempts a seated request: that fences
    assert pipe["pipeline_fences_total"] >= 1
    assert sched.preemptions_total >= 1


def _check_swap(sync, pipe, sched):
    for s in (sync, pipe):
        assert s["preemptions_total"] > 0
        assert s["swap_ins_total"] > 0
    assert pipe["pipeline_fences_total"] >= 1


def _check_depth0(sync, pipe, sched):
    # the proposer reads the committed history: nothing is launched
    # behind a program whose tokens were not read
    assert sched.engine.pipeline_depth() == 0
    assert pipe["decode_launches_pipelined_total"] == 0
    assert pipe["decode_launches_total"] == sync["decode_launches_total"]


def _check_window(sync, pipe, sched):
    # the same pages, released a step earlier
    assert pipe["window_pages_released_total"] \
        == sync["window_pages_released_total"] > 0
    assert pipe["moe_layer_steps_total"] > 0
    assert pipe["decode_launches_pipelined_total"] > 0


def _check_prefix(sync, pipe, sched):
    assert pipe["prefix_hit_tokens_total"] >= 16
    assert pipe["decode_launches_pipelined_total"] > 0


_SHARED = _prompts((16,), seed=21)[0]
_MOTIF = np.asarray([7, 11, 13], np.int32)
_CASES = {
    "fp": ({}, _check_pipelined),
    "int8_kv": (dict(kv_cache_dtype="int8"), _check_pipelined),
    "int4": (dict(weight_bits=4), _check_pipelined),
    "w8kv8": (dict(weight_bits=8, kv_cache_dtype="int8"),
              _check_pipelined),
    # the pipelined tp=2 run is compared against a SINGLE-CHIP
    # synchronous reference: tp decode is gated bit-identical to
    # single-chip (tests/test_tp_serving.py), so this gates
    # pipelined-tp2 == sync-tp2 without a second sharded run
    "tp2": (dict(mesh="tp2"), _check_pipelined),
    "swap_resume": (dict(host_tier=True, max_new=8,
                         prompts=_prompts((11, 12, 5), seed=6)),
                    _check_swap),
    "spec_verify": (dict(prompts=[np.tile(_MOTIF, 5)[:14]] * 3
                         + [np.tile(_MOTIF, 4)[:9]],
                         budget=24, burst=False, spec_k=2),
                    _check_depth0),
    "window_moe": (dict(prompts=[
        np.random.default_rng(4).integers(3, 256, (n,)).astype(np.int32)
        for n in (40, 23, 9)], max_new=24, burst=False, budget=40,
        max_len=128, prefill_chunk=16), _check_window),
    "prefix_hit": (dict(prompts=[
        np.concatenate([_SHARED, t]) for t in _prompts((3, 5, 4, 6),
                                                       seed=22)],
        burst=False, budget=40), _check_prefix),
}


@pytest.mark.parametrize("case", [
    pytest.param(c, marks=pytest.mark.slow) if c == "w8kv8" else c
    # fp/int8/int4 stay the tier-1 representatives of the tier sweep
    for c in _CASES])
def test_pipelined_equals_synchronous(case):
    """ACCEPTANCE: the pipelined path serves the synchronous chain's
    tokens, request for request."""
    kw, check = _CASES[case]
    kw = dict(kw)
    if case == "window_moe":
        kw["params"], kw["cfg"] = _window_moe()
    key = "fp" if case == "tp2" else case
    if case == "tp2":
        _gate_identity("fp")                # the single-chip reference
        ov, sched = _run_workload(True, **kw)
        assert ov == _REF["fp"][0]
        check(_REF["fp"][1], sched.stats(), sched)
        return
    check(*_gate_identity(key, **kw))


class TestPipelineScenarios:
    """The places where the pipelined step differs from the chain."""

    def test_eos_mid_stream_slot_reseated_at_once(self):
        """A row's step-k token is ``eos``: step k+1 was launched for
        it already. That row is computed and dropped (counted), its
        stray KV row lands in a page the next owner cannot read, and
        the slot's next occupant — seated in the same call, over a
        prefix hit on the retired row's pages — serves what it serves
        alone."""
        pa = np.concatenate([_SHARED, _prompts((3,), seed=31)[0]])
        pb = np.concatenate([pa, _prompts((4,), seed=32)[0]])
        free = _engine(max_batch=1).generate([pa], max_new_tokens=8)[0]
        toks = free[pa.size:].tolist()
        j = next(i for i in range(2, 8) if toks[i] not in toks[:i])
        alone = _engine(max_batch=1).generate([pb], max_new_tokens=6)[0]

        outs = {}
        for pipelined in (False, True):
            eng = _engine(max_batch=1)
            sched = _sched(eng, pipelined)
            a = sched.submit(pa, max_new_tokens=8, eos_token_id=toks[j])
            b = sched.submit(pb, max_new_tokens=6)
            sched.run()
            assert a.finish_reason == "eos" and b.finish_reason == "max_len"
            assert a.tokens == toks[:j + 1]
            assert np.array_equal(b.output, alone)
            st = sched.stats()
            # b's prompt continues a's: the two full pages and the
            # tail page's three rows came from the trie
            assert st["prefix_hit_tokens_total"] >= pa.size
            outs[pipelined] = st["pipeline_rows_dropped_total"]
        assert outs == {False: 0, True: 1}

    def test_eos_as_first_token(self):
        """The final chunk's own sample is ``eos``: the decode program
        launched behind the chunk is dropped whole."""
        p = _prompts((5,), seed=2)[0]
        first = int(_engine().generate([p], max_new_tokens=1)[0][-1])
        for pipelined in (False, True):
            sched = _sched(_engine(), pipelined)
            r = sched.submit(p, max_new_tokens=6, eos_token_id=first)
            sched.run()
            assert r.tokens == [first] and r.finish_reason == "eos"
            assert (sched.stats()["pipeline_rows_dropped_total"]
                    == int(pipelined))

    def test_max_len_finish_launches_no_row_past_its_count(self):
        """The count is known at dispatch: every request costs exactly
        ``max_new_tokens - 1`` decode rows (its first token comes from
        the chunk), pipelined as synchronous, and nothing is dropped."""
        prompts, new = _prompts((5, 11, 3, 7), seed=3), 5
        rows = {}
        for pipelined in (False, True):
            eng = _engine()
            launched = []
            dispatch = eng.decode_dispatch
            eng.decode_dispatch = lambda m: (
                launched.append(int(np.sum(m))), dispatch(m))[1]
            sched = _sched(eng, pipelined)
            reqs = [sched.submit(p, max_new_tokens=new) for p in prompts]
            sched.run()
            assert all(r.finish_reason == "max_len"
                       and len(r.tokens) == new for r in reqs)
            assert sched.stats()["pipeline_rows_dropped_total"] == 0
            rows[pipelined] = sum(launched)
        assert rows[True] == rows[False] == len(prompts) * (new - 1)

    def test_sampled_with_a_fixed_key(self):
        """temperature > 0: the key is split at dispatch, chunk and
        decode programs in the same order on both paths, so a fixed key
        samples the same tokens (one wave: a slot freed by a finish is
        refilled a step later when pipelined, which would reorder the
        splits of a second wave)."""
        kw = dict(temperature=0.8, key=jax.random.key(5), burst=False,
                  prompts=_prompts((5, 11), seed=3), max_new=8)
        sync, _ = _run_workload(False, **kw)
        pipe, sched = _run_workload(True, **kw)
        assert pipe == sync
        assert sched.stats()["decode_launches_pipelined_total"] > 0
        greedy, _ = _run_workload(False, **dict(kw, temperature=0.0))
        assert greedy != sync               # it did sample

    def test_preemption_with_two_steps_in_flight(self):
        """A HIGH arrival while the victim has two decode steps on the
        device: both are read before it is evicted (one fence), and it
        resumes to the synchronous chain's tokens."""
        prompts = _prompts((5, 11, 3), seed=3)
        ref = [_engine().generate([p], max_new_tokens=10)[0].tolist()
               for p in prompts]
        eng = _engine()
        sched = _sched(eng, True)
        low = [sched.submit(p, max_new_tokens=10, priority=Priority.LOW)
               for p in prompts[:2]]
        while _decodes_in_flight(eng) < 2:
            sched.step()
        seen = [len(r.tokens) for r in low]
        high = sched.submit(prompts[2], max_new_tokens=10,
                            priority=Priority.HIGH)
        sched.step()
        assert sched.preemptions_total == 1
        assert sched.stats()["pipeline_fences_total"] == 1
        victim = next(r for r in low if r.finish_reason == "preempted")
        # the fence read both steps before the eviction
        assert len(victim.tokens) == seen[low.index(victim)] + 2
        sched.run()
        assert [r.output.tolist() for r in low + [high]] == ref


class TestOverlapRecovery:
    """Faults at the dispatch/commit seams recover token-identically
    (the in-flight steps' results are lost with the poisoned engine;
    the journal replay recomputes them)."""

    @staticmethod
    def _run_sup(arm_site=None, nth=3):
        def factory():
            return _engine(True)
        sup = EngineSupervisor(factory, token_budget=20, backoff_s=0.0,
                               sleep=lambda s: None)
        assert sup.scheduler.overlap        # pipelined by default
        inj = FaultInjector(seed=0)
        if arm_site:
            inj.arm(arm_site, "raise", nth=nth)
        prompts = _prompts((5, 11, 3), seed=3)
        reqs = []
        with inj:
            for p in prompts:
                reqs.append(sup.submit(p, max_new_tokens=5))
            sup.run()
        assert all(r.done for r in reqs)
        return [r.output.tolist() for r in reqs], sup

    def test_fault_at_dispatch_and_commit(self):
        """The synchronous path's coverage of these sites lives in
        tests/test_resilience.py::TestRecoveryParity (parametrized over
        SITES); this is the PIPELINED loop, where the commit-seam fault
        strikes with up to two steps in flight — the journal held only
        COMMITTED tokens, so identity is the write-ahead-precedes-commit
        contract."""
        ref, sup0 = self._run_sup(None)
        assert sup0.recoveries == 0
        for site in ("dispatch", "commit"):
            out, sup = self._run_sup(site)
            assert sup.recoveries >= 1, f"{site}: nothing recovered"
            assert out == ref, f"{site}: recovery diverged"


class TestOverlapContracts:
    def test_budget_hard_ceiling(self):
        """Every pipelined step's (planned + reserved) tokens stay
        under the configured budget: the plan is drawn against the
        predicted state and launched as drawn."""
        budget = 16
        eng = _engine(True, max_batch=2, host_tier=True)
        sched = ServingScheduler(eng, token_budget=budget)
        assert sched.overlap
        prompts = _prompts((11, 14, 5, 3), seed=9)
        reqs = [sched.submit(p, max_new_tokens=6,
                             priority=Priority.LOW) for p in prompts[:2]]
        steps = 0
        while True:
            more = sched.step()
            plan = sched.last_plan
            assert (plan.scheduled_tokens + plan.reserved_tokens
                    <= budget), vars(plan)
            steps += 1
            if steps == 4:
                reqs += [sched.submit(p, max_new_tokens=4,
                                      priority=Priority.HIGH)
                         for p in prompts[2:]]
            if not more:
                break
            assert steps < 500
        assert all(r.done for r in reqs)

    def test_commit_rid_guard(self):
        """A slot that changes hands under TWO decode programs in
        flight must receive neither token; the victim re-decodes them
        on resume, identically. (``preempt_request`` reads what is in
        flight first; its unfenced half stands for any way a slot can
        change hands under a launched program.)"""
        eng = _engine(max_batch=1)
        pa, pb = _prompts((5, 7), seed=5)
        ref = eng.generate([pa], max_new_tokens=4)[0]

        eng = _engine(max_batch=1)
        a = eng.create_request(pa, max_new_tokens=4)
        assert eng.admit_request(a)
        while eng.pending_prefills():
            eng.prefill_step()
        assert eng.decode_dispatch(eng.ready_mask()) is not None
        assert eng.decode_dispatch(eng.ready_mask()) is not None
        assert _decodes_in_flight(eng) == 2 and len(a.tokens) == 1
        eng._evict_seated(a)            # slot cleared mid-flight
        b = eng.create_request(pb, max_new_tokens=4)
        assert eng.admit_request(b)     # new occupant of slot 0
        len_before = int(eng.cache.lengths[0])
        eng.commit_inflight()
        assert b.tokens == [] and len(a.tokens) == 1    # both dropped
        assert eng.stats()["pipeline_rows_dropped_total"] == 2
        assert int(eng.cache.lengths[0]) == len_before
        # the victim resumes and finishes identically regardless
        eng.cancel_request(b)           # free the only slot for the resume
        assert eng.admit_request(a)
        eng.run()
        assert a.tokens == ref[pa.size:].tolist()

    def test_commit_seat_guard_same_request(self):
        """The SAME request evicted (swap) and re-seated into its own
        slot between dispatch and commit: the rid is unchanged, so only
        the seat-generation snapshot can reject the stale token — its
        KV went to the old seating's freed pages. The dropped token is
        re-decoded after the swap-in, identically."""
        p = _prompts((7,), seed=8)[0]
        ref = _engine(max_batch=1).generate([p], max_new_tokens=4)[0]
        eng = _engine(max_batch=1, host_tier=True)
        a = eng.create_request(p, max_new_tokens=4)
        assert eng.admit_request(a)
        while eng.pending_prefills():
            eng.prefill_step()
        assert eng.decode_dispatch(eng.ready_mask()) is not None
        eng._evict_seated(a)            # swap-out mid-flight
        assert eng.admit_request(a)     # swap-in: SAME rid, same slot
        ntok, len0 = len(a.tokens), int(eng.cache.lengths[0])
        eng.commit_inflight()
        assert len(a.tokens) == ntok    # stale seating's token dropped
        assert int(eng.cache.lengths[0]) == len0
        eng.run()
        assert np.array_equal(a.output, ref)

    def test_preempt_request_reads_what_is_in_flight_first(self):
        """The public preemption fences: the victim keeps every token
        the device had computed for it."""
        eng = _engine(max_batch=1)
        a = eng.create_request(_prompts((5,), seed=5)[0], max_new_tokens=6)
        assert eng.admit_request(a)
        while eng.pending_prefills():
            eng.prefill_step()
        eng.decode_dispatch(eng.ready_mask())
        eng.decode_dispatch(eng.ready_mask())
        eng.preempt_request(a)
        assert len(a.tokens) == 3 and not eng.has_inflight()
        st = eng.stats()
        assert st["pipeline_fences_total"] == 1
        assert st["pipeline_rows_dropped_total"] == 0

    def test_synchronous_compositions_commit_what_is_in_flight(self):
        """``decode_step`` / ``prefill_step`` on an engine with work in
        flight commit it first, in launch order."""
        eng = _engine()
        pa, pb = _prompts((5, 11), seed=3)
        ref = [_engine().generate([p], max_new_tokens=4)[0].tolist()
               for p in (pa, pb)]
        a = eng.create_request(pa, max_new_tokens=4)
        b = eng.create_request(pb, max_new_tokens=4)
        assert eng.admit_request(a)
        eng.prefill_dispatch(a.slot)        # a's only chunk, in flight
        assert eng.admit_request(b)
        assert eng.pending_prefills().keys() == {b.slot}
        eng.decode_dispatch(eng.ready_mask())   # behind a's chunk
        assert a.tokens == []
        eng.prefill_step(b.slot)            # commits both, then b's chunk
        assert len(a.tokens) == 2 and len(b.tokens) == 1
        eng.decode_dispatch(eng.ready_mask())
        assert eng.decode_step(eng.ready_mask()) == 2
        assert len(a.tokens) == 4 and a.done and len(b.tokens) == 3
        eng.run()
        assert [a.output.tolist(), b.output.tolist()] == ref

    def test_benchmark_start_sequence(self):
        """chipbench's staggered start (drivers/serve_open_loop.py,
        serve_arch.py): one ``sched.step()`` that admits the block and
        leaves chunks in flight, ``eng.prefill_step()`` until nothing is
        pending, then steps — on the default scheduler."""
        prompts = _prompts((21, 11, 19, 7), seed=12)
        ref = [_engine().generate([p], max_new_tokens=6)[0].tolist()
               for p in prompts]
        eng = _engine(max_batch=4, prefill_chunk=8)
        sched = ServingScheduler(eng)
        hs = [sched.submit(p, max_new_tokens=6) for p in prompts]
        sched.step()                        # admits the block
        assert eng.has_inflight()
        while eng.pending_prefills():       # prefill only: no decode
            eng.prefill_step()
        assert not eng.has_inflight()
        assert [len(h.tokens) for h in hs] == [1, 1, 1, 1]
        while sched.step():
            pass
        assert [h.output.tolist() for h in hs] == ref
        st = sched.stats()
        assert st["decode_launches_pipelined_total"] \
            >= st["decode_launches_total"] - 2
        assert st["pipeline_fences_total"] == 0

    def test_when_a_token_becomes_visible(self):
        """A token is on its handle at the start of the second call
        after the one that launched it; synchronous, when the call that
        computed it returns; ``flush()`` shows everything computed."""
        p = _prompts((5,), seed=2)[0]
        sync = _sched(_engine(), False)
        r = sync.submit(p, max_new_tokens=6)
        seen = []
        while sync.step():
            seen.append(len(r.tokens))
        assert seen[:3] == [1, 2, 3]

        eng = _engine()
        sched = _sched(eng, True)
        r = sched.submit(p, max_new_tokens=6)
        seen = []
        for _ in range(4):
            sched.step()
            seen.append(len(r.tokens))
        # call 1 launched the chunk, call 2 a decode step behind it,
        # call 3 read the chunk's token, call 4 the first decode's
        assert seen == [0, 0, 1, 2]
        assert _decodes_in_flight(eng) == 2
        assert sched.flush() == 2 and len(r.tokens) == 4
        assert not eng.has_inflight()
        sched.run()
        assert r.done and len(r.tokens) == 6

    def test_pipeline_counters(self):
        """The four counters are in ``stats()`` from the start, and a
        plain run launches nearly every decode step behind another."""
        eng = _engine()
        sched = ServingScheduler(eng)
        names = ("decode_launches_total", "decode_launches_pipelined_total",
                 "pipeline_rows_dropped_total", "pipeline_fences_total")
        assert [sched.stats()[n] for n in names] == [0, 0, 0, 0]
        for p in _prompts((5, 11), seed=3):
            sched.submit(p, max_new_tokens=12)
        sched.run()
        st = sched.stats()
        assert st["decode_launches_total"] >= 11
        # all but the first went behind a program not yet read
        assert st["decode_launches_pipelined_total"] \
            == st["decode_launches_total"] - 1
        assert st["pipeline_rows_dropped_total"] == 0
        assert st["pipeline_fences_total"] == 0
        assert st["overlap"] is True

    def test_constrained_rows_run_at_depth_zero(self):
        """A grammar's next mask follows from the token: while a
        constrained request is seated the engine commits before every
        launch, and runs ahead again once it has left."""
        from paddle_tpu.serving.constraints import dfa_from_sequences
        seqs = [[5, 9, 4, 8], [5, 7, 6, 3]]
        dfa = dfa_from_sequences(seqs, _CFG.vocab_size)
        outs = []
        for pipelined in (False, True):
            eng = _engine(constraints=True, eos_token_id=2)
            sched = _sched(eng, pipelined)
            c = sched.submit(_prompts((5,), seed=2)[0], max_new_tokens=4,
                             constraint=dfa)
            free = sched.submit(_prompts((11,), seed=3)[0],
                                max_new_tokens=12)
            sched.run()
            emitted = [t for t in c.tokens if t != 2]
            assert any(emitted == s[:len(emitted)] for s in seqs)
            outs.append((c.tokens, free.tokens))
        assert outs[0] == outs[1]
        st = sched.stats()
        assert st["pipeline_fences_total"] >= 3
        assert 0 < st["decode_launches_pipelined_total"] \
            < st["decode_launches_total"] - 1

    def test_host_overhead_fraction_emitted_and_lower(self):
        """The scoreboard: the gauge is emitted, and the pipelined
        path's exposed-host fraction is lower than the chain's on the
        same workload (admission, planning, launching and the commit
        run under a program in flight)."""
        from paddle_tpu import observability as obs
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            prompts = _prompts((9, 12, 7, 5), seed=13)
            _, s_sync = _run_workload(False, prompts=prompts,
                                      max_new=8)
            snap = obs.REGISTRY.to_json()
            assert "serving_host_overhead_fraction" in snap
            assert "serving_sched_step_ms" in snap
            _, s_ov = _run_workload(True, prompts=prompts, max_new=8)
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert s_sync.host_frac_ema is not None
        assert s_ov.host_frac_ema is not None
        assert s_ov.host_frac_ema < s_sync.host_frac_ema, (
            s_sync.host_frac_ema, s_ov.host_frac_ema)
        assert s_ov.stats()["overlap"] is True
        assert "host_overhead_fraction" in s_ov.stats()

    def test_run_fences_instead_of_busy_spin(self):
        """A step that plans zero tokens commits what is in flight; one
        that also commits nothing makes ``run()`` fence or yield instead
        of re-planning empty steps. Forced here by stubbing the planner
        empty for a few ticks while a request is mid-decode."""
        from paddle_tpu.serving.policy import StepPlan
        from paddle_tpu import observability as obs
        eng = _engine(True)
        sched = ServingScheduler(eng, token_budget=20)
        req = sched.submit(_prompts((5,), seed=2)[0], max_new_tokens=6)
        sched.step()                    # admit + first dispatch
        real_plan = sched._plan
        holes = {"n": 3}

        def empty_plan(reserved=0):
            if holes["n"] > 0:
                holes["n"] -= 1
                return StepPlan(budget=sched.planner.token_budget)
            return real_plan(reserved)

        sched._plan = empty_plan
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            sched.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert req.done
        assert sched.idle_fences_total >= 1
        assert "serving_sched_idle_steps_total" in snap

    def test_async_swap_pending_visibility(self):
        """A non-blocking swap-out is observable (has_swapped) before
        the fence, and fence_swaps materializes it into the store."""
        eng = _engine(True, host_tier=True, max_batch=1)
        a = eng.create_request(_prompts((7,), seed=4)[0],
                               max_new_tokens=6)
        assert eng.admit_request(a)
        while eng.pending_prefills():
            eng.prefill_step()
        eng.decode_step(eng.ready_mask())
        eng.preempt_request(a)          # overlap engine: async swap-out
        cache = eng.cache
        assert cache.has_swapped(a.rid)
        assert cache.fence_swaps() == 1
        assert cache.fence_swaps() == 0
        assert cache.has_swapped(a.rid)
        assert cache.swap_outs_total == 1

    def test_sync_points_lint(self):
        """The check_sync_points rule passes on the repo and catches a
        planted violation."""
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "tools"))
        try:
            import check_instrumentation as ci
        finally:
            sys.path.pop(0)
        assert ci.check_sync_points(root) == []
        body = ci._function_bodies(
            "class X:\n"
            "    def decode_dispatch(self):\n"
            "        x = np.asarray(nxt)\n"
            "    def other(self):\n"
            "        y = np.asarray(nxt)\n",
            ("decode_dispatch",))
        assert "np.asarray" in body and "y = " not in body
