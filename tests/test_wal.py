"""Crash-durable serving plane (ISSUE 15): on-disk WAL, incremental
checkpoints, cold-restart recovery.

Gates:
- WAL unit behavior: CRC framing, segment rotation, checkpoint
  compaction, torn-tail truncation, corrupt-frame quarantine, stale
  checkpoints never installed.
- ``EngineSupervisor.recover_from_disk``: whole-process death (the
  supervisor object is ABANDONED, never drained) recovers every live
  session TOKEN-IDENTICAL to uninterrupted decode — fp, int8-KV and
  tp=2, including swapped-out, adapter-pinned and grammar-constrained
  sessions.
- The HEADLINE crash-point sweep (tools/chaos_soak.run_crash_sweep):
  simulated ``kill -9`` after EVERY engine fault site — including the
  three new WAL sites — followed by disk recovery, with zero
  lost/duplicated requests and balanced allocators.
- Cluster cold restart: per-replica journal dirs recover the whole
  cluster after whole-process death.
- HostPageStore ``max_disk_bytes`` LRU-by-mtime pruning (satellite).
"""
import json
import os
import zlib

import numpy as np
import jax
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.serving import (EngineSupervisor, HostPageStore,
                                ServingCluster, WriteAheadLog,
                                recover_state)
from paddle_tpu.serving.resilience import ENGINE_SITES
from paddle_tpu.serving.constraints import (ConstraintState, TokenDFA,
                                            dfa_from_sequences)
from paddle_tpu.serving.wal import (_HDR, MAGIC, WalTorn,
                                    scan_segments)
from tools import chaos_soak as _SOAK

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(0), _CFG)

_SUP_KW = dict(backoff_s=0.0, sleep=lambda s: None,
               wal_kw=dict(group_interval_s=0.0))


def _factory(kv=None, **kw):
    def f():
        return ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=2, page_size=8, max_len=48,
            prefill_chunk=8, kv_cache_dtype=kv, **kw)
    return f


def _prompts(lens, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, _CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _refs(factory, jobs):
    eng = factory()
    out = []
    for p, m in jobs:
        r = eng.submit(p, max_new_tokens=m)
        eng.run()
        out.append(np.asarray(r.output))
    return out


class TestWalUnit:
    def test_frame_roundtrip_and_reopen(self, tmp_path):
        """Records survive close/reopen; a reopened log continues the
        lsn sequence in a FRESH segment (two generations never
        interleave frames in one file)."""
        d = str(tmp_path)
        w = WriteAheadLog(d, group_interval_s=0.0)
        l1 = w.append("submit", {"rid": 1, "tokens": []})
        l2 = w.append("step", {"rid": 1, "toks": [7, 8]})
        w.commit(force=True)
        w.close()
        w2 = WriteAheadLog(d)
        assert w2.lsn == l2 == l1 + 1
        w2.append("finish", {"rid": 1, "reason": "eos"})
        w2.commit(force=True)
        w2.close()
        recs, report = scan_segments(d, repair=False)
        assert [r["kind"] for r in recs] == ["submit", "step",
                                             "finish"]
        assert [r["lsn"] for r in recs] == [1, 2, 3]
        assert report["torn_tail_truncated"] == 0
        assert len([f for f in os.listdir(d)
                    if f.startswith("wal-")]) == 2

    def test_segment_rotation_and_checkpoint_pruning(self, tmp_path):
        """Small segments rotate; a checkpoint prunes every fully
        covered segment and the replay equals checkpoint + suffix."""
        d = str(tmp_path)
        w = WriteAheadLog(d, segment_bytes=256, group_interval_s=0.0)
        for i in range(20):
            w.append("submit", {"rid": i, "prompt": [3] * 10,
                                "max_new_tokens": 2, "tokens": [],
                                "admitted": False})
        segs_before = [f for f in os.listdir(d) if f.startswith("wal-")]
        assert len(segs_before) > 2
        w.checkpoint({"sessions": [{"rid": 99, "prompt": [4],
                                    "max_new_tokens": 1,
                                    "tokens": [5], "admitted": True}],
                      "next_rid": 100})
        segs_after = [f for f in os.listdir(d) if f.startswith("wal-")]
        assert len(segs_after) < len(segs_before)
        w.append("submit", {"rid": 100, "prompt": [6],
                            "max_new_tokens": 1, "tokens": [],
                            "admitted": False})
        w.commit(force=True)
        w.close()
        state = recover_state(d)
        # sessions = checkpoint snapshot + the post-checkpoint suffix;
        # pre-checkpoint records are compacted away
        assert 99 in state["sessions"] and 100 in state["sessions"]
        assert state["sessions"][99]["tokens"] == [5]
        assert state["next_rid"] >= 101
        assert state["report"]["ckpt_lsn"] == 20

    def test_torn_tail_truncated_at_last_valid_frame(self, tmp_path):
        """Mid-frame truncation (process death mid-write): recovery
        keeps every complete frame, truncates the file at the tear,
        and counts it."""
        d = str(tmp_path)
        w = WriteAheadLog(d, group_interval_s=0.0)
        for i in range(4):
            w.append("submit", {"rid": i, "tokens": []})
        w.commit(force=True)
        w.close()
        seg = os.path.join(d, sorted(
            f for f in os.listdir(d) if f.startswith("wal-"))[0])
        size = os.path.getsize(seg)
        with open(seg, "r+b") as f:
            f.truncate(size - 7)            # mid-frame tear
        state = recover_state(d)
        assert sorted(state["sessions"]) == [0, 1, 2]
        assert state["report"]["torn_tail_truncated"] == 1
        # the file is REPAIRED: a fresh scan sees a clean log
        recs, rep2 = scan_segments(d, repair=False)
        assert len(recs) == 3 and rep2["torn_tail_truncated"] == 0

    def test_bitflip_quarantines_suffix(self, tmp_path):
        """A corrupt frame BODY (bit-flip, CRC mismatch) stops replay
        at the last valid frame — records past a hole are never
        installed — and later whole segments quarantine, counted."""
        d = str(tmp_path)
        w = WriteAheadLog(d, segment_bytes=128, group_interval_s=0.0)
        for i in range(10):
            w.append("submit", {"rid": i, "tokens": []})
        w.commit(force=True)
        w.close()
        segs = sorted(f for f in os.listdir(d) if f.startswith("wal-"))
        assert len(segs) >= 3
        target = os.path.join(d, segs[1])
        data = bytearray(open(target, "rb").read())
        data[_HDR.size + 2] ^= 0xFF         # flip a payload byte
        open(target, "wb").write(bytes(data))
        state = recover_state(d)
        assert state["report"]["corrupt_quarantined"] >= 1
        first_seg_rids = [r["rid"] for r in scan_segments(
            d, repair=False)[0]]
        # only the prefix before the corruption survives
        assert sorted(state["sessions"]) == sorted(first_seg_rids)
        assert any(f.endswith(".quarantined") for f in os.listdir(d))

    def test_stale_checkpoint_never_installed(self, tmp_path):
        """A checkpoint claiming an lsn the log never reached (foreign
        or stale artifact next to a regressed log) quarantines —
        recovery falls back to pure log replay instead of installing
        state the log cannot corroborate."""
        d = str(tmp_path)
        w = WriteAheadLog(d, group_interval_s=0.0)
        for i in range(3):
            w.append("submit", {"rid": i, "tokens": []})
        w.commit(force=True)
        w.close()
        # fabricate a checkpoint from 'the future'
        meta = {"sessions": [{"rid": 77, "prompt": [4],
                              "max_new_tokens": 1, "tokens": [9],
                              "admitted": True}],
                "next_rid": 78, "wal_lsn": 999, "checksums": {}}
        fn = os.path.join(d, "ckpt-0000000000000999.npz")
        with open(fn, "wb") as f:
            np.savez(f, meta=np.frombuffer(
                json.dumps(meta).encode(), np.uint8))
        state = recover_state(d)
        assert 77 not in state["sessions"]
        assert sorted(state["sessions"]) == [0, 1, 2]
        assert state["report"]["ckpt_quarantined"] == 1
        assert not os.path.exists(fn)       # renamed .quarantined

    def test_corrupt_checkpoint_falls_back(self, tmp_path):
        """A torn checkpoint file quarantines and recovery proceeds
        from the log (or an older checkpoint) — never a crash, never
        corrupt state."""
        d = str(tmp_path)
        w = WriteAheadLog(d, group_interval_s=0.0)
        for i in range(2):
            w.append("submit", {"rid": i, "tokens": []})
        w.checkpoint({"sessions": [], "next_rid": 2})
        ck = [f for f in os.listdir(d) if f.startswith("ckpt-")][0]
        full = os.path.join(d, ck)
        data = open(full, "rb").read()
        open(full, "wb").write(data[:len(data) // 2])   # torn write
        w.append("submit", {"rid": 5, "tokens": []})
        w.commit(force=True)
        w.close()
        state = recover_state(d)
        assert state["report"]["ckpt_quarantined"] == 1
        assert sorted(state["sessions"]) == [0, 1, 5]

    def test_tamper_latches_log_dead(self, tmp_path):
        """The torn-write tamper writes half a frame and latches the
        log: further appends raise (a 'process' must not keep writing
        after its own simulated death), and recovery truncates the
        tear."""
        from paddle_tpu.serving import FaultInjector, InjectedFault
        d = str(tmp_path)
        w = WriteAheadLog(d, group_interval_s=0.0)
        w.append("submit", {"rid": 0, "tokens": []})
        inj = FaultInjector(seed=0)
        inj.arm_tamper("wal_append", nth=1)
        with inj:
            with pytest.raises(InjectedFault):
                w.append("step", {"rid": 0, "toks": [4]})
        with pytest.raises(WalTorn):
            w.append("step", {"rid": 0, "toks": [5]})
        state = recover_state(d)
        assert sorted(state["sessions"]) == [0]
        assert state["sessions"][0]["tokens"] == []
        assert state["report"]["torn_tail_truncated"] == 1

    def test_fsync_policy_validated(self, tmp_path):
        with pytest.raises(ValueError, match="fsync"):
            WriteAheadLog(str(tmp_path), fsync="sometimes")


class TestConstraintSerialization:
    def test_dfa_record_roundtrip(self):
        dfa = dfa_from_sequences([[4, 5, 6], [4, 7]], 32)
        rec = dfa.to_record()
        json.dumps(rec)                     # JSON-able, by contract
        back = TokenDFA.from_record(rec)
        np.testing.assert_array_equal(back.next, dfa.next)
        np.testing.assert_array_equal(back.accepting, dfa.accepting)
        assert back.start == dfa.start

    def test_constraint_state_roundtrip_mid_grammar(self):
        dfa = dfa_from_sequences([[4, 5, 6]], 32)
        st = ConstraintState(dfa, eos_token_id=2)
        st.mask(32)
        st.advance(4)
        rec = st.to_record()
        back = ConstraintState.from_record(rec)
        assert back.state == st.state and not back.finished
        # the restored state admits exactly what the live one does
        np.testing.assert_array_equal(back.mask(32), st.mask(32))
        back.advance(5)
        back.advance(6)
        assert back.dfa.accepting[back.state]


class TestRecoverFromDisk:
    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_cold_restart_token_identity(self, kv, tmp_path):
        """Kill -9 mid-decode (supervisor ABANDONED), recover from the
        journal dir alone: every session finishes token-identical to
        uninterrupted decode, fp and int8-KV."""
        factory = _factory(kv)
        jobs = list(zip(_prompts([12, 5, 20], seed=1), [5, 6, 4]))
        refs = _refs(factory, jobs)
        wd = str(tmp_path / "j")
        sup = EngineSupervisor(factory, wal_dir=wd, checkpoint_every=4,
                               **_SUP_KW)
        reqs = [sup.submit(p, max_new_tokens=m) for p, m in jobs]
        for _ in range(5):
            sup.step()
        del sup                             # kill -9: no drain, no sync
        sup2 = EngineSupervisor.recover_from_disk(factory, wd,
                                                  **_SUP_KW)
        assert sorted(sup2.restored) == [r.rid for r in reqs]
        sup2.run()
        for req, ref in zip(reqs, refs):
            out = sup2.restored[req.rid]
            assert out.finish_reason in ("eos", "max_len")
            np.testing.assert_array_equal(out.output, ref)
        # repeated crashes recover repeatedly: the recovered supervisor
        # keeps journaling to the same directory
        assert sup2.wal.lsn > 0

    def test_geometry_mismatch_rejected(self, tmp_path):
        wd = str(tmp_path / "j")
        sup = EngineSupervisor(_factory(), wal_dir=wd, **_SUP_KW)
        sup.submit(_prompts([6])[0], max_new_tokens=2)
        sup.step()
        del sup
        def other():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=2, page_size=16, max_len=48)
        with pytest.raises(ValueError, match="page_size"):
            EngineSupervisor.recover_from_disk(other, wd, **_SUP_KW)

    def test_swapped_session_recovers_by_replay(self, tmp_path):
        """A session swapped out to host RAM at crash time: the
        payload died with the process, so cold recovery falls back to
        the gated replay resume — token-identical, counted."""
        from paddle_tpu.serving import Priority
        factory = _factory(host_tier=True)
        jobs = list(zip(_prompts([10, 8], seed=3), [10, 10]))
        refs = _refs(factory, jobs)
        wd = str(tmp_path / "j")
        sup = EngineSupervisor(factory, wal_dir=wd, **_SUP_KW)
        reqs = [sup.submit(p, max_new_tokens=m) for p, m in jobs]
        for _ in range(4):                  # both decode-phase
            sup.step()
        hp = sup.submit(_prompts([4], seed=4)[0], max_new_tokens=2,
                        priority=Priority.HIGH)
        for _ in range(2):                  # HIGH preempts -> swap-out
            sup.step()
        sup._sync_journal(force=True)
        sup.wal.commit(force=True)
        swapped = [e.rid for e in sup.journal.live_entries()
                   if e.swapped]
        assert swapped, "the drill never swapped anyone out"
        del sup
        sup2 = EngineSupervisor.recover_from_disk(factory, wd,
                                                  **_SUP_KW)
        assert any(r.swapped for r in sup2.restored.values())
        sup2.run()
        cache = sup2.engine.cache
        assert cache.swap_replay_fallbacks >= 1
        for req, ref in zip(reqs, refs):
            out = sup2.restored.get(req.rid, req)
            np.testing.assert_array_equal(out.output, ref)
        assert (hp.done and hp.finish_reason in ("eos", "max_len")
                or sup2.restored[hp.rid].done)

    def test_constrained_session_recovers_always_valid(self, tmp_path):
        """A mid-grammar session survives whole-process death: the WAL
        carries the DFA + live state, recovery re-attaches it, and the
        finished stream is token-identical to the uninterrupted
        constrained run (never silently unconstrained)."""
        factory = _factory(constraints=True, eos_token_id=2)
        dfa = dfa_from_sequences([[4, 5, 6, 7, 8, 9]], _CFG.vocab_size)
        p = _prompts([5], seed=5)[0]
        ref_eng = factory()
        ref = ref_eng.submit(p, max_new_tokens=5, constraint=dfa)
        ref_eng.run()
        wd = str(tmp_path / "j")
        sup = EngineSupervisor(factory, wal_dir=wd, **_SUP_KW)
        r = sup.submit(p, max_new_tokens=5, constraint=dfa)
        for _ in range(4):
            sup.step()
        assert r.tokens and not r.done      # genuinely mid-grammar
        del sup
        # a factory without the mask input must refuse loudly while
        # the constrained session is still live in the journal
        with pytest.raises(ValueError, match="constraints=True"):
            EngineSupervisor.recover_from_disk(_factory(), wd,
                                               **_SUP_KW)
        sup2 = EngineSupervisor.recover_from_disk(factory, wd,
                                                  **_SUP_KW)
        r2 = sup2.restored[r.rid]
        assert r2.constraint is not None
        sup2.run()
        np.testing.assert_array_equal(r2.output, ref.output)

    def test_checkpoint_prefix_restores_trie(self, tmp_path):
        """checkpoint_prefix=True carries the trie's pages in every
        incremental checkpoint, and cold recovery WRITES THEM BACK:
        the restarted engine serves the persisted chain as a prefix
        HIT (regression: the payload used to be written but never
        read on the cold path)."""
        factory = _factory()
        wd = str(tmp_path / "j")
        sup = EngineSupervisor(factory, wal_dir=wd,
                               checkpoint_prefix=True, **_SUP_KW)
        prompt = _prompts([16], seed=9)[0]
        r = sup.submit(prompt, max_new_tokens=2)
        sup.run()
        assert r.done
        sup.checkpoint_now()
        del sup
        sup2 = EngineSupervisor.recover_from_disk(factory, wd,
                                                  **_SUP_KW)
        matched, _ = sup2.engine.cache.prefix.match(prompt)
        # the chain covers the prompt's full pages minus the CoW tail
        # donor: one restored page for a 16-token / page=8 prompt
        assert len(matched) >= 1
        ref = factory().generate([prompt], max_new_tokens=2)[0]
        r2 = sup2.submit(prompt, max_new_tokens=2)
        sup2.run()
        np.testing.assert_array_equal(r2.output, ref)

    def test_deadline_survives_restore_then_crash(self, tmp_path):
        """A re-anchored deadline stays DURABLE through
        drain→restore→kill -9→recover (regression: the restore-side
        adopt used to serialize it as null, silently disabling the
        SLO after the next cold restart)."""
        t = [0.0]
        clock = lambda: t[0]                # noqa: E731
        factory = _factory()
        wd = str(tmp_path / "j1")
        sup = EngineSupervisor(factory, wal_dir=wd, clock=clock,
                               **_SUP_KW)
        r = sup.submit(_prompts([10], seed=10)[0], max_new_tokens=8,
                       deadline_s=100.0)
        sup.step()
        path = str(tmp_path / "drain.npz")
        sup.drain(path)
        wd2 = str(tmp_path / "j2")
        sup2 = EngineSupervisor.restore(factory, path, wal_dir=wd2,
                                        clock=clock, **_SUP_KW)
        assert sup2.restored[r.rid].deadline_at is not None
        sup2.step()
        del sup2                            # kill -9
        sup3 = EngineSupervisor.recover_from_disk(factory, wd2,
                                                  clock=clock,
                                                  **_SUP_KW)
        assert sup3.restored[r.rid].deadline_at is not None

    def test_drained_dir_resurrects_nothing(self, tmp_path):
        """drain() tombstones its sessions in the WAL: the drain
        checkpoint owns them (restore() revives them elsewhere), so a
        cold recovery of the directory must come up EMPTY — exactly
        one recovery owner."""
        factory = _factory()
        wd = str(tmp_path / "j")
        sup = EngineSupervisor(factory, wal_dir=wd, **_SUP_KW)
        sup.submit(_prompts([10], seed=6)[0], max_new_tokens=6)
        for _ in range(3):
            sup.step()
        sup.drain(str(tmp_path / "drain.npz"))
        sup2 = EngineSupervisor.recover_from_disk(factory, wd,
                                                  **_SUP_KW)
        assert sup2.restored == {}


class TestCrashPointSweep:
    """ACCEPTANCE (ISSUE 15 headline): simulated process death after
    EVERY engine fault site — the three WAL sites included — then
    recover_from_disk: token-identical replays, zero lost/duplicated,
    balanced allocators."""

    @pytest.mark.parametrize("site", ENGINE_SITES)
    @pytest.mark.parametrize("kv", [None, "int8"], ids=["fp", "int8"])
    def test_every_engine_site(self, kv, site, tmp_path):
        rep = _SOAK.run_crash_sweep(sites=[site], kv_cache_dtype=kv,
                                    wal_root=str(tmp_path))
        assert rep["sites"][site]["deaths"] >= 1
        assert rep["sites"][site]["fired"] >= 1

    def test_the_sweep_covers_every_engine_site(self):
        """The cases above ARE resilience.ENGINE_SITES, both KV tiers:
        narrowing the parameter list fails here."""
        marks = {m.args[0]: m.args[1] for m in
                 type(self).test_every_engine_site.pytestmark}
        assert tuple(marks["site"]) == ENGINE_SITES
        assert list(marks["kv"]) == [None, "int8"]

    @pytest.mark.parametrize("site", [
        "decode_step", "prefill_chunk", "swap_in", "wal_append",
        "checkpoint_write"])
    def test_tp2_representative_sites(self, site, tmp_path):
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices (8-device host platform)")
        rep = _SOAK.run_crash_sweep(tp=2, sites=[site],
                                    wal_root=str(tmp_path))
        assert rep["sites"][site]["deaths"] >= 1

    @pytest.mark.slow
    def test_tp2_every_engine_site(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        rep = _SOAK.run_crash_sweep(tp=2)
        assert all(v["deaths"] >= 1 for v in rep["sites"].values())

    def test_constrained_and_adapter_sessions(self):
        """Mid-grammar + adapter-pinned sessions ride the sweep too
        (the constrained engine excludes spec, so verify_step is the
        speculative sweeps' job)."""
        rep = _SOAK.run_crash_sweep(
            constrained=True,
            sites=["decode_step", "prefill_chunk", "adapter_load",
                   "wal_append", "wal_fsync", "checkpoint_write"])
        assert all(v["deaths"] >= 1 for v in rep["sites"].values())


class TestCrashSoak:
    def test_randomized_crash_soak(self):
        """tools/chaos_soak.py --crash wired into tier-1: random armed
        kills (one a torn WAL write), disk recovery each time, zero
        lost/duplicated + token identity + balanced allocator."""
        rep = _SOAK.run_crash_soak(seed=0)
        assert rep["deaths"] >= 1
        assert rep["requests"] >= 12


class TestClusterColdRecovery:
    def test_whole_process_death_and_recovery(self, tmp_path):
        """Per-replica journal dirs: the whole cluster dies (object
        abandoned), ServingCluster.recover_from_disk rebuilds every
        replica from its directory, and all live sessions finish
        token-identical with zero lost/duplicated."""
        factory = _factory()
        jobs = list(zip(_prompts([10, 6, 14, 7], seed=7), [5, 6, 4, 5]))
        refs = _refs(factory, jobs)
        wd = str(tmp_path / "cluster")
        kw = dict(supervisor_kw=dict(
            backoff_s=0.0, sleep=lambda s: None,
            wal_kw=dict(group_interval_s=0.0), checkpoint_every=4))
        cluster = ServingCluster(factory, replicas=2, wal_dir=wd,
                                 **kw)
        reqs = [cluster.submit(p, max_new_tokens=m,
                               tenant=f"t{i % 2}")
                for i, (p, m) in enumerate(jobs)]
        for _ in range(4):
            cluster.step()
        del cluster                         # whole-process kill -9
        rec = ServingCluster.recover_from_disk(factory, wd, **kw)
        assert len(rec.replicas) == 2
        rec.run()
        done = 0
        for req, ref in zip(reqs, refs):
            out = rec.recovered.get(req.rid, req)
            assert out.done and out.finish_reason in ("eos", "max_len")
            np.testing.assert_array_equal(out.output, ref)
            done += 1
        assert done == len(jobs)

    def test_failover_tombstones_dead_dir(self, tmp_path):
        """In-process failover rehomes sessions AND tombstones them in
        the dead replica's journal dir — a later cold recovery of that
        directory resurrects nothing (exactly one recovery owner)."""
        from paddle_tpu.serving import EngineDead, FaultInjector
        factory = _factory()
        wd = str(tmp_path / "cluster")
        kw = dict(supervisor_kw=dict(
            backoff_s=0.0, sleep=lambda s: None, circuit_threshold=2,
            wal_kw=dict(group_interval_s=0.0)))
        cluster = ServingCluster(factory, replicas=2, wal_dir=wd,
                                 **kw)
        jobs = list(zip(_prompts([10, 8], seed=8), [6, 6]))
        reqs = [cluster.submit(p, max_new_tokens=m)
                for p, m in jobs]
        for _ in range(2):
            cluster.step()
        inj = FaultInjector(seed=0)
        for _ in range(2):
            inj.arm("sched_tick", "raise", nth=1)
        with inj:
            for _ in range(6):
                cluster.step()
        assert cluster.failovers_total >= 1
        cluster.run()
        for req in reqs:
            assert req.done and req.finish_reason in ("eos", "max_len")
        # the failed-over dir recovers EMPTY: its sessions were
        # rehomed and durably forgotten
        for sub in sorted(os.listdir(wd)):
            state = recover_state(os.path.join(wd, sub), repair=False)
            assert state["sessions"] == {}


class TestHostStoreDiskBound:
    def test_max_disk_bytes_prunes_lru(self, tmp_path):
        """The standing disk layer stays under ``max_disk_bytes``:
        oldest-mtime files prune first, counted next to the
        corrupt-unlink counter, and pruning never eats the entry whose
        write triggered it."""
        d = str(tmp_path / "store")
        store = HostPageStore(page_size=8, path=d, max_disk_bytes=1)
        # every persisted write must prune the PREVIOUS file (cap = 1
        # byte), never the fresh one
        keys = []
        for i in range(4):
            key = bytes([i]) * 8
            keys.append(key)
            store.put(key, {"k": np.full((2, 1, 8), i, np.float32)},
                      persist=True)
            files = [f for f in os.listdir(d) if f.endswith(".npz")]
            assert len(files) == 1
        assert store.disk_pruned_total == 3
        assert store.disk_pruned_bytes_total > 0
        st = store.stats()
        assert st["disk_pruned_total"] == 3
        # the survivor is the newest write and still reads cleanly
        fresh = HostPageStore(page_size=8, path=d)
        assert fresh.get(keys[-1]) is not None
        assert fresh.get(keys[0]) is None

    def test_unbounded_by_default(self, tmp_path):
        d = str(tmp_path / "store")
        store = HostPageStore(page_size=8, path=d)
        for i in range(3):
            store.put(bytes([i]) * 4,
                      {"k": np.zeros((2, 1, 8), np.float32)},
                      persist=True)
        assert len([f for f in os.listdir(d)
                    if f.endswith(".npz")]) == 3
        assert store.disk_pruned_total == 0

    def test_validation(self):
        with pytest.raises(ValueError, match="max_disk_bytes"):
            HostPageStore(page_size=8, max_disk_bytes=0)
