"""Multi-tenant adapter plane tests (ISSUE 14 acceptance gates).

The per-request LoRA plane (paddle_tpu/serving/adapters.py), sampled
speculation (rejection_sample_tokens) and grammar-constrained decoding
(paddle_tpu/serving/constraints.py). The hard gates:

- **adapter_id=0 bit-identity**: an engine built WITH an adapter pool
  serves base-model rows token-for-token identically to the plain
  engine — fp, int8-KV, per-group int4 weights, and under a tp=2
  serving mesh (slot 0 holds exact zeros, so the added term is an
  exactly-zero add).
- **Multi-adapter batch == dense-merged reference**: a mixed batch of
  adapter rows matches, per request, a single-model engine whose
  weights have that request's adapter dense-merged in.
- **Slot residency**: refcounted pins (concurrent rows share one
  slot), LRU reclaim demotes cold adapters to the host store
  (CRC-stamped) and promotes them back; a torn payload quarantines and
  falls back to a fresh registry load, counted; every-slot-pinned is
  back-pressure (AdapterPoolExhausted is a PoolExhausted).
- **Sampled speculation**: rejection sampling emits tokens distributed
  exactly as plain sampled decode (distribution gate) and degenerates
  to the greedy acceptance rule at temperature 0 (token-identity gate).
- **Constrained decoding**: every emitted token is admitted by the
  grammar, and constrained greedy decode is token-identical to
  unconstrained whenever the grammar admits the argmax.
- **Lifecycle**: preempt → swap → resume with a live adapter stays
  token-identical; supervisor recovery re-pins journaled adapters.

Ordered LAST by tests/conftest.py (the newest gates lose first on a
watchdog-truncated slow-box run, keeping the established prefix
comparable).
"""
import numpy as np
import jax
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.distributed.mesh import serving_mesh
from paddle_tpu.serving import (AdapterPool, AdapterPoolExhausted,
                                AdapterRegistry, ConstraintState,
                                EngineSupervisor, FaultInjector,
                                HostPageStore, PoolExhausted, Priority,
                                ServingScheduler, TokenDFA,
                                dfa_from_regex, dfa_from_sequences,
                                init_lora, json_schema_dfa, merge_lora,
                                rejection_sample_tokens)
from tools.chaos_soak import _speculator

_CFG = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
_PARAMS = llama.init_params(jax.random.key(1), _CFG)

_REG = AdapterRegistry(_CFG)
for _aid in (1, 2, 3):
    _REG.register(_aid, init_lora(_CFG, 4, seed=40 + _aid))

#: compiled-program cache across engines of one config key — the
#: test_host_tier._PROTO idiom (programs are pure functions of their
#: array arguments; only the adapter/constraint SIGNATURE must match)
_PROTO = {}


def _engine(kv=None, mesh=None, adapters=False, pool=None, **kw):
    eng_kw = dict(max_batch=2, page_size=8, max_len=32,
                  kv_cache_dtype=kv, mesh=mesh)
    if pool is not None:
        eng_kw["adapters"] = pool
    elif adapters:
        eng_kw["adapters"] = dict(slots=3, rank=4, registry=_REG)
    eng_kw.update(kw)
    eng = ContinuousBatchingEngine(_PARAMS, _CFG, **eng_kw)
    key = (kv, None if mesh is None else tuple(mesh.shape.items()),
           eng.adapters is not None, eng.constraints,
           eng.weight_bits, eng.temperature, eng.spec_k,
           eng.max_batch)
    proto = _PROTO.get(key)
    if proto is None:
        _PROTO[key] = eng
    else:
        eng._chunk_fns = proto._chunk_fns
        eng._spec_fns = proto._spec_fns
        if proto._decode_fn is not None:
            eng._decode_fn = proto._decode_fn
    return eng


def _prompts(lens, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, _CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


# ---------------- pool / registry (pure host, fast) ----------------

class TestAdapterRegistry:
    def test_register_validates(self):
        reg = AdapterRegistry(_CFG)
        with pytest.raises(ValueError, match="reserved"):
            reg.register(0, init_lora(_CFG, 4))
        bad = init_lora(_CFG, 4)
        bad["ak"] = bad["aq"]            # k/v factors fork the KV
        with pytest.raises(ValueError, match="q/o-projection"):
            reg.register(1, bad)
        short = {k: v for k, v in init_lora(_CFG, 4).items()
                 if k != "bo"}
        with pytest.raises(ValueError, match="missing"):
            reg.register(1, short)
        wrong = init_lora(_CFG, 4)
        wrong["bq"] = wrong["bq"][:, :2]
        with pytest.raises(ValueError, match="shape"):
            reg.register(1, wrong)

    def test_merge_rejects_quantized(self):
        from paddle_tpu.models import generate
        q = generate.quantize_weights(_PARAMS, _CFG, bits=8)
        with pytest.raises(ValueError, match="quantized"):
            merge_lora(q, _CFG, _REG.get(1))


class TestAdapterPool:
    def test_refcounts_shared_slot_and_release(self):
        pool = AdapterPool(_CFG, slots=2, rank=4, registry=_REG)
        s1 = pool.acquire(1)
        s1b = pool.acquire(1)            # concurrent row, same slot
        assert s1 == s1b and pool.pins(1) == 2
        assert pool.loads_total == 1     # one copy in HBM
        assert pool.slot_hits_total == 1
        pool.release(1)
        assert pool.pins(1) == 1 and pool.resident(1)
        pool.release(1)
        pool.release(1)                  # idempotent on zero pins
        assert pool.pins(1) == 0 and pool.resident(1)  # stays warm

    def test_lru_reclaim_and_backpressure(self):
        pool = AdapterPool(_CFG, slots=2, rank=4, registry=_REG)
        pool.acquire(1)
        pool.acquire(2)
        with pytest.raises(AdapterPoolExhausted):
            pool.acquire(3)              # every slot pinned
        # back-pressure, not failure: the engine/scheduler admission
        # paths already defer on PoolExhausted
        assert issubclass(AdapterPoolExhausted, PoolExhausted)
        pool.release(1)                  # 1 unpinned -> LRU victim
        s3 = pool.acquire(3)
        assert not pool.resident(1) and pool.resident(3)
        assert s3 == pool.slot_of(3)
        assert pool.evictions_total == 1

    def test_base_id_is_slot0_and_free(self):
        pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG)
        assert pool.acquire(0) == 0 and pool.pins(0) == 0
        assert pool.slot_of(0) == 0 and pool.resident(0)
        pool.release(0)                  # no-op

    def test_rank_bucket_pads_and_bounds(self):
        reg = AdapterRegistry(_CFG)
        reg.register(1, init_lora(_CFG, 2, seed=9))   # rank 2 < bucket
        reg.register(2, init_lora(_CFG, 8, seed=9))   # rank 8 > bucket
        pool = AdapterPool(_CFG, slots=2, rank=4, registry=reg)
        pool.acquire(1)                  # zero-pads into the bucket
        sl = pool.slot_of(1)
        a = np.asarray(pool.arrays["aq"])[:, sl]
        assert a[:, :, 2:].max() == 0.0  # padded rank columns exact 0
        assert np.abs(a[:, :, :2]).max() > 0
        with pytest.raises(ValueError, match="rank"):
            pool.acquire(2)
        with pytest.raises(KeyError):
            pool.acquire(77)             # registered nowhere

    def test_demote_promote_roundtrip_crc(self):
        store = HostPageStore(page_size=8)
        pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG,
                           store=store)
        pool.acquire(1)
        src = {n: np.asarray(pool.arrays[n])[:, pool.slot_of(1)].copy()
               for n in ("aq", "bq", "ao", "bo")}
        pool.release(1)
        pool.acquire(2)                  # evicts 1 -> demote to store
        assert pool.demotions_total == 1
        entry = store.get(b"adapter/1", touch=False)
        assert entry is not None and entry.get("checksums")
        pool.release(2)
        pool.acquire(1)                  # promote back
        assert pool.promotions_total == 1
        for n in ("aq", "bq", "ao", "bo"):
            got = np.asarray(pool.arrays[n])[:, pool.slot_of(1)]
            np.testing.assert_array_equal(got, src[n])

    def test_standing_store_promotes_across_restart(self):
        """A demoted adapter persisted to the standing on-disk layer
        promotes into a FRESH pool sharing only the store directory —
        the restarted engine's first admission is a promote (CRC
        verified), not a registry re-read."""
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG,
                               store=HostPageStore(page_size=8, path=d))
            pool.acquire(1)
            src = {n: np.asarray(pool.arrays[n])[:, 1].copy()
                   for n in ("aq", "bq", "ao", "bo")}
            pool.release(1)
            pool.acquire(2)              # demote 1 -> disk too
            # "restart": fresh pool + fresh store over the same path,
            # and an EMPTY registry — the payload must come from disk
            pool2 = AdapterPool(_CFG, slots=1, rank=4,
                                registry=AdapterRegistry(_CFG),
                                store=HostPageStore(page_size=8,
                                                    path=d))
            pool2.acquire(1)
            assert pool2.promotions_total == 1
            for n in ("aq", "bq", "ao", "bo"):
                np.testing.assert_array_equal(
                    np.asarray(pool2.arrays[n])[:, pool2.slot_of(1)],
                    src[n])

    def test_torn_payload_quarantines_and_falls_back(self):
        store = HostPageStore(page_size=8)
        pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG,
                           store=store)
        pool.acquire(1)
        good = {n: np.asarray(pool.arrays[n])[:, 1].copy()
                for n in ("aq", "bq", "ao", "bo")}
        pool.release(1)
        pool.acquire(2)                  # demote 1
        entry = store.get(b"adapter/1", touch=False)
        torn = entry["arrays"]["bq"].copy()
        torn.view(np.uint8).reshape(-1)[3] ^= 0xFF   # flip a real byte
        entry["arrays"]["bq"] = torn
        pool.release(2)
        pool.acquire(1)                  # CRC fails -> fresh load
        assert pool.fallbacks_total == 1
        assert store.quarantined_total == 1
        assert store.get(b"adapter/1", touch=False) is None  # gone
        for n in ("aq", "bq", "ao", "bo"):
            np.testing.assert_array_equal(
                np.asarray(pool.arrays[n])[:, pool.slot_of(1)], good[n])


# ---------------- engine parity gates ----------------

class TestAdapterParity:
    @pytest.mark.parametrize("kv,bits", [(None, None), ("int8", None),
                                         (None, 4)])
    def test_adapter_id0_bit_identity(self, kv, bits):
        """The adapter-enabled engine on BASE rows == the plain engine,
        token for token — fp, int8-KV and int4 weights (the acceptance
        criterion's three tiers; tp=2 below)."""
        prompts = _prompts([4, 7], seed=1)
        plain = _engine(kv=kv, weight_bits=bits)
        ref = plain.generate(prompts, max_new_tokens=6)
        witha = _engine(kv=kv, weight_bits=bits, adapters=True)
        out = witha.generate(prompts, max_new_tokens=6)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)

    def test_multi_adapter_batch_matches_merged_reference(self):
        """A mixed batch (base + two different adapters) matches, per
        request, the single-model engine with that adapter dense-merged
        — the multi-tenant batch is exactly N virtual engines."""
        prompts = _prompts([4, 6, 7], seed=2)
        aids = [0, 1, 2]
        refs = []
        for p, aid in zip(prompts, aids):
            par = (merge_lora(_PARAMS, _CFG, _REG.get(aid)) if aid
                   else _PARAMS)
            e = ContinuousBatchingEngine(par, _CFG, max_batch=1,
                                         page_size=8, max_len=32)
            refs.append(e.generate([p], max_new_tokens=6)[0])
        eng = _engine(adapters=True, max_batch=3)
        reqs = [eng.submit(p, max_new_tokens=6, adapter_id=aid)
                for p, aid in zip(prompts, aids)]
        eng.run()
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.output, ref)

    def test_chunked_prefill_carries_adapter(self):
        """A multi-chunk prompt (prefill_chunk=8) through the adapter
        term matches the merged reference — the chunk program's
        one-request adapter gather."""
        p = _prompts([20], seed=3)[0]
        merged = merge_lora(_PARAMS, _CFG, _REG.get(1))
        ref = ContinuousBatchingEngine(
            merged, _CFG, max_batch=1, page_size=8, max_len=32,
            prefill_chunk=8).generate([p], max_new_tokens=4)[0]
        eng = _engine(adapters=True, prefill_chunk=8)
        r = eng.submit(p, max_new_tokens=4, adapter_id=1)
        eng.run()
        np.testing.assert_array_equal(r.output, ref)

    def test_tp2_adapter_parity(self):
        """tp=2 sharded adapter decode == single-chip adapter decode,
        token for token (B factors column-shard with the weights), and
        id-0 rows under tp == the plain tp engine."""
        prompts = _prompts([4, 7], seed=4)
        ref_eng = _engine(adapters=True)
        refs = [ref_eng.submit(p, max_new_tokens=6, adapter_id=aid)
                for p, aid in zip(prompts, (1, 0))]
        ref_eng.run()
        mesh = serving_mesh(2)
        pool = AdapterPool(_CFG, slots=3, rank=4, registry=_REG,
                           mesh=mesh)
        tp_eng = _engine(mesh=mesh, pool=pool)
        outs = [tp_eng.submit(p, max_new_tokens=6, adapter_id=aid)
                for p, aid in zip(prompts, (1, 0))]
        tp_eng.run()
        for r, o in zip(refs, outs):
            np.testing.assert_array_equal(r.output, o.output)

    def test_spec_verify_carries_adapter(self):
        """Greedy spec decode WITH an adapter == plain decode with the
        same adapter (the verify program's per-row adapter gather keeps
        the acceptance rule consistent)."""
        p = np.tile(_prompts([5], seed=5)[0], 3)
        plain = _engine(adapters=True)
        r0 = plain.submit(p, max_new_tokens=8, adapter_id=1)
        plain.run()
        spec = _engine(adapters=True, spec_k=3)
        r1 = spec.submit(p, max_new_tokens=8, adapter_id=1)
        spec.run()
        np.testing.assert_array_equal(r0.output, r1.output)

    def test_mesh_mismatch_rejected(self):
        pool = AdapterPool(_CFG, slots=2, rank=4, registry=_REG)
        with pytest.raises(ValueError, match="mesh"):
            ContinuousBatchingEngine(_PARAMS, _CFG, max_batch=2,
                                     page_size=8, max_len=32,
                                     mesh=serving_mesh(2),
                                     adapters=pool)

    def test_adapter_without_pool_rejected(self):
        eng = _engine()
        with pytest.raises(ValueError, match="adapter"):
            eng.submit(_prompts([4])[0], max_new_tokens=2, adapter_id=1)


# ---------------- sampled speculation ----------------

class TestRejectionSampling:
    def test_temperature0_equals_greedy_rule(self):
        rs = np.random.default_rng(0)
        logits = rs.normal(size=(4, 16)).astype(np.float32)
        targets = np.argmax(logits, axis=-1)
        drafts = np.array([targets[0], targets[1], 5], np.int64)
        toks, a = rejection_sample_tokens(logits, drafts, 0.0, rs)
        from paddle_tpu.serving import longest_accepted_prefix
        a_ref = longest_accepted_prefix(drafts, targets)
        assert a == a_ref == 2
        assert toks == [int(targets[0]), int(targets[1]),
                        int(targets[2])]

    def test_output_distribution_matches_plain_sampling(self):
        """The distribution gate: the FIRST committed token of the
        rejection-sampled run is distributed exactly as
        softmax(logits[0]/T) — accept-the-draft with p(draft) plus the
        corrected residual reconstructs p itself, so sampled spec
        decode emits the plain sampled-decode law token for token."""
        rng = np.random.default_rng(3)
        V, T, temp, N = 12, 2, 0.8, 6000
        logits = rng.normal(size=(T, V)).astype(np.float64) * 2.0
        z = logits[0] / temp
        p = np.exp(z - z.max())
        p /= p.sum()
        draft = int(np.argsort(p)[-2])   # a plausible but non-argmax draft
        counts = np.zeros(V)
        for _ in range(N):
            toks, _ = rejection_sample_tokens(
                logits, [draft], temp, rng)
            counts[toks[0]] += 1
        tv = 0.5 * np.abs(counts / N - p).sum()
        assert tv < 0.05, (tv, counts / N, p)

    def test_no_draft_row_samples_plain(self):
        rng = np.random.default_rng(4)
        logits = rng.normal(size=(1, 8)).astype(np.float64)
        toks, a = rejection_sample_tokens(logits, None, 0.7, rng)
        assert a == 0 and len(toks) == 1 and 0 <= toks[0] < 8

    def test_engine_temp0_spec_equals_greedy_spec(self):
        """Engine-level: the rejection-sampled commit at temperature 0
        degenerates to the PR 5 greedy acceptance — token-identical."""
        p = np.tile(_prompts([5], seed=6)[0], 3)
        greedy = _engine(spec_k=3)
        r0 = greedy.submit(p, max_new_tokens=8)
        greedy.run()
        plain = _engine()
        r1 = plain.submit(p, max_new_tokens=8)
        plain.run()
        np.testing.assert_array_equal(r0.output, r1.output)

    def test_sampled_spec_commits_and_counts(self):
        from paddle_tpu import observability as obs
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            p = np.tile(_prompts([4], seed=7)[0], 4)
            # a speculator that drafts at every step: the n-gram one
            # stops drafting once a sampled token leaves the motif,
            # which at this seed is the first, and nothing is counted
            eng = _engine(temperature=0.7, spec_k=3,
                          speculator=_speculator(3))
            r = eng.submit(p, max_new_tokens=10)
            eng.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert r.done and len(r.tokens) == 10
        drafted = snap["serving_sample_drafted_total"]["values"][""]
        accepted = snap["serving_sample_accepted_total"]["values"][""]
        assert drafted > 0 and 0 <= accepted <= drafted
        assert snap["serving_sample_accept_rate"]["values"][""][
            "count"] >= 1

    def test_spec_with_constraints_rejected(self):
        with pytest.raises(ValueError, match="constraints"):
            _engine(spec_k=2, constraints=True)


# ---------------- constrained decoding ----------------

class TestConstraintCompilers:
    def test_trie_dfa_paths(self):
        dfa = dfa_from_sequences([[4, 5], [4, 6, 7]], 16)
        assert dfa.allowed(dfa.start)[4] and not \
            dfa.allowed(dfa.start)[5]
        s = dfa.advance(dfa.start, 4)
        assert dfa.accepting[dfa.advance(s, 5)]
        assert dfa.advance(s, 9) == -1

    def test_regex_dfa_token_lift(self):
        # token strings: multi-char tokens die mid-string when the
        # pattern can't absorb them from the current state
        toks = ["", "a", "b", "ab", "ba", "c"]
        dfa = dfa_from_regex("a(b|c)*", toks)
        s0 = dfa.start
        assert dfa.advance(s0, 1) >= 0       # "a"
        assert dfa.advance(s0, 2) == -1      # "b" can't start
        assert dfa.advance(s0, 3) >= 0       # "ab" runs a then b
        assert dfa.advance(s0, 0) == -1      # empty token never admitted
        s1 = dfa.advance(s0, 1)
        assert dfa.accepting[s1]             # "a" alone matches
        assert dfa.advance(s1, 4) == -1      # "ba" dies (a after b-state)
        s2 = dfa.advance(s1, 2)              # "ab"
        assert dfa.accepting[s2]
        assert dfa.advance(s2, 5) >= 0       # "abc"

    def test_json_schema_dfa_accepts_valid_only(self):
        toks = list('{}":,abcdefghijklmnopqrstuvwxyz0123456789-') \
            + ["true", "false"]
        dfa = json_schema_dfa(
            {"type": "object",
             "properties": {"name": {"type": "string"},
                            "ok": {"type": "boolean"}}}, toks)

        def run(text_tokens):
            s = dfa.start
            for t in text_tokens:
                s = dfa.advance(s, toks.index(t))
                if s < 0:
                    return -1
            return s

        good = list('{"name":"ab","ok":') + ["true"] + ["}"]
        s = run(good)
        assert s >= 0 and dfa.accepting[s]
        assert run(list('{"ok"')) == -1      # wrong key order
        assert run(list('{"name":12')) == -1  # int for string
        with pytest.raises(ValueError, match="object"):
            json_schema_dfa({"type": "array"}, toks)

    def test_json_schema_escapes_regex_metachars(self):
        """Enum values and keys are DATA: an unescaped ``+`` would
        quantify, ``.`` would wildcard and ``(`` would crash the
        compile — regression for the literal-escaping fix."""
        toks = list('{}":,ab+.()0123456789')
        dfa = json_schema_dfa(
            {"type": "object",
             "properties": {"a.b": {"enum": ["a+b", "(a)"]}}}, toks)

        def run(text):
            s = dfa.start
            for ch in text:
                s = dfa.advance(s, toks.index(ch))
                if s < 0:
                    return -1
            return s

        s = run('{"a.b":"a+b"}')
        assert s >= 0 and dfa.accepting[s]
        s = run('{"a.b":"(a)"}')
        assert s >= 0 and dfa.accepting[s]
        assert run('{"a.b":"aab"}') == -1    # '+' must not quantify
        assert run('{"a0b":"a+b"}') == -1    # '.' must not wildcard

    def test_state_deadend_admits_eos_and_counts(self):
        table = np.full((1, 8), -1, np.int32)   # no live transitions
        st = ConstraintState(TokenDFA(table, [False]), eos_token_id=2)
        m = st.mask(8)
        assert m[2] and m.sum() == 1 and st.dead_ends == 1

    def test_advance_rejects_unmasked_commit(self):
        dfa = dfa_from_sequences([[4]], 8)
        st = ConstraintState(dfa, eos_token_id=2)
        with pytest.raises(ValueError, match="inadmissible"):
            st.advance(6)


class TestConstrainedEngine:
    def test_always_valid_output(self):
        """The hard gate: every emitted token has a live DFA transition
        (or is eos from an accepting state) — on greedy AND sampled
        engines."""
        seqs = [[4, 5, 6], [4, 9], [10, 11, 12, 13]]
        for temp in (0.0, 0.9):
            dfa = dfa_from_sequences(seqs, _CFG.vocab_size)
            eng = _engine(constraints=True, temperature=temp,
                          eos_token_id=2)
            reqs = [eng.submit(p, max_new_tokens=8, constraint=dfa)
                    for p in _prompts([4, 6], seed=8)]
            eng.run()
            for r in reqs:
                toks = [t for t in r.tokens if t != 2]
                s = dfa.start
                for t in toks:
                    s = dfa.advance(s, t)
                    assert s >= 0, (temp, r.tokens)

    def test_greedy_identity_when_grammar_admits_argmax(self):
        """Masking only EXCLUDES: a full-vocab grammar leaves greedy
        decode token-identical to the unconstrained engine."""
        full = TokenDFA(
            np.zeros((1, _CFG.vocab_size), np.int32), [True])
        prompts = _prompts([4, 7], seed=9)
        ref = _engine().generate(prompts, max_new_tokens=6)
        eng = _engine(constraints=True)
        reqs = [eng.submit(p, max_new_tokens=6, constraint=full)
                for p in prompts]
        eng.run()
        for r, a in zip(reqs, ref):
            np.testing.assert_array_equal(r.output, a)

    def test_mixed_batch_constrained_and_free(self):
        """Constrained and unconstrained rows share one program: the
        free row matches the plain engine while the constrained row
        obeys its grammar."""
        prompts = _prompts([4, 6], seed=10)
        ref_free = _engine().generate([prompts[1]],
                                      max_new_tokens=6)[0]
        dfa = dfa_from_sequences([[4, 5, 6, 7, 8, 9]],
                                 _CFG.vocab_size)
        eng = _engine(constraints=True, eos_token_id=2)
        rc = eng.submit(prompts[0], max_new_tokens=6, constraint=dfa)
        rf = eng.submit(prompts[1], max_new_tokens=6)
        eng.run()
        np.testing.assert_array_equal(rf.output, ref_free)
        s = dfa.start
        for t in (t for t in rc.tokens if t != 2):
            s = dfa.advance(s, t)
            assert s >= 0

    def test_violation_counter_and_mask_metrics(self):
        from paddle_tpu import observability as obs
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            # a grammar that CANNOT contain the unconstrained argmax
            # path for long: a single-token answer set far from the
            # model's preference is near-guaranteed to mask the argmax
            # at least once
            dfa = dfa_from_sequences([[3, 3, 3, 3, 3, 3]],
                                     _CFG.vocab_size)
            eng = _engine(constraints=True, eos_token_id=2)
            r = eng.submit(_prompts([5], seed=11)[0], max_new_tokens=5,
                           constraint=dfa)
            eng.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert all(t in (3, 2) for t in r.tokens)
        assert snap["serving_constrain_rows_total"]["values"][""] >= 1
        assert snap["serving_constrain_mask_ms"]["values"][""][
            "count"] >= 1
        assert snap["serving_constrain_violations_avoided_total"][
            "values"][""] >= 1

    def test_first_token_violation_counted(self):
        """The violation-avoided counter covers the PREFILL commit
        path too: a grammar that masks the first token's unconstrained
        argmax counts exactly one violation at max_new_tokens=1."""
        from paddle_tpu import observability as obs
        p = _prompts([5], seed=13)[0]
        free = _engine().generate([p], max_new_tokens=1)[0][-1]
        forced = 3 if int(free) != 3 else 4   # anything but the argmax
        dfa = dfa_from_sequences([[forced]], _CFG.vocab_size)
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            eng = _engine(constraints=True, eos_token_id=2)
            r = eng.submit(p, max_new_tokens=1, constraint=dfa)
            eng.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        assert list(r.tokens) == [forced]
        assert snap["serving_constrain_violations_avoided_total"][
            "values"][""] == 1

    def test_drain_carries_live_constrained_sessions(self, tmp_path):
        """ISSUE 15 satellite: a drain checkpoint now SERIALIZES live
        grammar state (dense DFA table + state id + violation
        counters), so draining mid-grammar works — and the restored
        session finishes always-valid and token-identical to the
        uninterrupted constrained run (the standing refusal is gone).
        A restore into an engine WITHOUT constraints=True still fails
        loudly instead of silently decoding unconstrained."""
        def factory():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
                constraints=True, eos_token_id=2)

        dfa = dfa_from_sequences([[4, 5, 6, 7, 8, 9]], _CFG.vocab_size)
        p = _prompts([4], seed=14)[0]
        ref_eng = factory()
        ref = ref_eng.submit(p, max_new_tokens=5, constraint=dfa)
        ref_eng.run()

        sup = EngineSupervisor(factory, backoff_s=0.0,
                               sleep=lambda s: None)
        r = sup.submit(p, max_new_tokens=5, constraint=dfa)
        for _ in range(4):                 # mid-grammar: some tokens in
            sup.step()
        assert r.tokens and not r.done
        path = str(tmp_path / "drain.npz")
        summary = sup.drain(path)
        assert summary["sessions"] == 1
        # an engine with no mask input must refuse the restore loudly
        def bare_factory():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
                eos_token_id=2)
        with pytest.raises(ValueError, match="constraints=True"):
            EngineSupervisor.restore(bare_factory, path,
                                     backoff_s=0.0,
                                     sleep=lambda s: None)
        sup2 = EngineSupervisor.restore(factory, path, backoff_s=0.0,
                                        sleep=lambda s: None)
        sup2.run()
        r2 = sup2.restored[r.rid]
        np.testing.assert_array_equal(r2.output, ref.output)
        # always-valid: every emitted token walks the grammar (or eos)
        assert r2.constraint is not None and r2.constraint.finished \
            or all(t in (4, 5, 6, 7, 8, 9, 2) for t in r2.tokens)

    def test_eosless_engine_completed_grammar_freeruns(self):
        """Regression: on an engine with NO eos id, a grammar
        production that completes (accepting state, no live
        transitions) has no terminator to emit — the state must latch
        finished and free-run the tail instead of unmasking everything
        and then raising ``inadmissible token`` at commit."""
        seqs = [[2, 4, 6], [2, 4, 8], [1, 3]]
        dfa = dfa_from_sequences(seqs, _CFG.vocab_size)
        eng = _engine(constraints=True)       # eos_token_id=None
        r = eng.submit(_prompts([4], seed=12)[0], max_new_tokens=6,
                       constraint=dfa)
        eng.run()
        assert r.done and len(r.tokens) == 6
        # the head of the stream is grammar-valid; the tail past the
        # completed production is the documented free-run
        s = dfa.start
        for t in r.tokens:
            nxt = dfa.advance(s, t)
            if nxt < 0:
                assert r.constraint.finished
                break
            s = nxt
        assert r.constraint.finished and r.constraint.dead_ends == 0

    def test_constraint_without_flag_rejected(self):
        eng = _engine()
        dfa = dfa_from_sequences([[4]], _CFG.vocab_size)
        with pytest.raises(ValueError, match="constraints=True"):
            eng.submit(_prompts([4])[0], max_new_tokens=2,
                       constraint=dfa)


# ---------------- lifecycle ----------------

class TestAdapterLifecycle:
    def test_preempt_swap_resume_with_live_adapter(self):
        """A decode-phase adapter request preempted to the host tier
        (swap-out) resumes by swap-in and finishes TOKEN-IDENTICAL to
        the uninterrupted adapter run — the adapter pin drops with the
        preemption and re-pins at resume."""
        pool = AdapterPool(_CFG, slots=3, rank=4, registry=_REG)
        ref_eng = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
            adapters=AdapterPool(_CFG, slots=3, rank=4, registry=_REG))
        ref = ref_eng.submit(_prompts([6], seed=12)[0],
                             max_new_tokens=8, adapter_id=1)
        ref_eng.run()
        eng = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
            host_tier=True, adapters=pool)
        sched = ServingScheduler(eng)
        a = sched.submit(_prompts([6], seed=12)[0], max_new_tokens=8,
                         priority=Priority.LOW, adapter_id=1)
        while len(a.tokens) < 3:
            sched.step()
        assert pool.pins(1) == 1
        sched.submit(_prompts([4], seed=13)[0], max_new_tokens=2,
                     priority=Priority.HIGH)
        sched.step()
        assert a.preemptions == 1 and a.slot is None
        assert pool.pins(1) == 0         # evicted: no residency pinned
        sched.run()
        assert a.done and a.finish_reason in ("eos", "max_len")
        np.testing.assert_array_equal(a.output, ref.output)
        st = eng.stats()
        assert st["swap_outs_total"] >= 1 and st["swap_ins_total"] >= 1
        assert pool.pins(1) == 0         # retired: pin released

    def test_scheduler_defers_on_pinned_pool(self):
        """AdapterPoolExhausted is back-pressure: the second adapter's
        admission defers until the first retires, then both finish."""
        pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG)
        eng = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=2, page_size=8, max_len=32,
            adapters=pool)
        sched = ServingScheduler(eng)
        r1 = sched.submit(_prompts([4], seed=14)[0], max_new_tokens=4,
                          adapter_id=1)
        r2 = sched.submit(_prompts([5], seed=15)[0], max_new_tokens=4,
                          adapter_id=2)
        sched.run()
        assert r1.done and r2.done
        assert r1.finish_reason in ("eos", "max_len")
        assert r2.finish_reason in ("eos", "max_len")
        assert pool.evictions_total >= 1   # 2 displaced 1 after retire

    def test_unknown_adapter_rejected_at_submit(self):
        """An unresolvable adapter_id rejects at INTAKE — queued, it
        would raise at admission inside the serving loop and poison
        every tenant's step (and every recovery re-admission)."""
        eng = _engine(adapters=True)
        with pytest.raises(ValueError, match="neither registered"):
            eng.submit(_prompts([4], seed=20)[0], max_new_tokens=2,
                       adapter_id=99)
        big = AdapterRegistry(_CFG)
        big.register(1, init_lora(_CFG, 8, seed=50))
        pool = AdapterPool(_CFG, slots=2, rank=4, registry=big)
        e2 = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=2, page_size=8, max_len=32,
            adapters=pool)
        with pytest.raises(ValueError, match="rank bucket"):
            e2.submit(_prompts([4], seed=20)[0], max_new_tokens=2,
                      adapter_id=1)
        # the engine keeps serving after either rejection
        r = eng.submit(_prompts([4], seed=21)[0], max_new_tokens=2,
                       adapter_id=1)
        eng.run()
        assert r.done

    def test_pinned_pool_never_preempts_baseline_victims(self):
        """An adapter-slot shortfall must NOT trigger page-oriented
        preemption of lower-class BASE-MODEL victims: evicting them
        frees no adapter slot, so the admission defers instead (zero
        pointless preemptions); with every slot pinned by equal-class
        runners the request simply waits for a retirement."""
        pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG)
        eng = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=2, page_size=8, max_len=32,
            adapters=pool)
        sched = ServingScheduler(eng)
        lo = sched.submit(_prompts([4], seed=22)[0], max_new_tokens=8,
                          priority=Priority.LOW)          # base model
        hi = sched.submit(_prompts([5], seed=23)[0], max_new_tokens=8,
                          priority=Priority.HIGH, adapter_id=1)
        sched.step()                  # both running; slot pinned by hi
        want = sched.submit(_prompts([6], seed=24)[0], max_new_tokens=2,
                            priority=Priority.NORMAL, adapter_id=2)
        sched.run()
        assert want.done and lo.done and hi.done
        assert sched.preemptions_total == 0
        assert lo.preemptions == 0

    def test_recovery_repins_journaled_adapter(self):
        """A mid-decode fault tears the engine down; the rebuilt engine
        (same pool, pins reset) re-admits the journaled session through
        acquire() and finishes token-identically."""
        pool = AdapterPool(_CFG, slots=3, rank=4, registry=_REG)

        def factory():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
                adapters=pool)

        ref_eng = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
            adapters=AdapterPool(_CFG, slots=3, rank=4, registry=_REG))
        ref = ref_eng.submit(_prompts([5], seed=16)[0],
                             max_new_tokens=6, adapter_id=2)
        ref_eng.run()
        inj = FaultInjector(seed=0)
        inj.arm("decode_step", "raise", nth=3)
        sup = EngineSupervisor(factory, backoff_s=0.0,
                               sleep=lambda s: None)
        with inj:
            r = sup.submit(_prompts([5], seed=16)[0], max_new_tokens=6,
                           adapter_id=2)
            sup.run()
        assert inj.fired_total == 1 and sup.recoveries == 1
        np.testing.assert_array_equal(r.output, ref.output)
        assert pool.pins(2) == 0

    @pytest.mark.parametrize("site", ["adapter_load",
                                      "adapter_promote"])
    def test_fault_at_adapter_site_recovers_token_identically(
            self, site):
        """A fault AT the load/promote site commits nothing: the
        registry entry / demoted payload survives for the retried
        admission after recovery, and the stream finishes exactly the
        uninterrupted run (the per-site recovery-parity gate the
        resilience sweep delegates here)."""
        store = HostPageStore(page_size=8)
        pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG,
                           store=store)

        def factory():
            return ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
                adapters=pool)

        ref_eng = ContinuousBatchingEngine(
            _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
            adapters=AdapterPool(_CFG, slots=1, rank=4, registry=_REG))
        p = _prompts([5], seed=18)[0]
        ref = ref_eng.submit(p, max_new_tokens=4, adapter_id=1)
        ref_eng.run()
        sup = EngineSupervisor(factory, backoff_s=0.0,
                               sleep=lambda s: None)
        if site == "adapter_promote":
            # demote 1 first so the faulted admission is a PROMOTION
            warm = sup.submit(_prompts([4], seed=19)[0],
                              max_new_tokens=2, adapter_id=1)
            sup.run()
            warm2 = sup.submit(_prompts([4], seed=20)[0],
                               max_new_tokens=2, adapter_id=2)
            sup.run()
            assert warm.done and warm2.done
            assert pool.demotions_total >= 1
        inj = FaultInjector(seed=0)
        # the very next visit to the site faults (the admission commits
        # nothing); the post-recovery re-admission's visit succeeds
        inj.arm(site, "raise", nth=1)
        with inj:
            r = sup.submit(p, max_new_tokens=4, adapter_id=1)
            sup.run()
        assert inj.fired[site] == 1, f"{site} never fired"
        assert sup.recoveries >= 1 and sup.health != "dead"
        np.testing.assert_array_equal(r.output, ref.output)

    def test_adapter_metrics_emitted(self):
        from paddle_tpu import observability as obs
        was = obs.metrics_enabled()
        obs.REGISTRY.clear()
        obs.enable()
        try:
            store = HostPageStore(page_size=8)
            pool = AdapterPool(_CFG, slots=1, rank=4, registry=_REG,
                               store=store)
            eng = ContinuousBatchingEngine(
                _PARAMS, _CFG, max_batch=1, page_size=8, max_len=32,
                adapters=pool)
            for aid in (1, 2, 1):        # load, evict+load, promote
                r = eng.submit(_prompts([4], seed=17)[0],
                               max_new_tokens=2, adapter_id=aid)
                eng.run()
            snap = obs.REGISTRY.to_json()
        finally:
            obs.REGISTRY.clear()
            if not was:
                obs.disable()
        vals = snap["serving_adapter_loads_total"]["values"]
        assert sum(vals.values()) == 3
        assert any("promote" in k for k in vals)
        assert snap["serving_adapter_demotions_total"]["values"][
            ""] >= 2
        assert snap["serving_adapter_slots_used"]["values"][""] == 1
        assert snap["serving_adapter_load_ms"]["values"][""][
            "count"] == 3
        gather = snap["serving_adapter_gather_bytes_total"]["values"]
        assert sum(gather.values()) > 0   # traced into the programs
