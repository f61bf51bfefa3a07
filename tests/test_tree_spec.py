"""Model-based draft + tree speculation tests (ISSUE 20 gates).

The truncated-layer shared-embedding DRAFT MODEL proposes tokens
(linear chain or comb tree) with its own KV in a second small paged
pool; ONE verify forward scores the whole proposal (the tree via the
ancestor mask folded into the chunk kernel). Hard gates:

- draft-linear and tree speculative GREEDY decode are TOKEN-IDENTICAL
  to plain paged decode at fp and int8-KV (tp=2 / overlap / sampled
  variants ride the slow tier);
- sampled acceptance is DISTRIBUTION-gated: real-q rejection sampling
  and the tree walk both emit the plain sampled-decode law
  token-for-token (property tests over broad / narrow / mismatched-
  support q — the ISSUE 20 satellite);
- the kernel's tree-mask path: a chain tree through the Pallas kernel
  is BIT-IDENTICAL to the kernel's own causal path, and the tree path
  matches the pure-lax masked reference (fp + int8 temp cache);
- the token budget charges a tree by its NODE count and trims LEAVES,
  never the root path — budgeted tree runs stay token-identical;
- draft-pool lifecycle: admit / rejection cascades / preemption /
  exhaustion-skip all drain the second pool balanced;
- resilience: a kill mid-tree-verify recovers token-identically from
  the journal (the draft pool rebuilds cold), and recovery REFUSES a
  factory whose draft identity differs from the journaled one;
- synth_trace's text mode is non-repetitive by construction (the
  n-gram proposer finds nothing), so the bench acceptance rider
  measures the draft model, not in-context repetition.
"""
import numpy as np
import jax
import pytest

from paddle_tpu.models import llama
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.serving import (NgramProposer, Priority,
                                ServingScheduler, TreeDraft,
                                build_comb_tree, longest_accepted_path,
                                longest_accepted_prefix,
                                rejection_sample_tokens, synth_trace,
                                tree_ancestor_matrix, tree_depths,
                                tree_rejection_sample)

ENG = dict(max_batch=3, page_size=8, max_len=32)


def _setup(seed=0):
    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
    params = llama.init_params(jax.random.key(seed), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _aligned(params, draft_layers=1, damp=1e-3):
    """Damp the post-draft layers' residual contributions (wo/wd) so
    the truncated draft TRACKS the full target — acceptance becomes
    high without touching what either model is: identity gates stay
    exact (both engines see the same damped params) while the 1+k
    compression actually engages."""
    layers = dict(params["layers"])
    for n in ("wo", "wd"):
        layers[n] = layers[n].at[draft_layers:].multiply(damp)
    out = dict(params)
    out["layers"] = layers
    return out


def _softmax(z):
    e = np.exp(z - z.max())
    return e / e.sum()


# ---------------- tree structure ----------------

class TestTreeDraft:
    def test_topology_validation(self):
        with pytest.raises(ValueError, match="topological"):
            TreeDraft([5, 6], [0, -1])
        with pytest.raises(ValueError, match="topological"):
            TreeDraft([5, 6, 7], [-1, 0, 2])     # parent not < i
        with pytest.raises(ValueError, match="non-empty"):
            TreeDraft([], [])

    def test_size_and_leading_slice_trims_leaves_first(self):
        # comb (width 2, depth 3): chain 10,11,12 + one sibling per
        # depth — chain-first order means [:k] sheds siblings, then
        # the chain tail; the root path prefix always survives
        t = build_comb_tree(5, [10, 11, 12], [[20], [21], [22]])
        assert t.size == 6 and t.tokens.size == 7
        np.testing.assert_array_equal(t.tokens,
                                      [5, 10, 11, 12, 20, 21, 22])
        np.testing.assert_array_equal(t.parents, [-1, 0, 1, 2, 0, 1, 2])
        trim = t[:4]                           # drops two sibling leaves
        np.testing.assert_array_equal(trim.tokens, [5, 10, 11, 12, 20])
        trim = t[:2]                           # down to a chain prefix
        np.testing.assert_array_equal(trim.tokens, [5, 10, 11])
        np.testing.assert_array_equal(trim.parents, [-1, 0, 1])
        assert t[:0].tokens.size == 1          # root only
        assert t[:99].size == t.size

    def test_only_leading_slices(self):
        t = build_comb_tree(5, [10, 11])
        with pytest.raises(TypeError, match="leading"):
            t[1:3]
        with pytest.raises(TypeError, match="leading"):
            t[::2]

    def test_depths_and_ancestor_matrix(self):
        t = build_comb_tree(5, [10, 11], [[20], [21]])
        np.testing.assert_array_equal(t.depths(), [0, 1, 2, 1, 2])
        anc = tree_ancestor_matrix(t.parents)
        # sibling of chain[0] (node 3) sees root + itself only
        np.testing.assert_array_equal(anc[3], [1, 0, 0, 1, 0])
        # deep sibling (node 4, child of chain node 1) sees its path
        np.testing.assert_array_equal(anc[4], [1, 1, 0, 0, 1])

    def test_chain_ancestor_matrix_is_causal(self):
        t = build_comb_tree(5, [10, 11, 12])
        np.testing.assert_array_equal(
            tree_ancestor_matrix(t.parents),
            np.tril(np.ones((4, 4), bool)))
        np.testing.assert_array_equal(tree_depths(t.parents),
                                      np.arange(4))

    def test_sibling_lists_beyond_chain_ignored(self):
        t = build_comb_tree(5, [10], [[20], [21]])
        assert t.tokens.size == 3              # root + chain + 1 sibling


# ---------------- greedy tree acceptance ----------------

class TestGreedyTreeWalk:
    def test_chain_matches_linear_rule(self):
        t = build_comb_tree(5, [10, 11, 12])
        targets = np.array([10, 11, 9, 7])
        path, committed, acc = longest_accepted_path(
            t.tokens, t.parents, targets)
        a = longest_accepted_prefix(np.array([10, 11, 12]), targets[:3])
        assert acc == a == 2
        assert committed == [10, 11, 9] and path == [0, 1, 2]

    def test_sibling_rescues_rejected_chain(self):
        # chain proposes 10 but the target is the SIBLING 20: the walk
        # must follow the sibling and keep accepting below it
        t = TreeDraft([5, 10, 11, 20, 30],
                      [-1, 0, 1, 0, 3])         # 30 hangs off sibling 20
        path, committed, acc = longest_accepted_path(
            t.tokens, t.parents, np.array([20, 0, 0, 30, 8]))
        assert path == [0, 3, 4] and acc == 2
        assert committed == [20, 30, 8]        # 8 = bonus at the leaf

    def test_no_match_commits_bonus_only(self):
        t = build_comb_tree(5, [10], [[20]])
        path, committed, acc = longest_accepted_path(
            t.tokens, t.parents, np.array([7, 0, 0]))
        assert path == [0] and acc == 0 and committed == [7]


# ---------------- real-q rejection sampling (property gates) ----------------

class TestRealQRejectionSampling:
    def _law(self, rng, logits, temp, draw_draft, q_of, n=6000, tol=0.05):
        """TV distance between the first committed token's empirical
        law and the target p — drafts drawn fresh per trial."""
        p = _softmax(logits[0] / temp)
        counts = np.zeros(p.size)
        for _ in range(n):
            x = draw_draft()
            toks, _ = rejection_sample_tokens(
                logits, [x], temp, rng, q=q_of(x))
            counts[toks[0]] += 1
        return 0.5 * np.abs(counts / n - p).sum()

    def test_broad_q_matches_plain_law(self):
        rng = np.random.default_rng(0)
        V, temp = 10, 0.9
        logits = rng.normal(size=(2, V)) * 2.0
        q = np.full((1, V), 1.0 / V)           # broad: uniform proposer
        tv = self._law(rng, logits, temp,
                       lambda: int(rng.integers(V)), lambda x: q)
        assert tv < 0.05, tv

    def test_narrow_q_matches_plain_law(self):
        rng = np.random.default_rng(1)
        V, temp = 10, 0.9
        logits = rng.normal(size=(2, V)) * 2.0
        qrow = _softmax(rng.normal(size=V) * 6.0)   # near point mass
        q = qrow[None]
        tv = self._law(rng, logits, temp,
                       lambda: int(rng.choice(V, p=qrow)),
                       lambda x: q)
        assert tv < 0.05, tv

    def test_mismatched_support_q_matches_plain_law(self):
        # the proposer only ever draws from the LOW half of the vocab
        # while p concentrates on the high half — committed law must
        # still be exactly p (heavy rejection, corrected residual)
        rng = np.random.default_rng(2)
        V, temp = 10, 0.8
        logits = np.zeros((2, V))
        logits[0, V // 2:] = 3.0
        qrow = np.zeros(V)
        qrow[:V // 2] = 2.0 / V
        q = qrow[None]
        tv = self._law(rng, logits, temp,
                       lambda: int(rng.choice(V, p=qrow)),
                       lambda x: q)
        assert tv < 0.05, tv

    def test_zero_q_mass_with_target_mass_accepts(self):
        # q(x) = 0 but p(x) > 0: min(1, p/q) -> 1 in the limit — the
        # draft must be accepted with certainty, never div-by-zero
        rng = np.random.default_rng(3)
        V = 6
        logits = np.zeros((2, V))
        q = np.zeros((1, V))
        q[0, 0] = 1.0                          # all q mass elsewhere
        toks, acc = rejection_sample_tokens(
            logits, [3], 1.0, rng, q=q)
        assert acc == 1 and toks[0] == 3

    def test_zero_q_zero_p_rejects_and_never_commits_x(self):
        rng = np.random.default_rng(4)
        V = 6
        logits = np.full((2, V), 0.0)
        logits[0, 5] = -1e9                     # p(5) ~ 0
        q = np.zeros((1, V))
        q[0, 0] = 1.0                           # q(5) = 0 too
        for _ in range(50):
            toks, acc = rejection_sample_tokens(
                logits, [5], 1.0, rng, q=q)
            assert acc == 0 and toks[0] != 5

    def test_p_equals_q_always_accepts(self):
        rng = np.random.default_rng(5)
        V, temp = 8, 1.0
        logits = rng.normal(size=(2, V))
        q = _softmax(logits[0] / temp)[None]
        for _ in range(50):
            x = int(rng.choice(V, p=q[0]))
            toks, acc = rejection_sample_tokens(
                logits, [x], temp, rng, q=q)
            assert acc == 1 and toks[0] == x

    def test_q_must_cover_drafts(self):
        rng = np.random.default_rng(6)
        with pytest.raises(ValueError, match="cover"):
            rejection_sample_tokens(np.zeros((3, 8)), [1, 2], 0.7, rng,
                                    q=np.full((1, 8), 0.125))

    def test_temperature0_ignores_q(self):
        rng = np.random.default_rng(7)
        logits = rng.normal(size=(3, 8))
        targets = np.argmax(logits, axis=-1)
        toks, acc = rejection_sample_tokens(
            logits, [int(targets[0]), 5], 0.0, rng,
            q=np.full((2, 8), 0.125))
        assert toks[:1] == [int(targets[0])]
        assert acc == longest_accepted_prefix(
            np.array([targets[0], 5]), targets[:2])


class TestTreeRejectionSampling:
    def test_temp0_equals_greedy_walk(self):
        rng = np.random.default_rng(0)
        t = build_comb_tree(5, [3, 4], [[6], [7]])
        logits = rng.normal(size=(5, 12))
        assert tree_rejection_sample(
            t.tokens, t.parents, logits, 0.0, rng
        ) == longest_accepted_path(
            t.tokens, t.parents, np.argmax(logits, axis=-1))

    def test_first_committed_token_law(self):
        # width-2 tree at the root: accept child A with p(a), then B
        # from the residual, else the final residual — the committed
        # first token must be distributed exactly as p
        rng = np.random.default_rng(1)
        V, temp, n = 10, 0.9, 6000
        logits = rng.normal(size=(3, V)) * 2.0
        t = TreeDraft([5, 2, 7], [-1, 0, 0])
        p = _softmax(logits[0] / temp)
        counts = np.zeros(V)
        for _ in range(n):
            _, committed, _ = tree_rejection_sample(
                t.tokens, t.parents, logits, temp, rng)
            counts[committed[0]] += 1
        tv = 0.5 * np.abs(counts / n - p).sum()
        assert tv < 0.05, tv

    def test_fuzz_commit_shape_and_path_consistency(self):
        rng = np.random.default_rng(2)
        for _ in range(40):
            w, d = int(rng.integers(1, 4)), int(rng.integers(1, 4))
            chain = rng.integers(3, 30, size=d)
            sibs = [rng.integers(3, 30, size=w - 1) for _ in range(d)]
            t = build_comb_tree(int(rng.integers(3, 30)), chain, sibs)
            logits = rng.normal(size=(t.tokens.size, 32))
            temp = float(rng.choice([0.0, 0.7, 1.2]))
            path, committed, acc = tree_rejection_sample(
                t.tokens, t.parents, logits, temp, rng)
            assert len(committed) == acc + 1 == len(path)
            assert path[0] == 0
            for prev, v in zip(path, path[1:]):
                assert t.parents[v] == prev     # a root path
            # accepted tokens are the path nodes' tokens
            np.testing.assert_array_equal(
                committed[:acc], t.tokens[path[1:]])


# ---------------- kernel tree-mask path ----------------

class TestKernelTreeMask:
    def _shapes(self, seed=0, quant=False):
        rs = np.random.RandomState(seed)
        B, T, H, HK, D, W = 2, 5, 4, 2, 8, 32
        q = rs.randn(B, T, H, D).astype(np.float32)
        kst = rs.randint(0, W - T, (B,)).astype(np.int32)
        if quant:
            ck = rs.randint(-90, 90, (B, W, HK, D)).astype(np.int8)
            cv = rs.randint(-90, 90, (B, W, HK, D)).astype(np.int8)
            rows = dict(k_rows=rs.rand(B, W, HK).astype(np.float32)
                        + 0.5,
                        v_rows=rs.rand(B, W, HK).astype(np.float32)
                        + 0.5)
        else:
            ck = rs.randn(B, W, HK, D).astype(np.float32)
            cv = rs.randn(B, W, HK, D).astype(np.float32)
            rows = {}
        return (B, T, W), q, ck, cv, kst, rows

    def test_chain_tree_bitwise_equals_causal_kernel(self):
        """A pure-chain ancestor matrix IS the causal mask — through
        the Pallas kernel the tree path must reproduce the plain path
        BIT-identically (same kernel, same blocking, only the mask
        predicate differs)."""
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import serving_fused as sf
        (B, T, W), q, ck, cv, kst, _ = self._shapes()
        tm = np.broadcast_to(np.tril(np.ones((T, T), bool)), (B, T, T))
        fa.set_interpret(True)
        try:
            plain = sf.flash_chunk_attention_kernel(q, ck, cv, W, kst)
            tree = sf.flash_chunk_attention_kernel(q, ck, cv, W, kst,
                                                   tree_mask=tm)
        finally:
            fa.set_interpret(False)
        np.testing.assert_array_equal(np.asarray(plain),
                                      np.asarray(tree))

    @pytest.mark.parametrize("quant", [False, True],
                             ids=["fp", "int8rows"])
    def test_tree_kernel_matches_lax_reference(self, quant):
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import serving_fused as sf
        (B, T, W), q, ck, cv, kst, rows = self._shapes(quant=quant)
        t = build_comb_tree(5, [1, 2], [[3], [4]])   # 5 nodes = T
        tm = np.broadcast_to(tree_ancestor_matrix(t.parents), (B, T, T))
        ref = sf.flash_chunk_attention_reference(
            q, ck, cv, W, kst, tree_mask=tm, **rows)
        fa.set_interpret(True)
        try:
            ker = sf.flash_chunk_attention_kernel(
                q, ck, cv, W, kst, tree_mask=tm, **rows)
        finally:
            fa.set_interpret(False)
        # int8 rows dequant to O(100) magnitudes: gate on relative error
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4 if quant else 1e-5)

    def test_tree_mask_capped_at_32_nodes(self):
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import serving_fused as sf
        rs = np.random.RandomState(0)
        B, T, D, W = 1, 33, 8, 64
        q = rs.randn(B, T, 2, D).astype(np.float32)
        ck = rs.randn(B, W, 2, D).astype(np.float32)
        tm = np.broadcast_to(np.tril(np.ones((T, T), bool)), (B, T, T))
        fa.set_interpret(True)
        try:
            with pytest.raises(ValueError, match="32"):
                sf.flash_chunk_attention_kernel(
                    q, ck, ck, W, np.zeros((B,), np.int32),
                    tree_mask=tm)
        finally:
            fa.set_interpret(False)


# ---------------- engine token identity ----------------

class TestEngineIdentity:
    def test_draft_linear_greedy_matches_plain_and_accepts(self):
        cfg, params = _setup()
        params = _aligned(params)
        prompts = _prompts(cfg, [5, 9, 7], seed=7)
        ref = ContinuousBatchingEngine(params, cfg, **ENG).generate(
            prompts, max_new_tokens=10)
        eng = ContinuousBatchingEngine(params, cfg, spec_k=3,
                                       draft_layers=1, **ENG)
        got = eng.generate(prompts, max_new_tokens=10)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        # the aligned draft tracks the target: the ISSUE 20 acceptance
        # bar (> 0.3) must clear on a non-repetitive workload
        assert eng.spec.acceptance_rate > 0.3
        assert eng.spec.verify_steps > 0

    @pytest.mark.parametrize("kv", [None, "int8"], ids=["fp", "int8"])
    def test_tree_greedy_matches_plain(self, kv):
        cfg, params = _setup()
        params = _aligned(params)
        prompts = _prompts(cfg, [5, 9, 7], seed=7)
        kw = dict(ENG, kv_cache_dtype=kv)
        ref = ContinuousBatchingEngine(params, cfg, **kw).generate(
            prompts, max_new_tokens=10)
        eng = ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                       spec_tree=(2, 3), **kw)
        got = eng.generate(prompts, max_new_tokens=10)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert eng.spec.verify_steps > 0
        assert eng.draft_cache.allocator.num_used == 0

    def test_unaligned_tree_still_token_identical(self):
        # a draft that tracks NOTHING (raw random weights) must cost
        # only speed — identity is unconditional
        cfg, params = _setup()
        prompts = _prompts(cfg, [6, 4], seed=9)
        ref = ContinuousBatchingEngine(params, cfg, **ENG).generate(
            prompts, max_new_tokens=8)
        eng = ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                       spec_tree=(2, 2), **ENG)
        got = eng.generate(prompts, max_new_tokens=8)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_sampled_tree_runs_and_draft_pool_balanced(self):
        cfg, params = _setup()
        prompts = _prompts(cfg, [5, 9, 7], seed=7)
        eng = ContinuousBatchingEngine(
            params, cfg, draft_layers=1, spec_tree=(2, 2),
            temperature=0.8, key=jax.random.key(11), **ENG)
        out = eng.generate(prompts, max_new_tokens=10)
        assert all(len(o) > len(p) for o, p in zip(out, prompts))
        assert eng.draft_cache.allocator.num_used == 0
        assert not eng.draft_cache.active.any()

    def test_spec_tree_requires_draft_model(self):
        cfg, params = _setup()
        with pytest.raises(ValueError, match="draft_layers"):
            ContinuousBatchingEngine(params, cfg, spec_tree=(2, 2),
                                     **ENG)

    def test_tree_node_cap(self):
        cfg, params = _setup()
        with pytest.raises(ValueError, match="32"):
            ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                     spec_tree=(8, 4), **ENG)

    def test_spec_k_conflicting_with_tree_depth_rejected(self):
        cfg, params = _setup()
        with pytest.raises(ValueError, match="conflicts"):
            ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                     spec_k=5, spec_tree=(2, 2), **ENG)

    def test_stats_report_draft_identity(self):
        cfg, params = _setup()
        eng = ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                       spec_tree=(2, 2), **ENG)
        s = eng.stats()
        assert s["draft_layers"] == 1
        assert (s["tree_width"], s["tree_depth"]) == (2, 2)


class TestEngineIdentityHeavy:
    """tp x int8 x sampled x overlap tree parity — the slow tier
    (ISSUE 20 satellite: heavy variants ride `-m slow`)."""

    @pytest.mark.slow
    def test_tp2_tree_greedy_matches_single_chip(self):
        from paddle_tpu.distributed.mesh import serving_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        cfg, params = _setup()
        params = _aligned(params)
        prompts = _prompts(cfg, [5, 9, 7], seed=7)
        ref = ContinuousBatchingEngine(params, cfg, **ENG).generate(
            prompts, max_new_tokens=10)
        eng = ContinuousBatchingEngine(
            params, cfg, draft_layers=1, spec_tree=(2, 3),
            mesh=serving_mesh(2), **ENG)
        got = eng.generate(prompts, max_new_tokens=10)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.slow
    def test_tp2_int8_sampled_tree_runs_balanced(self):
        from paddle_tpu.distributed.mesh import serving_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        cfg, params = _setup()
        prompts = _prompts(cfg, [5, 9], seed=3)
        eng = ContinuousBatchingEngine(
            params, cfg, draft_layers=1, spec_tree=(2, 2),
            kv_cache_dtype="int8", temperature=0.7,
            key=jax.random.key(5), mesh=serving_mesh(2), **ENG)
        out = eng.generate(prompts, max_new_tokens=8)
        assert all(len(o) > len(p) for o, p in zip(out, prompts))
        assert eng.draft_cache.allocator.num_used == 0

    @pytest.mark.slow
    def test_overlap_int8_tree_scheduler_identity(self):
        cfg, params = _setup()
        params = _aligned(params)
        prompts = _prompts(cfg, [5, 9, 7], seed=7)
        new = 10
        kw = dict(ENG, kv_cache_dtype="int8")
        ref = ContinuousBatchingEngine(params, cfg, **kw).generate(
            prompts, max_new_tokens=new)
        eng = ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                       spec_tree=(2, 3), overlap=True,
                                       **kw)
        sched = ServingScheduler(eng)
        reqs = [sched.submit(p, max_new_tokens=new) for p in prompts]
        while sched.step():
            pass
        for p, full, r in zip(prompts, ref, reqs):
            np.testing.assert_array_equal(
                np.asarray(full)[len(p):], r.tokens)


# ---------------- budget + scheduler integration ----------------

class TestBudgetTreeTrim:
    def test_budget_trims_leaves_never_root_path(self):
        """With 3 rows of (2, 3) trees a 10-token budget cannot seat
        every node (3 x 7 > 10): the planner must trim tree WIDTH via
        the leading-slice contract — chain-first order sheds sibling
        leaves / chain tail — while every executed step stays within
        budget and the run stays token-identical."""
        cfg, params = _setup()
        params = _aligned(params)
        prompts = _prompts(cfg, [5, 9, 7], seed=7)
        new = 10
        ref = ContinuousBatchingEngine(params, cfg, **ENG).generate(
            prompts, max_new_tokens=new)
        budget = 10
        eng = ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                       spec_tree=(2, 3), **ENG)
        sched = ServingScheduler(eng, token_budget=budget)
        reqs = [sched.submit(p, max_new_tokens=new) for p in prompts]
        trimmed = False
        while sched.step():
            plan = sched.last_plan
            assert plan.scheduled_tokens <= budget
            for k in (plan.spec_drafts or {}).values():
                trimmed = trimmed or 0 < k < 6
        assert trimmed, "budget never actually trimmed a tree"
        for p, full, r in zip(prompts, ref, reqs):
            np.testing.assert_array_equal(
                np.asarray(full)[len(p):], r.tokens)

    def test_unbudgeted_scheduler_tree_identity(self):
        cfg, params = _setup()
        params = _aligned(params)
        prompts = _prompts(cfg, [5, 9, 7], seed=7)
        new = 10
        ref = ContinuousBatchingEngine(params, cfg, **ENG).generate(
            prompts, max_new_tokens=new)
        eng = ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                       spec_tree=(2, 3), **ENG)
        sched = ServingScheduler(eng)
        reqs = [sched.submit(p, max_new_tokens=new) for p in prompts]
        while sched.step():
            pass
        for p, full, r in zip(prompts, ref, reqs):
            np.testing.assert_array_equal(
                np.asarray(full)[len(p):], r.tokens)


# ---------------- draft-pool lifecycle ----------------

class TestDraftPoolLifecycle:
    def test_preemption_frees_draft_pages_token_identical(self):
        """HIGH admissions preempt draft-holding LOW rows: the draft
        pool must release the victim's pages (its state is disposable
        — the catch-up forward refills on resume) and every stream
        still matches plain decode."""
        cfg, params = _setup()
        params = _aligned(params)
        prompts = _prompts(cfg, [6, 7, 5, 4], seed=5)
        new = 8
        plain = ContinuousBatchingEngine(params, cfg, **ENG)
        ref = plain.generate(prompts, max_new_tokens=new)
        eng = ContinuousBatchingEngine(
            params, cfg, draft_layers=1, spec_tree=(2, 2), max_batch=2,
            page_size=8, max_len=32, host_tier=True)
        sched = ServingScheduler(eng)
        reqs = [sched.submit(p, max_new_tokens=new, priority=Priority.LOW)
                for p in prompts[:3]]
        for _ in range(4):
            sched.step()
        reqs.append(sched.submit(prompts[3], max_new_tokens=new,
                                 priority=Priority.HIGH))
        while sched.step():
            pass
        for p, full, r in zip(prompts, ref, reqs):
            np.testing.assert_array_equal(
                np.asarray(full)[len(p):], r.tokens)
        assert eng.draft_cache.allocator.num_used == 0
        st = eng.draft_cache.allocator.stats()
        assert st["allocs_total"] == st["frees_total"]

    def test_draft_pool_exhaustion_degrades_to_plain_decode(self):
        """A draft pool too small to admit anyone must not break
        anything: rows silently skip drafting (PoolExhausted at the
        lazy admit) and the run is plain paged decode, token-identical."""
        cfg, params = _setup()
        prompts = _prompts(cfg, [6, 4], seed=3)
        ref = ContinuousBatchingEngine(params, cfg, **ENG).generate(
            prompts, max_new_tokens=8)
        eng = ContinuousBatchingEngine(params, cfg, draft_layers=1,
                                       spec_tree=(2, 2), draft_pages=2,
                                       **ENG)
        got = eng.generate(prompts, max_new_tokens=8)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert eng.spec.verify_steps == 0      # nobody ever drafted
        assert eng.draft_cache.allocator.num_used == 0


# ---------------- resilience: crash + identity validation ----------------

class TestTreeRecovery:
    def test_kill_mid_tree_verify_recovers_token_identical(self):
        """The ISSUE 20 crash gate: simulated kill -9 at the
        tree_verify site (armed BEFORE the verify launches), recovery
        from the journal alone — the draft pool rebuilds cold and
        every acked request finishes exactly its uninterrupted stream
        (run_crash_sweep raises SoakError on any violation; the full
        every-site sweep in test_wal.py covers draft_propose too)."""
        import tools.chaos_soak as soak
        rep = soak.run_crash_sweep(sites=["tree_verify"])
        assert rep["sites"]["tree_verify"]["deaths"] >= 1
        assert rep["sites"]["tree_verify"]["fired"] >= 1

    def test_recovery_rejects_draft_identity_mismatch(self):
        """The journal records the DRAFT IDENTITY (draft_layers +
        tree shape), not draft state: a recovery factory that builds a
        different draft cannot silently re-speculate differently — it
        must be refused."""
        import tempfile
        from paddle_tpu.serving import EngineSupervisor

        cfg, params = _setup()

        def tree_factory():
            return ContinuousBatchingEngine(
                params, cfg, draft_layers=1, spec_tree=(2, 2), **ENG)

        def plain_factory():
            return ContinuousBatchingEngine(params, cfg, **ENG)

        wd = tempfile.mkdtemp(prefix="tree_wal_")
        kw = dict(backoff_s=0.0, sleep=lambda s: None,
                  checkpoint_every=4, wal_kw=dict(group_interval_s=0.0))
        sup = EngineSupervisor(tree_factory, wal_dir=wd, **kw)
        sup.submit(_prompts(cfg, [5], seed=1)[0], max_new_tokens=4)
        while sup.step():
            pass
        with pytest.raises(ValueError, match="draft"):
            EngineSupervisor.recover_from_disk(plain_factory, wd, **kw)
        # the matching factory is accepted
        sup2 = EngineSupervisor.recover_from_disk(tree_factory, wd, **kw)
        assert sup2.engine.draft_layers == 1


# ---------------- synth_trace text mode ----------------

class TestSynthTraceTextMode:
    KW = dict(duration_s=2.0, base_rps=6.0, tenants=2, page_size=8,
              prefix_pages=2, vocab=512, tail_tokens=(4, 12))

    def test_prompts_are_non_repetitive(self):
        trace = synth_trace(3, text=True, **self.KW)
        assert trace
        prop = NgramProposer(ngram_max=3)
        for tr in trace:
            p = np.asarray(tr.prompt)
            # sampled WITHOUT replacement: no token repeats, so no
            # n-gram (not even a 1-gram) ever recurs in-context
            assert np.unique(p).size == p.size
            assert prop.propose(p, 4).size == 0

    def test_deterministic_and_distinct_from_default_mode(self):
        a = synth_trace(3, text=True, **self.KW)
        b = synth_trace(3, text=True, **self.KW)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x.prompt, y.prompt)
        c = synth_trace(3, text=False, **self.KW)
        assert any(not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))

    def test_small_vocab_rejected(self):
        with pytest.raises(ValueError, match="vocab"):
            synth_trace(3, text=True, **dict(self.KW, vocab=20))

    def test_tenant_prefix_sharing_survives(self):
        # same tenant -> same system prefix (the prefix-cache workload
        # contract the default mode has) even in text mode
        trace = synth_trace(4, text=True, **self.KW)
        plen = self.KW["prefix_pages"] * self.KW["page_size"]
        by_tenant = {}
        for tr in trace:
            head = np.asarray(tr.prompt[:plen])
            if tr.tenant in by_tenant:
                np.testing.assert_array_equal(by_tenant[tr.tenant], head)
            else:
                by_tenant[tr.tenant] = head


# ---------------- AOT lowering ----------------

class TestTreeLowering:
    def test_serving_treespec_programs_lower_for_tpu(self):
        """tools/aot_validate --config serving-treespec from the test
        tier: the tree-masked flash kernel (fp + int8 rows), the
        one-forward tree verify (fp + int8-KV pool), the draft-model
        decode step and the tree commit must all export for the TPU
        platform, kernels via Mosaic tpu_custom_call."""
        import tools.aot_validate as av
        rep = av.validate_serving_treespec(1)
        assert all(rep["lowered"].values()), rep["lowered"]
