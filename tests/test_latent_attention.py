"""Latent attention (MLA) on the serving path: a paged pool of latents, the
absorbed decode kernel and chunk attention, a leading dense layer, and
sigmoid-routed experts with a share held here, against the plain reference
``chipbench/reference/kimi_k2.py`` (the EXPANDED form only).

Small sizes, the CPU, seeded weights, float32 on both sides. The reference
makes keys and values by head from the latents; the program's serving paths
take the queries into the latent instead (``q^nope W_UK^T`` before the
scores, ``W_UV`` after the values), which is the same function with its
float32 sums in another order, and the kernel's online softmax reorders
them again. The tolerance on logits, 2e-4 absolute at logits of order 1, is
some fifty times the largest difference seen over the cases here (4e-6) and
a hundredth of what bfloat16 in the latent gives (2e-2 and more)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.archs import kimi_k2 as arch
from chipbench.reference import kimi_k2 as reference
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate as gen, latent, llama
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_latent_attention as pla
from paddle_tpu.serving import PagedKVCache, Priority, ServingScheduler

TOL = 2e-4

#: the configuration file's keys at a small size: a leading dense layer and
#: two expert layers holding experts 4..7 of 16
SMALL = {
    "model_type": "kimi_k2", "num_hidden_layers": 3, "hidden_size": 64,
    "intermediate_size": 96, "vocab_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 24, "n_shared_experts": 1,
    "router_outputs": 16, "n_routed_experts": 4, "first_expert_held": 4,
    "num_experts_per_tok": 6, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "rope_theta": 50000,
    "rope_scaling": {"type": "yarn", "factor": 32, "mscale": 1,
                     "mscale_all_dim": 1, "beta_fast": 1, "beta_slow": 1,
                     "original_max_position_embeddings": 16},
    "tie_word_embeddings": False}


def _model(c=SMALL, seed=0, max_len=64):
    cfg = dataclasses.replace(arch.program_config(c, max_len, remat=False),
                              dtype=jnp.float32)
    return cfg, arch.weights(jax.random.key(seed), c, dtype=jnp.float32)


def _tokens(n, seed=1, vocab=SMALL["vocab_size"]):
    return np.random.default_rng(seed).integers(3, vocab, (n,)).astype(
        np.int32)


def _ref_logits(params, tokens, c=SMALL):
    x = reference.hidden(params, jnp.asarray(tokens), c, q_block=16,
                         t_block=16)
    return np.asarray(reference.logits(params, x, c))


# ---- the model ----
@pytest.mark.parametrize("lead", [0, 1, 2])
def test_no_cache_forward_agrees_with_the_reference(lead):
    """``first_k_dense_replace`` 0, 1 and 2: the prologue of dense layers
    before the scan over the expert layers."""
    c = dict(SMALL, first_k_dense_replace=lead)
    cfg, params = _model(c)
    assert cfg.dense_layers == lead
    assert ("dense_layers" in params) == bool(lead)
    toks = _tokens(37)
    got = np.asarray(llama.forward(params, jnp.asarray(toks)[None], cfg))[0]
    np.testing.assert_allclose(got, _ref_logits(params, toks, c), atol=TOL,
                               rtol=0)


def test_the_config_says_what_the_cache_keeps():
    cfg, _ = _model()
    la = cfg.latent
    assert cfg.cache_layers() == {"latent": 3}
    assert (la.row, la.row_lanes, la.qk_dim) == (40, 128, 24)
    m = 0.1 * np.log(32) + 1
    np.testing.assert_allclose(la.scale, 24 ** -0.5 * m * m, rtol=1e-6)
    pool = gen.init_paged_cache(cfg, 9, 4)
    assert set(pool) == {"c"} and pool["c"].shape == (3, 9, 4, 128)
    pool = gen.init_paged_cache(cfg, 9, 4, kv_dtype="int8")
    assert pool["c"].dtype == jnp.int8 and pool["cs"].shape == (3, 9, 4, 2)
    # the published widths: 576 numbers a token a layer, in 640 lanes
    big = llama.LatentConfig(q_rank=1536, kv_rank=512, nope_dim=128,
                             rope_dim=64, v_dim=128)
    assert (big.row, big.row_lanes) == (576, 640)


def test_the_rotary_table_is_the_rotary_keys_width():
    """``rope_tables`` at the rotary key's width, not a head's: the ramp
    of beta_fast = beta_slow = 1 lies between two frequency pairs."""
    cfg, _ = _model()
    cos, sin = llama.rope_tables_by_kind(cfg, 20)["latent"]
    assert cos.shape == (20, cfg.latent.rope_dim // 2)
    inv, mult = reference.rotary(SMALL)
    ang = np.arange(20)[:, None] * inv[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang) * mult, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang) * mult, atol=1e-5)


def test_the_absorbed_form_is_the_expanded_one():
    """One layer's attention: the queries taken into the latent and the
    result taken back out, against keys and values made by head."""
    cfg, params = _model()
    la, nh = cfg.latent, cfg.num_heads
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    S = 21
    u = jax.random.normal(jax.random.key(2), (1, S, cfg.hidden_size))
    cos, sin = latent._rope(cfg, S)
    rpos = jnp.arange(S, dtype=jnp.int32)[None]
    q_nope, q_rope, rows = latent._project(u, lp, cfg, cos, sin, rpos)
    w = latent._kvb(lp, cfg, u.dtype)
    # expanded
    kv = jnp.einsum("bsr,rhd->bshd", rows[..., :la.kv_rank], w)
    k = jnp.concatenate([kv[..., :la.nope_dim], jnp.broadcast_to(
        rows[:, :, None, la.kv_rank:], (1, S, nh, la.rope_dim))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", jnp.concatenate([q_nope, q_rope], -1),
                   k) * la.scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., la.nope_dim:])
    # absorbed, over the rows as the pool lays them out
    ctx = jnp.pad(rows[0], ((0, 0), (0, la.row_lanes - la.row)))
    o = latent.latent_chunk_attention(
        latent._absorb(q_nope, q_rope, w, cfg)[0], ctx, 0, la.scale,
        la.kv_rank)
    got = latent._unabsorb(o[None], w, cfg)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(1, S, -1),
                               atol=2e-5, rtol=0)


def test_the_trainer_refuses_by_name():
    cfg, params = _model()
    with pytest.raises(ValueError, match="training a latent-attention"):
        llama.loss_fn(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(ValueError, match="num_params"):
        cfg.num_params()


@pytest.mark.parametrize("bad", [
    dict(layer_pattern=("full",)), dict(layer_pattern=None),
    dict(dense_layers=3), dict(dense_layers=1, moe=None)])
def test_the_config_refuses_what_it_cannot_mean(bad):
    cfg, _ = _model()
    with pytest.raises(ValueError, match="latent|dense_layers"):
        dataclasses.replace(cfg, **bad)
    with pytest.raises(ValueError, match="latent"):
        llama.LlamaConfig.tiny(layer_pattern=("latent",))
    with pytest.raises(ValueError, match="dense_layers"):
        llama.LlamaConfig.tiny(dense_layers=1)


# ---- the kernel ----
@pytest.mark.parametrize("lengths", [(1, 9, 40), (33, 64, 17), (0, 5, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_agrees_with_its_twin_at_ragged_lengths(lengths, dtype):
    """The Pallas kernel, interpreted, against the jnp twin: rows of other
    lengths, one of them past a whole group of pages, one empty; tables
    that point into another layer's pages."""
    B, H, R, D, page, ppseq, P = 3, 4, 32, 128, 4, 16, 60
    k = jax.random.split(jax.random.key(sum(lengths)), 3)
    q = jax.random.normal(k[0], (B, H, D)).astype(dtype)
    pool = jax.random.normal(k[1], (P, page, D)).astype(dtype)
    pool = pool.at[..., 40:].set(0)
    tables = jax.random.permutation(k[2], P - 1)[:B * ppseq].reshape(
        B, ppseq).astype(jnp.int32) + 1
    ln = jnp.asarray(lengths, jnp.int32)
    want = pla.paged_latent_attention_reference(
        q, pool, tables, ln, scale=0.2, value_dim=R)
    fa.set_interpret(True)
    try:
        got = pla.paged_latent_attention(q, pool, tables, ln, scale=0.2,
                                         value_dim=R, use_kernel=True)
    finally:
        fa.set_interpret(False)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    if 0 in lengths:
        assert not np.asarray(got, np.float32)[lengths.index(0)].any()


def test_the_int8_tier_goes_through_the_twin():
    """A token's latent and its key are scaled apart; the dequantised rows
    give the float rows' result to the int8 step."""
    B, H, R, D, page = 2, 4, 32, 128, 4
    k = jax.random.split(jax.random.key(7), 3)
    rows = jax.random.normal(k[0], (24, 40)) * jnp.where(
        jnp.arange(40) < R, 1.0, 30.0)          # a key far larger
    held = {"c": jnp.zeros((6, page, D), jnp.int8),
            "cs": jnp.zeros((6, page, 2), jnp.float32)}
    held = latent._write_rows(held, rows, jnp.arange(24), R)
    plain = latent._write_rows({"c": jnp.zeros((6, page, D))}, rows,
                               jnp.arange(24), R)
    q = jax.random.normal(k[1], (B, H, D))
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    ln = jnp.asarray([11, 12], jnp.int32)
    want = pla.paged_latent_attention(q, plain["c"], tables, ln, scale=0.05,
                                      value_dim=R)
    got = pla.paged_latent_attention(q, held["c"], tables, ln, scale=0.05,
                                     value_dim=R, scales=held["cs"],
                                     use_kernel=True)
    err = float(jnp.abs(got - want).max())
    assert 1e-4 < err < 0.1, err
    # one scale for the whole row would lose the latent beside the key
    lat = held["c"][..., :R].astype(jnp.float32) * held["cs"][..., :1]
    np.testing.assert_allclose(np.asarray(lat).reshape(24, R),
                               np.asarray(rows[:, :R]), atol=0.02)


# ---- the serving programs ----
def _prefill(params, cfg, cache, slot, prompt, chunk, page, done=0):
    table = jnp.asarray(cache.block_tables[slot])
    pool = cache.pool
    while done < prompt.size:
        take = min(chunk, prompt.size - done)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :take] = prompt[done:done + take]
        lg, pool = gen.paged_prefill_chunk(
            params, jnp.asarray(toks), pool, table, cfg,
            ctx_cap=cache.ctx_cap_pages(cache.pages_for(done)) * page,
            ctx_len=done, chunk_len=take)
        done += take
    return np.asarray(lg)[0], pool


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("chunk", [8, 12, 20, 32])
def test_chunked_prefill_then_decode_agree_with_the_reference(chunk, kernel):
    """Chunks that do (8, 32) and do not (12, 20) divide the prompt of 32;
    then six decode steps, through the jnp twin and through the kernel."""
    cfg, params = _model()
    page = 4
    seq = _tokens(38, seed=chunk)
    prompt, rest = seq[:32], seq[32:]
    want = _ref_logits(params, seq)
    cache = PagedKVCache(cfg, 2, 64, page_size=page)
    cache.admit(1, seq.size)
    first, pool = _prefill(params, cfg, cache, 1, prompt, chunk, page)
    out = [first]
    lengths = np.zeros((2,), np.int32)
    # one compiled program, as the engine runs it (a kernel interpreted
    # op by op inside an eager scan leaves XLA:CPU's later compiles in the
    # same process unsound)
    step = jax.jit(lambda last, pool, tables, lengths: (
        gen.paged_decode_forward(
            params, last, pool, tables, lengths, cfg,
            active=jnp.asarray([False, True]), use_kernel=kernel or None)))
    fa.set_interpret(kernel)
    try:
        for i, tok in enumerate(rest):
            lengths[1] = prompt.size + i
            lg, pool = step(jnp.asarray([0, tok]), pool,
                            jnp.asarray(cache.block_tables.copy()),
                            jnp.asarray(lengths.copy()))
            out.append(np.asarray(lg)[1])
    finally:
        fa.set_interpret(False)
    np.testing.assert_allclose(np.stack(out), want[31:], atol=TOL, rtol=0)
    # the row that was not active wrote to its layer's trash page alone
    used = np.asarray(cache.block_tables[1][:cache.pages_for(seq.size)])
    other = np.setdiff1d(np.arange(1, cache.num_pages), used)
    assert not np.asarray(pool["c"][:, other]).any()


@pytest.mark.parametrize("prefill_chunk", [8, 12])
def test_engine_greedy_tokens_lie_on_the_reference(prefill_chunk):
    """The engine and scheduler end to end: every served token is the
    reference's best at its position (its logit within TOL of the best)."""
    cfg, params = _model()
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=4,
                                   max_len=64, prefill_chunk=prefill_chunk)
    sched = ServingScheduler(eng)
    prompts = [_tokens(21, seed=5), _tokens(30, seed=6)]
    hs = [sched.submit(p, max_new_tokens=7) for p in prompts]
    sched.run()
    attended = 0
    for p, h in zip(prompts, hs):
        lg = _ref_logits(params, np.concatenate([p, h.tokens]))
        at = lg[p.size - 1:p.size - 1 + len(h.tokens)]
        gap = at.max(-1) - at[np.arange(len(h.tokens)), h.tokens]
        assert gap.max() <= TOL
        # a decode step of a row with n tokens cached attends over n + 1
        attended += sum(p.size + i + 1 for i in range(6))
    s = sched.stats()
    assert s["latent_chunk_tokens_total"] == 51
    assert s["latent_decode_rows_total"] == 12
    assert s["latent_tokens_attended_total"] == attended * 3
    assert s["latent_pool_bytes"] == eng.cache.pool["c"].size * 4
    assert s["latent_pool_used_peak"] == s["full_pool_used_peak"] > 0
    assert s["moe_items_elsewhere_total"] > s["moe_routed_items_total"] > 0
    # two expert layers a program: the dense layer counts in none
    programs = s["moe_layer_steps_total"] // 2
    assert s["moe_layer_steps_total"] == 2 * programs


def test_a_prefix_hit_on_latent_pages_gives_the_cold_logits():
    """The second prompt starts with the first's 19 tokens: four whole
    pages mapped from the trie and the partial one's three rows copied on
    write. Its tokens are what a cold engine gives it, and only its own
    tail was prefilled."""
    cfg, params = _model()
    kw = dict(max_batch=2, page_size=4, max_len=64, prefill_chunk=8)
    a = _tokens(19, seed=20)
    b = np.concatenate([a, _tokens(9, seed=22)])
    cold = ContinuousBatchingEngine(params, cfg, **kw).generate(
        [b], max_new_tokens=6)[0]
    eng = ContinuousBatchingEngine(params, cfg, **kw)
    eng.generate([a], max_new_tokens=3)
    before = eng.stats()["latent_chunk_tokens_total"]
    np.testing.assert_array_equal(eng.generate([b], max_new_tokens=6)[0],
                                  cold)
    assert eng.stats()["latent_chunk_tokens_total"] - before == b.size - 19
    assert eng.stats()["cow_copies"] == 1
    lg = _ref_logits(params, cold)
    at = lg[b.size - 1:-1]
    assert (at.max(-1) - at[np.arange(6), cold[b.size:]]).max() <= TOL


@pytest.mark.parametrize("at", ["mid_decode", "mid_prefill"])
def test_a_preempted_row_resumes_where_an_undisturbed_one_ends(at):
    cfg, params = _model()
    kw = dict(max_batch=1, page_size=4, max_len=64, prefill_chunk=8,
              enable_prefix_cache=False)
    p, new = _tokens(22, seed=9), 9
    ref = ContinuousBatchingEngine(params, cfg, **kw).generate(
        [p], max_new_tokens=new)[0]
    sched = ServingScheduler(ContinuousBatchingEngine(params, cfg, **kw))
    a = sched.submit(p, max_new_tokens=new, priority=Priority.LOW)
    if at == "mid_decode":
        while len(a.tokens) < 3:
            sched.step()
    else:
        sched.step()
        sched.step()
        assert sched.engine.pending_prefills()
    b = sched.submit(_tokens(9, seed=10), max_new_tokens=2,
                     priority=Priority.HIGH)
    sched.step()
    assert a.preemptions == 1 and a.slot is None
    sched.run()
    assert a.done and b.done
    np.testing.assert_array_equal(a.output, ref)


def test_the_lower_precision_tier_stays_inside_its_band():
    """What ``--plant control`` switches on: 8-bit attention projections,
    dense and shared FFN and head (the expert stacks stay), and the int8
    pool of latents. Its served tokens lie within 1.0 of the reference's
    best logit and, over 40 tokens, not on it: the tier is a different
    result, which the comparison has to see."""
    cfg, params = _model()
    eng = ContinuousBatchingEngine(
        params, cfg, max_batch=2, page_size=4, max_len=64, prefill_chunk=8,
        weight_bits=8, kv_cache_dtype="int8")
    for group, names in (("layers", ("wq_a", "wkv_b", "wo", "ws_g")),
                         ("dense_layers", ("wq_b", "wkv_a", "wg", "wd"))):
        for n in names:
            assert eng.params[group][n].dtype == jnp.int8
    assert eng.params["lm_head"].dtype == jnp.int8
    assert eng.params["layers"]["moe_wg"].dtype == jnp.float32
    assert eng.cache.pool["c"].dtype == jnp.int8
    prompts = [_tokens(17, seed=11), _tokens(23, seed=12)]
    outs = eng.generate(prompts, max_new_tokens=20)
    gaps = []
    for p, o in zip(prompts, outs):
        lg = _ref_logits(params, o)[p.size - 1:-1]
        gaps += list(lg.max(-1) - lg[np.arange(20), o[p.size:]])
    assert max(gaps) < 1.0
    assert np.mean(gaps) > 10 * TOL or max(gaps) > 10 * TOL


@pytest.mark.parametrize("walker", ["drain_restore", "export_import"])
def test_the_walkers_that_deal_in_whole_arrays_serve_a_latent_pool(walker):
    """Drain/restore of the prefix trie and the handoff's export/import
    copy a pool's arrays page by page whatever they are called: restored
    latent pages serve a prefix hit that gives the cold tokens, and an
    imported row's pages hold the exporter's bytes."""
    cfg, params = _model()
    kw = dict(max_batch=2, page_size=4, max_len=64, prefill_chunk=8)
    p = _tokens(19, seed=3)
    first = ContinuousBatchingEngine(params, cfg, **kw)
    if walker == "drain_restore":
        ref = first.generate([p], max_new_tokens=8)[0]
        fresh = ContinuousBatchingEngine(params, cfg, **kw)
        assert fresh.cache.restore_prefix(
            first.cache.checkpoint_prefix()) == 5
        np.testing.assert_array_equal(
            fresh.generate([p], max_new_tokens=8)[0], ref)
        # 16 tokens in four restored pages, three rows copied on write
        assert fresh.stats()["latent_chunk_tokens_total"] == 1
        return
    h = first.submit(p, max_new_tokens=8)
    while len(h.tokens) < 3:
        first.step()
    first.fence()
    src = first.cache
    payload = src.export_request(h.slot)
    assert sorted(payload["arrays"]) == ["c"]
    dst = PagedKVCache(cfg, 2, 64, page_size=4)
    table = dst.import_request(1, payload, p.size + 8)
    n = payload["num_pages"]
    np.testing.assert_array_equal(
        np.asarray(dst.pool["c"][:, table[:n]]),
        np.asarray(src.pool["c"][:, src.block_tables[h.slot][:n]]))


ENGINE_REFUSALS = {
    "host_tier": dict(host_tier=True),
    "speculative": dict(spec_k=2),
    "draft_layers": dict(draft_layers=1, spec_k=2),
    "fused": dict(fused=True),
    "adapters": dict(adapters=dict(slots=2, rank=4)),
}


@pytest.mark.parametrize("what", sorted(ENGINE_REFUSALS))
def test_the_engine_refuses_by_name(what):
    cfg, params = _model()
    kw = dict(max_batch=2, page_size=4, max_len=64)
    kw.update(ENGINE_REFUSALS[what])
    with pytest.raises(ValueError, match=f"{what}.*latent attention"):
        ContinuousBatchingEngine(params, cfg, **kw)


def test_a_mesh_is_refused_by_name():
    from paddle_tpu.distributed.mesh import serving_mesh
    cfg, params = _model()
    with pytest.raises(ValueError, match="mesh.*latent attention"):
        ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=4,
                                 max_len=64, mesh=serving_mesh(2))
    with pytest.raises(ValueError, match="mesh.*latent attention"):
        PagedKVCache(cfg, 2, 64, page_size=4, mesh=serving_mesh(2))
    with pytest.raises(ValueError, match="tp is not supported on a pool of "
                                         "latents"):
        gen.init_paged_cache(cfg, 9, 4, tp=2)
    with pytest.raises(ValueError, match="latent-attention config"):
        llama.validate_serving_mesh(cfg, 2)


@pytest.mark.parametrize("what", ["paged_prefill_insert",
                                  "paged_verify_forward", "init_cache",
                                  "make_draft_params"])
def test_the_programs_that_keep_heads_refuse_by_name(what):
    cfg, params = _model()
    pool = gen.init_paged_cache(cfg, 9, 4)
    toks, table = jnp.zeros((1, 8), jnp.int32), jnp.zeros((4,), jnp.int32)
    call = {
        "paged_prefill_insert": lambda: gen.paged_prefill_insert(
            params, toks, pool, table, cfg),
        "paged_verify_forward": lambda: gen.paged_verify_forward(
            params, toks, pool, table[None], jnp.zeros((1,), jnp.int32), cfg,
            ctx_cap=8),
        "init_cache": lambda: gen.init_cache(cfg, 1, 16),
        "make_draft_params": lambda: gen.make_draft_params(params, cfg, 1)}
    with pytest.raises(ValueError, match=f"{what}.*latent attention"):
        call[what]()
    for name in ("tp_axis", "dp_axis", "fused", "adapters"):
        with pytest.raises(ValueError, match=f"{name}.*latent"):
            gen.paged_decode_forward(
                params, jnp.zeros((1,), jnp.int32), pool, table[None],
                jnp.zeros((1,), jnp.int32), cfg, **{name: "x"})


# ---- the share and the model ----
def _expert_layer(c, seed=3):
    """One expert layer's leaves with ALL the router's experts held, and
    the input rows."""
    whole = dict(c, n_routed_experts=c["router_outputs"], first_expert_held=0,
                 first_k_dense_replace=0, num_hidden_layers=1)
    params = arch.weights(jax.random.key(seed), whole, dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(seed + 1), (2, 9, c["hidden_size"]))
    return whole, lp, x


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of the three-matrix sigmoid
    layer give, with the shared expert counted once, are the uncut
    reference's expert layer."""
    whole, lp, x = _expert_layer(SMALL)
    cfg = dataclasses.replace(
        arch.program_config(dict(whole), 64, remat=False), dtype=jnp.float32)
    stacks = tuple(lp[n][None] for n in reference.STACKS)
    want = np.asarray(reference.experts(x.reshape(18, -1), lp, stacks, 0,
                                        whole, 16))
    zero_shared = dict(lp, ws_d=jnp.zeros_like(lp["ws_d"]))
    E, held = whole["router_outputs"], SMALL["n_routed_experts"]
    total, items = 0.0, 0
    for first in range(0, E, held):
        mine = dict(zero_shared if first else lp,
                    first_expert=jnp.int32(first))
        y, stats = gen._moe_ffn(
            x, mine, cfg, layer=0,
            experts=tuple(a[:, first:first + held] for a in stacks))
        total = total + np.asarray(y).reshape(18, -1)
        items += int(stats[0])
        assert int(stats[0]) + int(stats[3]) == 18 * cfg.moe.top_k
    assert items == 18 * cfg.moe.top_k
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)


def test_a_held_share_lowers_to_no_collective():
    whole, lp, x = _expert_layer(SMALL)
    cfg = dataclasses.replace(
        arch.program_config(dict(whole), 64, remat=False), dtype=jnp.float32)
    mine = dict(lp, first_expert=jnp.int32(4))
    stacks = tuple(lp[n][None, 4:8] for n in reference.STACKS)
    text = jax.jit(lambda x: gen._moe_ffn(x, mine, cfg, experts=stacks)[0]
                   ).lower(x).as_text()
    for op in ("all_to_all", "all-to-all", "all_gather", "all-gather",
               "all_reduce", "all-reduce", "collective"):
        assert op not in text
    with pytest.raises(ValueError, match="share of the experts.*dp"):
        gen._moe_ffn(x, mine, cfg, experts=stacks, dp_axis="dp")


def test_the_selection_bias_selects_and_does_not_weigh():
    """A large bias on one expert puts it among every token's chosen; its
    weight is still its own sigmoid score's share."""
    whole, lp, x = _expert_layer(SMALL)
    xf = x.reshape(18, -1)
    idx0, w0 = gen._route(xf, lp["moe_gate"], jnp.zeros((16,)), 6, "sigmoid",
                          2.5)
    bias = jnp.zeros((16,)).at[11].set(10.0)
    idx1, w1 = gen._route(xf, lp["moe_gate"], bias, 6, "sigmoid", 2.5)
    assert (np.asarray(idx1)[:, 0] == 11).all()
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 2.5, rtol=1e-6)
    s = jax.nn.sigmoid(xf @ lp["moe_gate"])
    chosen = np.take_along_axis(np.asarray(s), np.asarray(idx1), -1)
    np.testing.assert_allclose(np.asarray(w1),
                               2.5 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    assert (np.asarray(idx0) != np.asarray(idx1)).any()
