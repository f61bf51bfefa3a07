"""A K/V decoder with gated attention and QK-norm, sliding layers with
rotary beside full layers without (NoPE), sandwich norms, an input scale, a
leading dense layer and sigmoid-routed experts with a share held here, on
the serving path, against the plain reference ``chipbench/reference/
afmoe.py`` (float32, no cache, no kernel), logits compared and not tokens.

Small sizes, the CPU, seeded weights, float32 on both sides. The program
sums in another order than the reference (its paged and chunk attention,
its grouped expert matmuls, its float32 router against the reference's at
``highest``): the tolerance on logits, 2e-4 absolute at logits of order 1
to 5, is some forty times the largest difference seen over the cases here
(5e-6) and a hundredth of what bfloat16 activations give (2e-2 and more)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.archs import afmoe as arch
from chipbench.reference import afmoe as reference
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate as gen, llama
from paddle_tpu.serving import PagedKVCache, Priority, ServingScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
PAGE = 4


def small(**over):
    """The published file at tiny widths: the layer list, the window's
    place in it, the dense prologue and every assumption key as the file
    has them; experts 4..7 of 16 held, a window of 8."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "trinity-large-preview.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=96, moe_intermediate_size=24,
             vocab_size=128, num_experts=4, router_outputs=16,
             num_experts_per_tok=4, first_expert_held=4, sliding_window=8)
    c.update(over)
    return c


SMALL = small()


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


def _served_logits(params, cfg, seq, prompt_len, chunk, max_len=64):
    """Prefill ``seq[:prompt_len]`` in chunks of ``chunk`` through the chunk
    program and decode the rest one token a step through the decode
    program, both pools and the window's release as the engine runs them;
    the logits of the last prompt token and of every decode step."""
    cache = PagedKVCache(cfg, 2, max_len, page_size=PAGE,
                         prefill_chunk=chunk)
    slot = 1
    cache.admit(slot, seq.size)
    step = jax.jit(lambda toks, pool, table, wt, done, take, ctx_cap: (
        gen.paged_prefill_chunk(params, toks, pool, table, cfg,
                                ctx_cap=ctx_cap, ctx_len=done,
                                chunk_len=take, window_table=wt)),
        static_argnums=(6,))
    decode = jax.jit(lambda last, pool, tables, wts, lengths: (
        gen.paged_decode_forward(params, last, pool, tables, lengths, cfg,
                                 active=jnp.asarray([False, True]),
                                 window_tables=wts)))
    pool, done, out = cache.pool, 0, []
    while done < prompt_len:
        take = min(chunk, prompt_len - done)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :take] = seq[done:done + take]
        cache.window_extend(slot, done + take)
        lg, pool = step(jnp.asarray(toks), pool,
                        jnp.asarray(cache.block_tables[slot]),
                        jnp.asarray(cache.window_tables[slot].copy()),
                        jnp.int32(done), jnp.int32(take),
                        cache.ctx_cap_pages(cache.pages_for(done)) * PAGE)
        done += take
        cache.window_release(slot, done)
    out.append(np.asarray(lg)[0])
    cache.lengths[slot] = prompt_len
    cache.window_extend(slot, prompt_len + 1)
    for tok in seq[prompt_len:]:
        lg, pool = decode(jnp.asarray([0, tok]), pool,
                          jnp.asarray(cache.block_tables.copy()),
                          jnp.asarray(cache.window_tables.copy()),
                          jnp.asarray(cache.lengths.copy()))
        out.append(np.asarray(lg)[1])
        cache.lengths[slot] += 1
        cache.window_step(np.asarray([slot]))
    return np.stack(out), cache


# ---- the model ----
@pytest.mark.parametrize("lead", [0, 1, 2])
def test_no_cache_forward_agrees_with_the_reference(lead):
    """A dense prologue of 0, 1 and 2 layers in front of one whole period
    of expert layers (sliding, full, sliding, sliding)."""
    c = small(first_layer_run=6 - lead, num_hidden_layers=4 + lead)
    cfg, params = _model(c)
    assert cfg.dense_layers == lead and cfg.prologue == ("sliding",) * lead
    assert cfg.period == ("sliding", "full", "sliding", "sliding")
    assert ("dense_layers" in params) == bool(lead)
    toks = _tokens(37)
    got = np.asarray(llama.forward(params, jnp.asarray(toks)[None], cfg))[0]
    np.testing.assert_allclose(got, _ref_logits(params, toks, c), atol=TOL,
                               rtol=0)


def test_the_config_says_what_it_runs_and_keeps():
    cfg, params = _model()
    assert cfg.cache_layers() == {"sliding": 4, "full": 1}
    assert cfg.kind_layers("sliding") == 4 and cfg.periods == 1
    assert cfg.nope_kinds == ("full",)
    assert cfg.embed_scale == pytest.approx(8.0)
    assert set(cfg.kv_parts()) == {
        "a dense prologue", "an attention output gate", "QK-norm",
        "attention with no rotary embedding", "sandwich norms",
        "an input scale"}
    assert llama.rope_tables_by_kind(cfg, 16)["full"] == (None, None)
    assert params["layers"]["moe_wg"].shape[1] == 4
    assert params["layers"]["moe_gate"].dtype == jnp.float32
    pool = gen.init_paged_cache(cfg, 9, PAGE, window_pages=5)
    assert pool["k"].shape[0] == 1 and pool["k_w"].shape[0] == 4
    # the plain decoder is what it was
    plain = llama.LlamaConfig.tiny()
    assert plain.kv_parts() == () and plain.prologue == ()


@pytest.mark.parametrize("bad", [
    dict(dense_layers=1, dense_kinds=()),
    dict(dense_layers=1, dense_kinds=("latent",)),
    dict(dense_kinds=("full",)),
    dict(nope_kinds=("latent",)),
    dict(dense_layers=1, dense_kinds=("full",), moe=None)])
def test_the_config_refuses_what_it_cannot_mean(bad):
    from paddle_tpu.models.moe import MoEConfig
    kw = dict(num_layers=3, moe=MoEConfig(num_experts=4, top_k=2))
    kw.update(bad)
    with pytest.raises(ValueError):
        llama.LlamaConfig.tiny(**kw)


def test_the_trainer_refuses_by_name():
    cfg, params = _model()
    with pytest.raises(ValueError, match="training a config with leading "
                                         "dense layers"):
        llama.loss_fn(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(ValueError, match="num_params"):
        cfg.num_params()


# ---- the serving programs ----
@pytest.mark.parametrize("chunk", [8, 12, 20, 32])
def test_chunked_prefill_then_decode_agree_with_the_reference(chunk):
    """Chunks that do (8, 32) and do not (12, 20) divide the prompt of 32,
    whose later chunks' contexts lie past the window of 8; then six decode
    steps, their contexts past it too."""
    cfg, params = _model()
    seq = _tokens(38, seed=chunk)
    got, cache = _served_logits(params, cfg, seq, 32, chunk)
    np.testing.assert_allclose(got, _ref_logits(params, seq)[31:],
                               atol=TOL, rtol=0)
    # a row holds a window and a chunk of sliding pages, not its length
    held = (cache.window_tables[1] != 0).sum()
    assert held <= cache.window_ring and held < cache.pages_for(seq.size)


PARTS = {
    "attn_output_gate": dict(attn_output_gate=True),
    "qk_norm": dict(qk_norm=True),
    "nope_full": dict(nope_layer_types=["full_attention"]),
    "sandwich_norms": dict(sandwich_norms=True),
    "mup_input_scale": dict(mup_enabled=True),
    "dense_prologue_0": dict(first_layer_run=6, num_hidden_layers=4),
    "dense_prologue_1": {},
    "dense_prologue_2": dict(first_layer_run=4, num_hidden_layers=6),
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_each_part_alone_serves_as_the_reference_computes(part):
    """Every part of the layer off but one (the prologue's cases keep all
    other parts off too): the served logits are the reference's."""
    c = small(attn_output_gate=False, qk_norm=False, nope_layer_types=[],
              sandwich_norms=False, mup_enabled=False)
    c.update(PARTS[part])
    cfg, params = _model(c)
    seq = _tokens(30, seed=3)
    got, _ = _served_logits(params, cfg, seq, 24, 8)
    np.testing.assert_allclose(got, _ref_logits(params, seq, c)[23:],
                               atol=TOL, rtol=0)


def test_a_chunk_too_wide_for_whole_scores_takes_a_key_head_at_a_time(
        monkeypatch):
    """Above ``paged_chunk_attention._SCORE_BYTES`` the twin of a chunk's
    attention over its pages runs one key head's group of query heads at a
    time: the same logits."""
    from paddle_tpu.ops.pallas import paged_chunk_attention as pca
    cfg, params = _model()
    seq = _tokens(34, seed=4)
    whole, _ = _served_logits(params, cfg, seq, 32, 16)
    monkeypatch.setattr(pca, "_SCORE_BYTES", 1024)
    blocked, _ = _served_logits(params, cfg, seq, 32, 16)
    np.testing.assert_allclose(blocked, whole, atol=1e-5, rtol=0)


@pytest.mark.parametrize("prefill_chunk", [8, 12])
def test_engine_greedy_tokens_lie_on_the_reference(prefill_chunk):
    """The engine and scheduler end to end: every served token is the
    reference's best at its position (its logit within TOL of the best),
    and the keys the programs read are counted by kind."""
    cfg, params = _model()
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=PAGE,
                                   max_len=64, prefill_chunk=prefill_chunk)
    sched = ServingScheduler(eng)
    prompts = [_tokens(21, seed=5), _tokens(30, seed=6)]
    hs = [sched.submit(p, max_new_tokens=7) for p in prompts]
    sched.run()
    window, full = 0, 0
    for p, h in zip(prompts, hs):
        lg = _ref_logits(params, np.concatenate([p, h.tokens]))
        at = lg[p.size - 1:p.size - 1 + len(h.tokens)]
        gap = at.max(-1) - at[np.arange(len(h.tokens)), h.tokens]
        assert gap.max() <= TOL
        # a decode step of a row with n tokens cached reads n + 1 keys
        seen = [p.size + i + 1 for i in range(6)]
        window += 4 * sum(min(n, 8) for n in seen)
        full += sum(seen)
    s = sched.stats()
    assert s["window_keys_attended_total"] == window
    assert s["full_keys_attended_total"] == full
    # however a prompt is cut into chunks, its token i sees i + 1 keys
    assert s["chunk_full_keys_attended_total"] == (21 * 22 + 30 * 31) // 2
    assert s["chunk_window_keys_attended_total"] == 4 * sum(
        min(i, 8) for n in (21, 30) for i in range(1, n + 1))
    assert s["moe_items_elsewhere_total"] > s["moe_routed_items_total"] > 0
    # four expert layers a program: the dense layer counts in none
    assert s["moe_layer_steps_total"] % 4 == 0
    assert s["window_pages_released_total"] > 0


@pytest.mark.parametrize("at", ["mid_decode", "mid_prefill"])
def test_a_preempted_row_resumes_where_an_undisturbed_one_ends(at):
    cfg, params = _model()
    kw = dict(max_batch=1, page_size=PAGE, max_len=64, prefill_chunk=8,
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
    """What ``--plant control`` switches on: 8-bit attention projections
    with the output gate, dense and shared FFN and head (the expert stacks
    stay), and the int8 pools. Its served tokens lie within 1.0 of the
    reference's best logit and, over 40 tokens, not on it: the tier is a
    different result, which the comparison has to see."""
    cfg, params = _model()
    eng = ContinuousBatchingEngine(
        params, cfg, max_batch=2, page_size=PAGE, max_len=64,
        prefill_chunk=8, weight_bits=8, kv_cache_dtype="int8")
    for group, names in (("layers", ("wq", "wgate", "wo", "ws_g")),
                         ("dense_layers", ("wk", "wgate", "wg", "wd"))):
        for n in names:
            assert eng.params[group][n].dtype == jnp.int8
    assert eng.params["lm_head"].dtype == jnp.int8
    assert eng.params["layers"]["moe_wg"].dtype == jnp.float32
    assert eng.cache.pool["k_w"].dtype == jnp.int8
    prompts = [_tokens(17, seed=11), _tokens(23, seed=12)]
    outs = eng.generate(prompts, max_new_tokens=20)
    gaps = []
    for p, o in zip(prompts, outs):
        lg = _ref_logits(params, o)[p.size - 1:-1]
        gaps += list(lg.max(-1) - lg[np.arange(20), o[p.size:]])
    assert max(gaps) < 1.0
    assert np.mean(gaps) > 10 * TOL or max(gaps) > 10 * TOL


ENGINE_REFUSALS = {
    "host_tier": (dict(host_tier=True), "sliding-window"),
    "speculative": (dict(spec_k=2), "sliding-window"),
    "draft_layers": (dict(draft_layers=1, spec_k=2), "dense prologue"),
    "fused": (dict(fused=True), "output gate"),
    "adapters": (dict(adapters=dict(slots=2, rank=4)), "QK-norm"),
}


@pytest.mark.parametrize("what", sorted(ENGINE_REFUSALS))
def test_the_engine_refuses_by_name(what):
    cfg, params = _model()
    kw, why = ENGINE_REFUSALS[what]
    with pytest.raises(ValueError, match=f"{what}.*{why}"):
        ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=PAGE,
                                 max_len=64, **kw)


def test_a_mesh_and_the_one_pool_programs_are_refused_by_name():
    from paddle_tpu.distributed.mesh import serving_mesh
    cfg, params = _model()
    with pytest.raises(ValueError, match="mesh.*dense prologue"):
        ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=PAGE,
                                 max_len=64, mesh=serving_mesh(2))
    pool = gen.init_paged_cache(cfg, 9, PAGE, window_pages=9)
    toks, table = jnp.zeros((1, 8), jnp.int32), jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="paged_prefill_insert.*sliding"):
        gen.paged_prefill_insert(params, toks, pool, table, cfg)
    for name in ("tp_axis", "dp_axis", "fused", "adapters"):
        with pytest.raises(ValueError, match=f"{name}.*sandwich norms"):
            gen.paged_decode_forward(
                params, jnp.zeros((1,), jnp.int32), pool, table[None],
                jnp.zeros((1,), jnp.int32), cfg, window_tables=table[None],
                **{name: "x"})
    with pytest.raises(ValueError, match="checkpoint_prefix.*sliding"):
        PagedKVCache(cfg, 2, 64, page_size=PAGE).checkpoint_prefix()


# ---- the share and the model ----
def _expert_layer(c, seed=3):
    """One expert layer's leaves with ALL the router's experts held, and
    the input rows."""
    whole = dict(c, num_experts=c["router_outputs"], first_expert_held=0,
                 first_layer_run=6, num_hidden_layers=4)
    params = arch.weights(jax.random.key(seed), whole, dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(seed + 1), (2, 9, c["hidden_size"]))
    return whole, lp, x


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of the sigmoid layer give,
    with the shared expert counted once, are the uncut reference's expert
    layer before its post-MLP norm: the partial sum each chip hands on."""
    whole, lp, x = _expert_layer(SMALL)
    cfg = dataclasses.replace(arch.program_config(whole, 64, remat=False),
                              dtype=jnp.float32)
    stacks = tuple(lp[n][None] for n in reference.STACKS)
    want = np.asarray(reference.ffn_partial(x.reshape(18, -1), lp, stacks,
                                            0, whole, 16))
    zero_shared = dict(lp, ws_d=jnp.zeros_like(lp["ws_d"]))
    E, held = whole["router_outputs"], SMALL["num_experts"]
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
    cfg = dataclasses.replace(arch.program_config(whole, 64, remat=False),
                              dtype=jnp.float32)
    mine = dict(lp, first_expert=jnp.int32(4))
    stacks = tuple(lp[n][None, 4:8] for n in reference.STACKS)
    text = jax.jit(lambda x: gen._moe_ffn(x, mine, cfg, experts=stacks)[0]
                   ).lower(x).as_text()
    for op in ("all_to_all", "all-to-all", "all_gather", "all-gather",
               "all_reduce", "all-reduce", "collective"):
        assert op not in text


def test_the_gate_is_a_named_scope_of_the_serving_programs():
    """The decode program's text names the gate's scope, where the
    device trace finds it."""
    cfg, params = _model()
    pool = gen.init_paged_cache(cfg, 9, PAGE, window_pages=9)
    t = jnp.zeros((2, 16), jnp.int32)
    text = jax.jit(lambda p, pg: gen.paged_decode_forward(
        p, jnp.zeros((2,), jnp.int32), pg, t, jnp.zeros((2,), jnp.int32),
        cfg, window_tables=t)[0]).lower(params, pool).as_text(
            debug_info=True)
    assert "attention_output_gate" in text
