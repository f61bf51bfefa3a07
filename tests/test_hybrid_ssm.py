"""A hybrid model on the serving path: Mamba-2 layers over the recurrent-
state pool, attention without rotary embedding over the paged KV pool, and
LatentMoE layers with a share of the routed experts held here, against the
plain reference ``chipbench/reference/nemotron_h.py``.

Small sizes, the CPU, seeded weights, float32 on both sides: the reference
scans token by token where the program scans in chunks and updates a pool in
place, so what is left between them is the order of float32 sums. The
tolerance on logits, 2e-4 absolute at logits of order 1, is some twenty times
the largest difference seen over the cases here (1e-5) and a thousandth of
what bfloat16 anywhere in the recurrence gives (2e-2 and more)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.archs import nemotron_h as arch
from chipbench.reference import nemotron_h as reference
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate as gen, llama
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import ssm
from paddle_tpu.serving import PagedKVCache, Priority, ServingScheduler

TOL = 2e-4

#: the configuration file's keys at a small size: two periods of 4 layers
#: (M E * E), so two Mamba-2, two attention and four expert layers
SMALL = {
    "model_type": "nemotron_h", "hybrid_override_pattern": "ME*EME*E" * 4,
    "num_hidden_layers": 8, "hidden_size": 64, "intermediate_size": 24,
    "vocab_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_num_heads": 8, "mamba_head_dim": 16, "expand": 2,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "moe_latent_size": 32, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "router_outputs": 16,
    "n_routed_experts": 4, "first_expert_held": 4, "num_experts_per_tok": 6,
    "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_eps": 1e-5,
    "tie_word_embeddings": False, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4}


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


def test_no_cache_forward_agrees_with_the_reference():
    cfg, params = _model()
    toks = _tokens(37)
    got = np.asarray(llama.forward(params, jnp.asarray(toks)[None], cfg))[0]
    np.testing.assert_allclose(got, _ref_logits(params, toks), atol=TOL,
                               rtol=0)


def _serve_logits(params, cfg, prompt, n_new, chunk, page=4, kv=None):
    """Chunked prefill then decode through the cache manager and the two
    serving programs, as the engine drives them: the logits at the
    prompt's last token and at each decoded one, teacher-forced."""
    cache = PagedKVCache(cfg, 2, 64, page_size=page, kv_dtype=kv,
                         enable_prefix_cache=False)
    slot, done, out = 1, 0, []
    cache.admit(slot, prompt.size + n_new)
    table = jnp.asarray(cache.block_tables[slot])
    pool = cache.pool
    while done < prompt.size:
        take = min(chunk, prompt.size - done)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :take] = prompt[done:done + take]
        lg, pool = gen.paged_prefill_chunk(
            params, jnp.asarray(toks), pool, table, cfg,
            ctx_cap=cache.ctx_cap_pages(cache.pages_for(done)) * page,
            ctx_len=done, chunk_len=take, state_slot=slot)
        done += take
    out.append(np.asarray(lg)[0])
    active = jnp.asarray([False, True])
    return out, pool, cache, active


@pytest.mark.parametrize("chunk", [8, 12, 20, 32])
def test_chunked_prefill_then_decode_agree_with_the_reference(chunk):
    """Chunks that do (8, 32) and do not (12, 20) divide the prompt of 32
    and the sub-chunk of 8; then six decode steps."""
    cfg, params = _model()
    seq = _tokens(38, seed=chunk)
    prompt, rest = seq[:32], seq[32:]
    want = _ref_logits(params, seq)
    out, pool, cache, active = _serve_logits(params, cfg, prompt, rest.size,
                                             chunk)
    lengths = np.zeros((2,), np.int32)
    for i, tok in enumerate(rest):
        lengths[1] = prompt.size + i
        lg, pool = gen.paged_decode_forward(
            params, jnp.asarray([0, tok]), pool,
            jnp.asarray(cache.block_tables), jnp.asarray(lengths), cfg,
            active=active)
        out.append(np.asarray(lg)[1])
    np.testing.assert_allclose(np.stack(out), want[31:], atol=TOL, rtol=0)
    # the row that was not active kept its (zero) state
    assert not np.asarray(pool["ssm"][:, 0]).any()


@pytest.mark.parametrize("prefill_chunk", [8, 12])
def test_engine_greedy_tokens_lie_on_the_reference(prefill_chunk):
    """The engine and scheduler end to end: every served token is the
    reference's best at its position (its logit within TOL of the best)."""
    cfg, params = _model()
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=4,
                                   max_len=64, prefill_chunk=prefill_chunk,
                                   enable_prefix_cache=False)
    sched = ServingScheduler(eng)
    prompts = [_tokens(21, seed=5), _tokens(30, seed=6)]
    hs = [sched.submit(p, max_new_tokens=7) for p in prompts]
    sched.run()
    for p, h in zip(prompts, hs):
        lg = _ref_logits(params, np.concatenate([p, h.tokens]))
        at = lg[p.size - 1:p.size - 1 + len(h.tokens)]
        gap = at.max(-1) - at[np.arange(len(h.tokens)), h.tokens]
        assert gap.max() <= TOL
    s = sched.stats()
    assert s["ssm_state_resets_total"] == 2
    assert s["ssm_chunk_tokens_total"] == 51
    # each answer's first token comes from its last chunk, the other six
    # from decode steps: 2 rows x 6 steps in each of 2 Mamba-2 layers
    assert s["ssm_state_rows_total"] == 2 * 6 * 2
    assert s["moe_items_elsewhere_total"] > s["moe_routed_items_total"] > 0
    assert s["state_slots_used_peak"] == 2 and s["state_pool_bytes"] > 0


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,base", [(3, 0), (2, 5)])
def test_state_update_kernel_agrees_with_its_twin(rows, base, state_dtype):
    """The Pallas kernel, interpreted, against the jnp twin: the rows it
    is given from ``base`` on advance, an inactive one and every other row
    of the pool stay as they were."""
    P, N, H = 16, 16, 8
    k = jax.random.split(jax.random.key(rows), 6)
    pool = jax.random.normal(k[0], (9, P, N, H)).astype(state_dtype)
    dtx = jax.random.normal(k[1], (rows, P, H))
    decay = jax.random.uniform(k[2], (rows, H))
    bx, cx = (jax.random.normal(kk, (rows, N, H)) for kk in k[3:5])
    active = jnp.arange(rows) != 1
    y0, p0 = ssm.ssm_state_update_reference(pool, base, dtx, decay, bx, cx,
                                            active)
    fa.set_interpret(True)
    try:
        y1, p1 = ssm.ssm_state_update(pool, base, dtx, decay, bx, cx, active,
                                      use_kernel=True)
    finally:
        fa.set_interpret(False)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(y1)[live], np.asarray(y0)[live],
                               rtol=1e-6, atol=1e-6)
    # (a fused multiply-add rounds once where the twin rounds twice)
    np.testing.assert_allclose(
        np.asarray(p1, np.float32), np.asarray(p0, np.float32), rtol=1e-5,
        atol=1e-6 if state_dtype == jnp.float32 else 2e-2)
    untouched = np.ones(9, bool)
    untouched[base:base + rows] = False
    untouched[base + 1] = True
    np.testing.assert_array_equal(np.asarray(p1, np.float32)[untouched],
                                  np.asarray(pool, np.float32)[untouched])


def test_a_reused_slot_starts_from_zero_state():
    cfg, params = _model()
    kw = dict(max_batch=1, page_size=4, max_len=64, prefill_chunk=8,
              enable_prefix_cache=False)
    first, second = _tokens(19, seed=7), _tokens(23, seed=8)
    alone = ContinuousBatchingEngine(params, cfg, **kw).generate(
        [second], max_new_tokens=6)[0]
    eng = ContinuousBatchingEngine(params, cfg, **kw)
    eng.generate([first], max_new_tokens=6)
    assert np.asarray(eng.cache.pool["ssm"]).any()      # the slot is dirty
    np.testing.assert_array_equal(
        eng.generate([second], max_new_tokens=6)[0], alone)
    assert eng.stats()["ssm_state_resets_total"] == 2


@pytest.mark.parametrize("at", ["mid_decode", "mid_prefill"])
def test_a_preempted_row_resumes_by_re_prefill(at):
    """Its recurrent state went with its slot; the replay of prompt and
    answer rebuilds it, and the row ends where an undisturbed one does."""
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
    assert sched.stats()["ssm_state_rebuilds_total"] == 1


def test_the_lower_precision_tier_is_accepted():
    """What ``--plant control`` switches on: 8-bit attention, Mamba-2
    projections and head, an 8-bit KV pool; the expert stacks stay."""
    cfg, params = _model()
    eng = ContinuousBatchingEngine(
        params, cfg, max_batch=2, page_size=4, max_len=64, prefill_chunk=8,
        enable_prefix_cache=False, weight_bits=8, kv_cache_dtype="int8")
    layers = eng.params["layers"]
    assert layers["mamba2"]["w_in"].dtype == jnp.int8
    assert layers["attention"]["wq"].dtype == jnp.int8
    assert eng.params["lm_head"].dtype == jnp.int8
    assert layers["experts"]["w1"].dtype == jnp.float32
    out = eng.generate([_tokens(17, seed=11)], max_new_tokens=5)[0]
    assert out.size == 22


ENGINE_REFUSALS = {
    "host_tier": dict(host_tier=True),
    "speculative": dict(spec_k=2),
    "draft_layers": dict(draft_layers=1, spec_k=2),
    "enable_prefix_cache": dict(enable_prefix_cache=True),
    "fused": dict(fused=True),
    "adapters": dict(adapters=dict(slots=2, rank=4)),
}


@pytest.mark.parametrize("what", sorted(ENGINE_REFUSALS))
def test_the_engine_refuses_by_name(what):
    cfg, params = _model()
    kw = dict(max_batch=2, page_size=4, max_len=64,
              enable_prefix_cache=False)
    kw.update(ENGINE_REFUSALS[what])
    with pytest.raises(ValueError, match=f"{what}.*state-space layers"):
        ContinuousBatchingEngine(params, cfg, **kw)


@pytest.mark.parametrize("what", ["export_request", "import_request",
                                  "checkpoint_prefix", "restore_prefix",
                                  "defrag", "import_request_direct"])
def test_the_cache_refuses_what_walks_pages(what):
    cfg, _ = _model()
    cache = PagedKVCache(cfg, 2, 64, page_size=4, enable_prefix_cache=False)
    cache.admit(0, 8)
    args = {"export_request": (0,), "import_request": (1, {}, 8),
            "restore_prefix": ({},), "import_request_direct": (1, cache, 0, 8)}
    with pytest.raises(ValueError, match=f"{what}.*state-space layers"):
        getattr(cache, what)(*args.get(what, ()))
    with pytest.raises(ValueError, match="prefix cache.*state-space"):
        PagedKVCache(cfg, 2, 64, page_size=4)


def test_the_one_pool_programs_refuse_by_name():
    cfg, params = _model()
    pool = gen.init_paged_cache(cfg, 9, 4, state_slots=2)
    with pytest.raises(ValueError, match="paged_prefill_insert.*state-space"):
        gen.paged_prefill_insert(params, jnp.zeros((1, 8), jnp.int32), pool,
                                 jnp.zeros((4,), jnp.int32), cfg)
    with pytest.raises(ValueError, match="state_slots"):
        gen.init_paged_cache(cfg, 9, 4)


# ---- the share and the model ----
def _expert_layer(c, seed=3):
    """One expert layer's leaves with ALL the router's experts held, and
    the input rows."""
    whole = dict(c, n_routed_experts=c["router_outputs"], first_expert_held=0,
                 hybrid_override_pattern="E", num_hidden_layers=1)
    params = arch.weights(jax.random.key(seed), whole, dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["experts"])
    x = jax.random.normal(jax.random.key(seed + 1), (2, 9, c["hidden_size"]))
    return whole, lp, x


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares give, each through the same
    up-projection (linear, no bias, so they add), with the shared expert
    counted once, are the uncut reference's expert layer."""
    whole, lp, x = _expert_layer(SMALL)
    cfg = dataclasses.replace(
        arch.program_config(dict(whole), 64, remat=False), dtype=jnp.float32)
    want = np.asarray(reference.experts(
        x.reshape(18, -1), lp, (lp["w1"][None], lp["w2"][None]), 0, whole,
        16))
    zero_shared = dict(lp, ws2=jnp.zeros_like(lp["ws2"]))
    E, held = whole["router_outputs"], SMALL["n_routed_experts"]
    total, items = 0.0, 0
    for first in range(0, E, held):
        mine = dict(zero_shared if first else lp,
                    first_expert=jnp.int32(first))
        stacks = tuple(lp[n][None, first:first + held] for n in ("w1", "w2"))
        y, stats = gen._latent_moe_ffn(x, mine, cfg, stacks, 0)
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
    stacks = tuple(lp[n][None, 4:8] for n in ("w1", "w2"))
    text = jax.jit(lambda x: gen._latent_moe_ffn(x, mine, cfg, stacks, 0)[0]
                   ).lower(x).as_text()
    for op in ("all_to_all", "all-to-all", "all_gather", "all-gather",
               "all_reduce", "all-reduce", "collective"):
        assert op not in text
