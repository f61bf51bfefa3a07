"""Sliding-window and full layers mixed, top-k routed experts through a
grouped matmul, and the cache with a pool for each layer kind: the small
windowed MoE config against the benchmark's plain reference
(``chipbench/reference/mellum.py``, float32, no cache, no kernel), logits
compared and not tokens."""
import dataclasses
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from chipbench.archs import mellum as arch
from chipbench.reference import mellum as ref
from paddle_tpu.inference.predictor import ContinuousBatchingEngine
from paddle_tpu.models import generate as gen
from paddle_tpu.models import llama
from paddle_tpu.models.moe import MoEConfig
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import serving_fused as sf
from paddle_tpu.serving import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = 16


def small_config(**over):
    """The published file at tiny widths: both rotary sections and the
    layer list as published, a window shorter than the sequences."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, moe_intermediate_size=32, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=256, num_experts=8, num_experts_per_tok=2,
             sliding_window=WINDOW)
    c.update(over)
    return c


def program(c, max_len=128, **over):
    """float32 twin of the benchmark's program config; the trainer's router
    drops nothing at a capacity of every token."""
    cfg = arch.program_config(c, max_len, remat=False)
    return dataclasses.replace(
        cfg, dtype=jnp.float32,
        moe=MoEConfig(num_experts=c["num_experts"],
                      top_k=c["num_experts_per_tok"],
                      capacity_factor=c["num_experts"]
                      / c["num_experts_per_tok"]), **over)


@pytest.fixture(scope="module")
def model():
    c = small_config()
    params = arch.weights(jax.random.key(7), c, dtype=jnp.float32)
    return c, params


def ref_logits(params, seq, c):
    with jax.default_matmul_precision("highest"):
        x = ref.hidden(params, jnp.asarray(seq, jnp.int32), c, q_block=32)
        return np.asarray(ref.logits(params, x, c))


def logit_gaps(params, c, prompt, tokens):
    """How far below the reference's best logit each served token lies."""
    seq = np.concatenate([prompt, tokens])
    lg = ref_logits(params, seq, c)[prompt.size - 1:-1]
    return lg.max(-1) - lg[np.arange(tokens.size), tokens]


# ---- (a) llama.py's full forward ----
def test_full_forward_matches_the_reference(model):
    c, params = model
    cfg = program(c)
    assert cfg.period == ("sliding", "sliding", "sliding", "full")
    seq = np.random.default_rng(0).integers(0, 256, (3 * WINDOW + 5,))
    got = np.asarray(llama.forward(params, jnp.asarray(seq[None]), cfg))[0]
    np.testing.assert_allclose(got, ref_logits(params, seq, c),
                               rtol=2e-4, atol=2e-4)


# ---- (c) the grouped expert layer under skewed routing ----
def test_grouped_experts_match_the_loop_under_skewed_routing(model):
    c, params = model
    cfg = program(c)
    rng = np.random.default_rng(1)
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jnp.asarray(rng.normal(size=(3, 16, 64)), jnp.float32)
    # a router that sends nearly every row to expert 5 first, leaves
    # experts 0 and 1 without an item, and spreads the second choice
    gate = np.zeros((64, 8), np.float32)
    gate[:, 5] = 0.0
    bias_row = np.asarray([-50, -50, 0, 0, 0, 8, 0, 0], np.float32)
    gate += rng.normal(size=(64, 8)).astype(np.float32) * 0.2
    x = x.at[..., 0].set(1.0)
    gate[0] += bias_row * 8.0       # x[..., 0] is 1 after RMS-free input
    lp = {**lp, "moe_gate": jnp.asarray(gate)}
    y, stats = gen._moe_ffn(x, lp, cfg)
    items, hit, top = np.asarray(stats)
    assert items == 3 * 16 * 2 and hit <= 6 and top >= 40
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x.reshape(48, 64), lp["moe_gate"], lp["moe_wg"],
                           lp["moe_wu"], lp["moe_wd"], 2)
    np.testing.assert_allclose(np.asarray(y).reshape(48, 64),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


# ---- (e) the kernels with a window against their references ----
@pytest.fixture
def interpreted():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_paged_kernel_with_a_window(interpreted, kv):
    rng = np.random.default_rng(2)
    B, H, HK, D, page, ppseq, P = 4, 4, 2, 16, 8, 12, 40
    lens = np.asarray([5, 16, 37, 96], np.int32)
    bt = np.zeros((B, ppseq), np.int32)      # dead pages: the trash page
    nxt = 1
    for b, n in enumerate(lens):
        first = max(n - WINDOW, 0) // page
        for i in range(first, -(-n // page)):
            bt[b, i] = nxt
            nxt += 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kw = {}
    if kv == "int8":
        kp, vp = (jnp.asarray(rng.integers(-127, 128, (P, page, HK, D)),
                              jnp.int8) for _ in range(2))
        kw = {n: jnp.asarray(rng.uniform(0.005, 0.02, (P, page, HK)),
                             jnp.float32) for n in ("ks_pages", "vs_pages")}
    else:
        kp, vp = (jnp.asarray(rng.normal(size=(P, page, HK, D)), jnp.float32)
                  for _ in range(2))
    want = pa.paged_attention_reference(q, kp, vp, bt, lens, window=WINDOW,
                                        **kw)
    got = pa.paged_attention_kernel(q, kp, vp, bt, lens, window=WINDOW, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and the window matters: the full read of the same tables differs
    full = pa.paged_attention_reference(q, kp, vp, bt, lens, **kw)
    assert np.abs(np.asarray(full)[2:] - np.asarray(want)[2:]).max() > 1e-3


@pytest.mark.parametrize("rows,skew", [(64, "one"), (64, "even"),
                                       (640, "one"), (24, "even")])
def test_grouped_matmul_kernel_matches_ragged_dot(interpreted, rows, skew):
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    rng = np.random.default_rng(5)
    L, G, K, N = 3, 8, 64, 32
    if skew == "one":       # one group holds most rows, three hold none
        ids = np.where(rng.random(rows) < 0.8, 5,
                       rng.choice([0, 2, 3, 7], rows))
    else:
        ids = rng.integers(0, G, rows)
    ids = np.sort(ids)
    sizes = jnp.asarray(np.bincount(ids, minlength=G), jnp.int32)
    xs = jnp.asarray(rng.normal(size=(rows, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(L, G, K, N)), jnp.float32)
    for layer in (0, 2):
        want = np.einsum("nk,nkj->nj", np.asarray(xs),
                         np.asarray(w)[layer][ids])
        ref_ = gm.grouped_matmul_reference(xs, w, sizes, jnp.int32(layer))
        got = gm.grouped_matmul_kernel(xs, w, sizes, jnp.int32(layer))
        np.testing.assert_allclose(np.asarray(ref_), want, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_flash_chunk_kernel_with_a_window(interpreted, kv):
    rng = np.random.default_rng(3)
    B, T, H, HK, D, W = 1, 8, 4, 2, 16, 72
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    kw = {}
    if kv == "int8":
        ck, cv = (jnp.asarray(rng.integers(-127, 128, (B, W, HK, D)),
                              jnp.int8) for _ in range(2))
        kw = {n: jnp.asarray(rng.uniform(0.005, 0.02, (B, W, HK)),
                             jnp.float32) for n in ("k_rows", "v_rows")}
    else:
        ck, cv = (jnp.asarray(rng.normal(size=(B, W, HK, D)), jnp.float32)
                  for _ in range(2))
    kstart = jnp.asarray([3], jnp.int32)
    want = sf.flash_chunk_attention_reference(q, ck, cv, W, kstart,
                                              window=WINDOW, **kw)
    got = sf.flash_chunk_attention_kernel(q, ck, cv, W, kstart, block_k=16,
                                          window=WINDOW, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    plain = sf.flash_chunk_attention_reference(q, ck, cv, W, kstart, **kw)
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-3


# ---- (b) chunked prefill, then paged decode, through the engine ----
PROMPTS = (40, 23, 57)          # past two windows; pages release, ring wraps
NEW = 24


def serve(params, cfg, prompts, new=NEW, **kw):
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=8,
                                   max_len=128, prefill_chunk=16, **kw)
    hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    eng.run()
    return eng, [np.asarray(h.tokens) for h in hs]


def prompts_of(lengths, seed=4):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 256, (n,)).astype(np.int32) for n in lengths]


def worst_gap(params, c, prompts, served):
    return max(float(logit_gaps(params, c, p, t).max())
               for p, t in zip(prompts, served))


def test_engine_serves_the_references_logits(model):
    c, params = model
    prompts = prompts_of(PROMPTS)
    eng, served = serve(params, program(c), prompts)
    assert worst_gap(params, c, prompts, served) < 1e-3
    st = eng.stats()
    # every row slid past whole pages, and nothing is left held
    assert st["window_pages_released_total"] >= 10
    assert eng.cache.window_allocator.num_used == len(
        eng.cache._trie_alloc.twin)
    assert st["window_pool_used_peak"] <= 2 * eng.cache.window_ring
    assert st["moe_routed_items_total"] > 0
    assert st["moe_experts_hit_total"] <= 8 * st["moe_layer_steps_total"]


def test_engine_with_int8_weights_and_cache_stays_close(model):
    """The program's w8/kv8 path (the cell's control) on the windowed MoE
    config: attention and head in int8 (the expert stacks stay as they
    are), both pools in int8. It serves, and what it serves lies near the
    reference's logits but further than the float32 path's."""
    c, params = model
    prompts = prompts_of(PROMPTS)
    eng, served = serve(params, program(c), prompts, weight_bits=8,
                        kv_cache_dtype="int8")
    assert eng.params["layers"]["wq"].dtype == jnp.int8
    assert eng.params["layers"]["moe_wg"].dtype == params["layers"][
        "moe_wg"].dtype
    assert eng.cache.pool["ks_w"].shape[0] == 3
    gaps = np.concatenate([logit_gaps(params, c, p, t)
                           for p, t in zip(prompts, served)])
    assert 1e-3 < gaps.max() and gaps.mean() < 0.1


def _broken_renorm(monkeypatch):
    """The experts' sum weighted by the raw probabilities of the chosen,
    not renormalised over them."""
    sound = gen._moe_ffn

    def broken(x, lp, cfg, **kw):
        y, st = sound(x, lp, cfg, **kw)
        p = jax.nn.softmax(x.astype(jnp.float32) @ lp["moe_gate"], -1)
        chosen = jnp.sum(jax.lax.top_k(p, cfg.moe.top_k)[0], -1)
        return y * chosen[..., None].astype(y.dtype), st
    monkeypatch.setattr(gen, "_moe_ffn", broken)


def _broken_release(monkeypatch):
    """One page too many goes back to the pool: the window's oldest."""
    sound = PagedKVCache.window_release

    def broken(self, slot, pos):
        return sound(self, slot, pos + self.page_size)
    monkeypatch.setattr(PagedKVCache, "window_release", broken)


@pytest.mark.parametrize("fault", ["window", "yarn_factor", "renorm",
                                   "release"])
def test_engine_with_a_planted_fault_does_not(model, monkeypatch, fault):
    c, params = model
    cfg = program(c)
    if fault == "window":           # the mask lets eight more keys in
        cfg = dataclasses.replace(cfg, sliding_window=WINDOW + 8)
    elif fault == "yarn_factor":    # cos and sin left unscaled
        cfg = dataclasses.replace(cfg, yarn=dataclasses.replace(
            cfg.yarn, attention_factor=1.0))
    elif fault == "renorm":
        _broken_renorm(monkeypatch)
    else:
        _broken_release(monkeypatch)
    prompts = prompts_of(PROMPTS)
    _, served = serve(params, cfg, prompts)
    assert worst_gap(params, c, prompts, served) > 1e-2


# ---- (d) the allocator of the sliding layers' pool ----
def test_released_pages_are_the_pages_slid_out(model):
    c, _ = model
    cache = PagedKVCache(program(c), max_batch=2, max_len=128, page_size=8,
                         prefill_chunk=16, enable_prefix_cache=False)
    assert cache.window_ring == 2 + 2 + 1
    assert cache.window_pages == 1 + 2 * cache.window_ring
    cache.admit(0, 100)
    wa = cache.window_allocator
    freed = 0
    for done in range(0, 64, 16):           # four chunks of 16
        cache.window_extend(0, done + 16)
        assert wa.num_used <= cache.window_ring
        freed += cache.window_release(0, done + 16)
    # a query at 64 sees keys from 49 on: pages 0..5 (positions < 48) went
    assert freed == 6 and cache._win_first[0] == 6
    assert (cache.window_tables[0, :6] == 0).all()
    assert (cache.window_tables[0, 6:8] > 0).all()
    cache.lengths[0] = 64
    for _ in range(30):                     # decode steps
        cache.lengths[0] += 1
        freed += cache.window_step(np.asarray([0]))
    slid_out = max(int(cache.lengths[0]) - WINDOW + 1, 0) // 8
    assert freed == slid_out == cache._win_first[0]
    cache.release(0)
    assert wa.num_used == 0 and wa.allocs_total == wa.frees_total


def test_a_page_the_trie_holds_is_not_released_and_a_hit_is_exact(model):
    c, params = model
    cfg = program(c)
    base = prompts_of((20,), seed=5)[0]
    longer = np.concatenate([base, prompts_of((30,), seed=6)[0]])
    eng, (first,) = serve(params, cfg, [base], new=30)
    cache = eng.cache
    twins = dict(cache._trie_alloc.twin)
    # the prompt's two full pages and its tail were published with their
    # sliding halves; decoding 30 tokens slid the window past all three,
    # and the trie's reference kept them out of the free list
    assert len(twins) == 3 and eng.stats()["window_pages_released_total"] > 0
    wa = cache.window_allocator
    assert all(wa.refcount(w) == 1 for w in twins.values())
    assert wa.num_used == 3
    # a prompt that extends the cached one maps both halves ...
    h = eng.submit(longer, max_new_tokens=NEW)
    eng.run()
    assert eng.stats()["prefix_hit_tokens_total"] == 20
    # ... and serves what a cold engine serves, to the reference's logits
    _, (cold,) = serve(params, cfg, [longer])
    np.testing.assert_array_equal(np.asarray(h.tokens), cold)
    assert worst_gap(params, c, [longer], [cold]) < 1e-3
    # a hit whose window has no sliding half left is a miss, not a guess
    eng2, _ = serve(params, cfg, [longer], new=4)
    short = longer[:17]       # one page of the long prompt's start
    h2 = eng2.submit(np.concatenate([short, base[:9]]), max_new_tokens=4)
    eng2.run()
    assert eng2.stats()["prefix_hit_tokens_total"] == 0
    assert worst_gap(params, c, [np.concatenate([short, base[:9]])],
                     [np.asarray(h2.tokens)]) < 1e-3


def test_page_copying_features_refuse_a_windowed_config(model):
    c, params = model
    cfg = program(c)
    for kw, word in ((dict(host_tier=True), "host_tier"),
                     (dict(spec_k=2), "speculative"),
                     (dict(spec_k=2, draft_layers=1), "speculative")):
        with pytest.raises(ValueError, match=word):
            ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=8,
                                     max_len=128, **kw)
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=8,
                                   max_len=128)
    for call in (eng.cache.checkpoint_prefix, eng.cache.defrag,
                 lambda: eng.cache.export_request(0)):
        with pytest.raises(ValueError, match="sliding-window"):
            call()


# ---- (f) a tp=2 mesh against the single device ----
def test_tp2_mesh_serves_the_single_devices_tokens(model):
    from paddle_tpu.distributed.mesh import serving_mesh
    c, params = model
    cfg = program(c)
    prompts = prompts_of(PROMPTS)
    _, want = serve(params, cfg, prompts)
    eng, got = serve(params, cfg, prompts, mesh=serving_mesh(2))
    assert eng.cache.pool["k_w"].sharding.spec[3] == "tp"
    assert eng.cache.pool["k"].sharding.spec[3] == "tp"
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert worst_gap(params, c, prompts, got) < 1e-3


# ---- (g) the period scan leaves a plain decoder as it was ----
@pytest.mark.parametrize("moe", [False, True])
@pytest.mark.parametrize("kv", [None, "int8"])
def test_a_period_of_full_layers_is_the_plain_decoder(kv, moe, monkeypatch):
    cfg = llama.LlamaConfig.tiny(
        num_layers=4, max_seq_len=128,
        moe=MoEConfig(num_experts=4, top_k=2) if moe else None)
    params = llama.init_params(jax.random.key(3), cfg)
    prompts = prompts_of(PROMPTS)
    _, plain = serve(params, cfg, prompts, kv_cache_dtype=kv)
    twice = dataclasses.replace(cfg, layer_pattern=("full", "full"))
    _, scanned = serve(params, twice, prompts, kv_cache_dtype=kv)
    for a, b in zip(plain, scanned):
        np.testing.assert_array_equal(a, b)
    if kv is None:
        # and the plain decoder is still the dense path's
        out = gen.generate(params, jnp.asarray(prompts[0][None]), cfg,
                           max_new_tokens=NEW)
        np.testing.assert_array_equal(np.asarray(out)[0, prompts[0].size:],
                                      plain[0])
    if not moe:
        return
    # a plain period with experts: the decode step hands back ONE (3,)
    # stats vector, the sum over layers of what each layer's _moe_ffn
    # counted over the live rows
    seen = []
    inner = gen._moe_ffn

    def spy(*a, **k):
        y, st = inner(*a, **k)
        jax.debug.callback(lambda v: seen.append(np.asarray(v)), st)
        return y, st
    monkeypatch.setattr(gen, "_moe_ffn", spy)
    paged = gen.init_paged_cache(cfg, 9, 8, kv_dtype=kv)
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    active = jnp.asarray([True, False, True])
    _, _, stats = gen.paged_decode_forward(
        params, jnp.asarray([5, 6, 7], jnp.int32), paged, tables,
        jnp.asarray([3, 0, 9], jnp.int32), cfg, active=active,
        with_stats=True)
    jax.effects_barrier()
    assert stats.shape == (3,) and len(seen) == cfg.num_layers
    np.testing.assert_array_equal(np.asarray(stats), np.sum(seen, axis=0))
    assert int(stats[0]) == cfg.num_layers * 2 * cfg.moe.top_k
