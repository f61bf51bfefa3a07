"""A Jamba model on the serving path: Mamba-1 layers over the recurrent-state
pool, attention with one key/value head and no positional embedding over
the paged KV pool, a dense MLP after every mixer, against the plain
reference ``chipbench/reference/jamba.py``.

Small sizes, the CPU, seeded weights, float32 on both sides: the reference
scans token by token in its own code where the program scans through
``ops/pallas/mamba1.py`` and updates a pool in place, so what is left
between them is the order of float32 sums. The tolerance on logits, 2e-5
absolute at logits of order 0.5, is some twenty times the largest
difference seen over the cases here (1e-6) and a hundredth of what
bfloat16 anywhere in the recurrence gives (2e-3 and more,
:func:`test_bfloat16_state_is_seen`)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.archs import jamba as arch
from chipbench.reference import jamba as reference
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate as gen, hybrid, llama
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import mamba1
from paddle_tpu.serving import PagedKVCache, Priority, ServingScheduler

TOL = 2e-5

#: the configuration file's keys at a small size: two periods of three
#: layers (Mamba-1, attention, Mamba-1), each mixer followed by its MLP
SMALL = {
    "model_type": "jamba", "attn_layer_offset": 1, "attn_layer_period": 3,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_size": 64,
    "intermediate_size": 96, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
    "mamba_proj_bias": False, "num_attention_heads": 4, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 6,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "vocab_size": 128}


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


def test_the_layer_order_and_the_pools_follow_the_file():
    cfg, _ = _model()
    assert cfg.period == ("mamba1", "mlp", "attention", "mlp", "mamba1",
                          "mlp")
    assert cfg.state_kind == "mamba1"
    assert cfg.cache_layers() == {"state": 4, "full": 2}
    pool = gen.init_paged_cache(cfg, 9, 4, state_slots=3)
    assert pool["ssm"].shape == (4, 3, 16, 128)
    assert pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (4, 3, 3 * 128)
    assert pool["k"].shape == (2, 9, 4, 1, 16)
    # the published file: layers 7 and 21 attend, two periods of 14
    full = dict(SMALL, num_hidden_layers=28, attn_layer_period=14,
                attn_layer_offset=7)
    kinds = arch.mixers(full)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert len(arch.period(full)) == 28


def test_a_period_of_two_kinds_of_state_is_refused():
    with pytest.raises(ValueError, match="one kind of state-space layer"):
        llama.LlamaConfig.tiny(layer_pattern=("mamba1", "mamba2"),
                               hybrid=llama.HybridConfig())


def test_no_cache_forward_agrees_with_the_reference():
    cfg, params = _model()
    toks = _tokens(37)
    got = np.asarray(llama.forward(params, jnp.asarray(toks)[None], cfg))[0]
    np.testing.assert_allclose(got, _ref_logits(params, toks), atol=TOL,
                               rtol=0)


def test_bfloat16_state_is_seen():
    """What the tolerance has to catch: the recurrence's float32 leaves
    (``dt_bias``, ``A_log``, ``D``) rounded to bfloat16 move the logits by
    a hundred times TOL or more."""
    cfg, params = _model()
    toks = _tokens(37)
    want = _ref_logits(params, toks)
    lo = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
                      if a.dtype == jnp.float32 else a, params)
    got = np.asarray(llama.forward(lo, jnp.asarray(toks)[None], cfg))[0]
    assert np.abs(got - want).max() > 100 * TOL


def _prefill(params, cfg, prompt, n_new, chunk, page=4, use_kernel=None):
    """Chunked prefill through the cache manager and the chunk program, as
    the engine drives it: the logits at the prompt's last token."""
    cache = PagedKVCache(cfg, 2, 64, page_size=page,
                         enable_prefix_cache=False)
    slot, done = 1, 0
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
            ctx_len=done, chunk_len=take, state_slot=slot,
            use_kernel=use_kernel)
        done += take
    return np.asarray(lg)[0], pool, cache


@pytest.mark.parametrize("chunk,kernel", [
    (8, ""), (12, ""), (20, ""), (32, ""), (12, "chunk"), (12, "decode")])
def test_chunked_prefill_then_decode_agree_with_the_reference(chunk, kernel):
    """Chunks that do (8, 32) and do not (12, 20) divide the prompt of 32;
    then six decode steps; twice with one program's kernels interpreted."""
    cfg, params = _model()
    seq = _tokens(38, seed=chunk)
    prompt, rest = seq[:32], seq[32:]
    want = _ref_logits(params, seq)
    fa.set_interpret(bool(kernel))
    try:
        first, pool, cache = _prefill(params, cfg, prompt, rest.size, chunk,
                                      use_kernel=(kernel == "chunk"))
        out, active = [first], jnp.asarray([False, True])
        lengths = np.zeros((2,), np.int32)
        step = jax.jit(lambda p, t, pool, bt, ln: gen.paged_decode_forward(
            p, t, pool, bt, ln, cfg, active=active,
            use_kernel=(kernel == "decode")))
        for i, tok in enumerate(rest):
            lengths[1] = prompt.size + i
            lg, pool = step(params, jnp.asarray([0, tok]), pool,
                            jnp.asarray(cache.block_tables),
                            jnp.asarray(lengths))
            out.append(np.asarray(lg)[1])
    finally:
        fa.set_interpret(False)
    np.testing.assert_allclose(np.stack(out), want[31:], atol=TOL, rtol=0)
    # the row that was not active kept its (zero) state and tail
    assert not np.asarray(pool["ssm"][:, 0]).any()
    assert not np.asarray(pool["conv"][:, 0]).any()


@pytest.mark.parametrize("cuts", [(5,), (8, 16), (1, 2, 3, 31)])
def test_the_scan_hands_its_state_across_chunks(cuts):
    """A mixer over a row cut into chunks, each from the state and the
    convolution tail the last one left, equals one pass over the row: the
    sequential recurrence, whatever the cut."""
    cfg, params = _model()
    lp = jax.tree.map(lambda a: a[0], params["layers"]["mamba1"])
    u = jax.random.normal(jax.random.key(4), (33, SMALL["hidden_size"]))
    s_shape, t_shape = hybrid.state_shapes(cfg)
    state, tail = jnp.zeros(s_shape), jnp.zeros(t_shape)
    whole, s_all, t_all = hybrid._mamba1_seq(u, lp, cfg, state, tail)
    parts, lo = [], 0
    for hi in cuts + (33,):
        # right-padded to 8 more than it holds: padding must not count
        pad = jnp.pad(u[lo:hi], ((0, 8), (0, 0)))
        f, state, tail = hybrid._mamba1_seq(pad, lp, cfg, state, tail,
                                            n_valid=hi - lo)
        parts.append(f[:hi - lo])
        lo = hi
    np.testing.assert_allclose(np.concatenate(parts), whole, atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(state, s_all, atol=1e-5, rtol=0)
    np.testing.assert_array_equal(tail, t_all)


@pytest.mark.parametrize("rows,base", [(3, 0), (3, 6), (8, 8), (16, 16)])
def test_state_update_kernel_agrees_with_its_twin(rows, base):
    """The Pallas kernel, interpreted, against the jnp twin: the rows from
    ``base`` on advance, an inactive one and every other row of the pool
    stay as they were (bit for bit)."""
    N, D = 16, 256
    k = jax.random.split(jax.random.key(rows + base), 7)
    pool = jax.random.normal(k[0], (40, N, D))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, D)))
    x = jax.random.normal(k[2], (rows, D))
    A = -jnp.exp(jax.random.uniform(k[3], (N, D), maxval=2.8))
    bm, cm = (jax.random.normal(kk, (rows, N)) for kk in k[4:6])
    active = jnp.arange(rows) != 1
    y0, p0 = mamba1.mamba1_state_update_reference(pool, base, dt, x, A, bm,
                                                  cm, active)
    fa.set_interpret(True)
    try:
        for blocks in ((8, 128), (8, 256), (16, 128)):
            y1, p1 = mamba1.mamba1_state_update_kernel(
                pool, base, dt, x, A, bm, cm, active, *blocks)
            live = np.asarray(active)
            # (a fused multiply-add rounds once where the twin rounds twice)
            np.testing.assert_allclose(np.asarray(y1)[live],
                                       np.asarray(y0)[live], rtol=1e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(p1, p0, rtol=1e-6, atol=1e-6)
            untouched = np.ones(40, bool)
            untouched[base:base + rows] = False
            untouched[base + 1] = True
            np.testing.assert_array_equal(np.asarray(p1)[untouched],
                                          np.asarray(pool)[untouched])
    finally:
        fa.set_interpret(False)


@pytest.mark.parametrize("kernel,rows,base", [
    (False, 32, 0), (False, 32, 64), (True, 32, 0), (True, 32, 96),
    (True, 16, 32), (True, 3, 6)])
def test_conv_step_agrees_with_the_plain_window_and_shift(kernel, rows,
                                                          base):
    """The convolution step, kernel interpreted or ``jnp`` twin, over a
    pool of 4 layers x 32 slots at a layer's ``base`` (and 3 rows at a
    ``base`` of 6: one block of the whole batch), against the window
    ``[tail, x]`` convolved and shifted in numpy: an active row's tail
    becomes the window's last three columns; an inactive row's tail, and
    every row outside the layer's, stay bit for bit."""
    K, D, R = 4, 256, 4 * 32
    B = 32 if rows % 16 == 0 else rows
    k = jax.random.split(jax.random.key(rows + base), 4)
    pool = jax.random.normal(k[0], (R, (K - 1) * D)).astype(jnp.bfloat16)
    x = jax.random.normal(k[1], (32, D)).astype(jnp.bfloat16)[:B]
    w = jax.random.normal(k[2], (K, D)).astype(jnp.bfloat16)
    b = jax.random.normal(k[3], (D,)).astype(jnp.bfloat16)
    active = jnp.arange(B) % 5 != 1
    fa.set_interpret(kernel)
    try:
        if kernel:
            c, new = mamba1.mamba1_conv_step_kernel(pool, base, x, w, b,
                                                    active, rows=rows)
        else:
            c, new = mamba1.mamba1_conv_step(pool, base, x, w, b, active,
                                             use_kernel=False)
    finally:
        fa.set_interpret(False)
    f = lambda a: np.asarray(a.astype(jnp.float32))
    tail = f(pool)[base:base + B].reshape(B, K - 1, D)
    win = np.concatenate([tail, f(x)[:, None]], axis=1)
    want = f(b) + (f(w)[None] * win).sum(axis=1)
    np.testing.assert_allclose(np.asarray(c), want, rtol=1e-5, atol=1e-5)
    live = np.asarray(active)
    got = f(new)[base:base + B].reshape(B, K - 1, D)
    np.testing.assert_array_equal(got[live], win[live, 1:])
    kept = np.ones(R, bool)
    kept[base:base + B] = ~live
    np.testing.assert_array_equal(np.asarray(new)[kept],
                                  np.asarray(pool)[kept])


@pytest.mark.parametrize("kernel", [False, True])
def test_a_tail_handed_between_chunks_and_decode_steps(kernel):
    """One row through a chunk, decode steps, a chunk from where the decode
    steps left it, and decode steps again: every tail handed from a chunk
    to the convolution step and back is read where the other wrote it, so
    the logits follow the reference's over the whole sequence; twice, the
    twins and every kernel (interpreted)."""
    cfg, params = _model()
    seq = _tokens(40, seed=12)
    want = _ref_logits(params, seq)
    cache = PagedKVCache(cfg, 2, 64, page_size=4, enable_prefix_cache=False)
    cache.admit(1, seq.size)
    table = jnp.asarray(cache.block_tables[1])
    pool, done, got = cache.pool, 0, {}
    active = jnp.asarray([False, True])
    step = jax.jit(lambda p, t, pool, bt, ln: gen.paged_decode_forward(
        p, t, pool, bt, ln, cfg, active=active, use_kernel=kernel))
    fa.set_interpret(kernel)
    try:
        for what, n in (("chunk", 13), ("decode", 6), ("chunk", 11),
                        ("decode", 9)):
            if what == "chunk":
                toks = np.zeros((1, 16), np.int32)
                toks[0, :n] = seq[done:done + n]
                lg, pool = gen.paged_prefill_chunk(
                    params, jnp.asarray(toks), pool, table, cfg,
                    ctx_cap=cache.ctx_cap_pages(cache.pages_for(done)) * 4,
                    ctx_len=done, chunk_len=n, state_slot=1,
                    use_kernel=kernel)
                done += n
                got[done - 1] = np.asarray(lg)[0]
                continue
            for _ in range(n):
                lg, pool = step(params, jnp.asarray([0, seq[done]]), pool,
                                jnp.asarray(cache.block_tables),
                                jnp.asarray([0, done], np.int32))
                got[done] = np.asarray(lg)[1]
                done += 1
    finally:
        fa.set_interpret(False)
    at = sorted(got)
    np.testing.assert_allclose(np.stack([got[i] for i in at]), want[at],
                               atol=TOL, rtol=0)
    assert not np.asarray(pool["conv"][:, 0]).any()


@pytest.mark.parametrize("T,lanes", [(9, 128), (24, 256)])
def test_chunk_scan_kernel_agrees_with_its_twin(T, lanes):
    """The scan kernel, interpreted, against the jnp twin, over blocks of
    channels; a token with ``dt`` 0 (padding) leaves the state as it was."""
    N, D = 16, 256
    k = jax.random.split(jax.random.key(T), 7)
    dt = jax.nn.softplus(jax.random.normal(k[0], (T, D)))
    dt = dt.at[T - 3:].set(0.0)
    x = jax.random.normal(k[1], (T, D))
    A = -jnp.exp(jax.random.uniform(k[2], (N, D), maxval=2.8))
    bm, cm = (jax.random.normal(kk, (T, N)) for kk in k[3:5])
    s0 = jax.random.normal(k[5], (N, D))
    y0, s_ref = mamba1.mamba1_chunk_scan_reference(dt, x, A, bm, cm, s0)
    _, s_short = mamba1.mamba1_chunk_scan_reference(
        dt[:T - 3], x[:T - 3], A, bm[:T - 3], cm[:T - 3], s0)
    np.testing.assert_array_equal(s_ref, s_short)
    fa.set_interpret(True)
    try:
        y1, s1 = mamba1.mamba1_chunk_scan_kernel(dt, x, A, bm, cm, s0, lanes)
    finally:
        fa.set_interpret(False)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("prefill_chunk", [8, 12])
def test_engine_greedy_tokens_lie_on_the_reference(prefill_chunk):
    """The engine and scheduler end to end: every served token is the
    reference's best at its position (its logit within TOL of the best),
    and the counters count what the programs did."""
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
    assert s["ssm_chunk_tokens_total"] == s["mamba1_chunk_tokens_total"] == 51
    # each answer's first token comes from its last chunk, the other six
    # from decode steps: 2 rows x 6 steps in each of 4 Mamba-1 layers
    assert s["ssm_state_rows_total"] == s["mamba1_state_rows_total"] == 48
    assert "mamba2_state_rows_total" not in s
    assert s["state_slots_used_peak"] == 2
    # 2 slots x 4 layers x (16 x 128 float32 + 3 x 128 float32)
    assert s["state_pool_bytes"] == 2 * 4 * (16 * 128 + 3 * 128) * 4


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


def test_a_preempted_row_resumes_by_re_prefill():
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
    while len(a.tokens) < 3:
        sched.step()
    b = sched.submit(_tokens(9, seed=10), max_new_tokens=2,
                     priority=Priority.HIGH)
    sched.step()
    assert a.preemptions == 1 and a.slot is None
    sched.run()
    assert a.done and b.done
    np.testing.assert_array_equal(a.output, ref)
    assert sched.stats()["ssm_state_rebuilds_total"] == 1


def test_the_lower_precision_tier_is_accepted():
    """What ``--plant control`` switches on: 8-bit attention, Mamba-1 and
    MLP projections, an 8-bit KV pool; the tied head stays."""
    cfg, params = _model()
    eng = ContinuousBatchingEngine(
        params, cfg, max_batch=2, page_size=4, max_len=64, prefill_chunk=8,
        enable_prefix_cache=False, weight_bits=8, kv_cache_dtype="int8")
    layers = eng.params["layers"]
    for kind, names in (("mamba1", ("w_in", "w_x", "w_dt", "w_out")),
                        ("attention", ("wq", "wk", "wv", "wo")),
                        ("mlp", ("wg", "wu", "wd"))):
        for n in names:
            assert layers[kind][n].dtype == jnp.int8, (kind, n)
    assert layers["mamba1"]["A_log"].dtype == jnp.float32
    assert "lm_head" not in eng.params
    out = eng.generate([_tokens(17, seed=11)], max_new_tokens=5)[0]
    assert out.size == 22


@pytest.mark.parametrize("what,kw", [
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("host_tier", dict(host_tier=True)),
    ("speculative", dict(spec_k=2))])
def test_the_engine_refuses_by_name(what, kw):
    cfg, params = _model()
    kw = dict(dict(max_batch=2, page_size=4, max_len=64,
                   enable_prefix_cache=False), **kw)
    with pytest.raises(ValueError, match=f"{what}.*state-space layers"):
        ContinuousBatchingEngine(params, cfg, **kw)
