"""The K/V chunk's attention over its row's pages
(``ops/pallas/paged_chunk_attention.py``): the Pallas kernel in interpret
mode against its ``jnp`` twin and against ``generate._attn_with_cache`` over
the same keys laid out as a dense cache, on full and sliding layers, at the
query heads a key head of the dense, Trinity and Mellum cells (2, 6, 8);
then the chunk program that feeds it, whose pools outside the row's new
positions come back as they went in and whose logits and rows equal the
gathering path's."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import generate as gen
from paddle_tpu.models import llama
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_chunk_attention as pca

PAGE, HD, POOL = 8, 128, 24


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def pools(nkv, dtype, seed=0):
    rng = np.random.default_rng(seed)
    shape = (POOL, PAGE, nkv, HD)
    return (jnp.asarray(rng.normal(size=shape), dtype),
            jnp.asarray(rng.normal(size=shape), dtype),
            jnp.asarray(rng.permutation(np.arange(1, POOL)), jnp.int32))


# rep, kv heads, context, valid chunk tokens, window, dtype, rows a grid
# step (None: the module's); each case names what it covers
CASES = {
    "full_rep2_empty_context": (2, 2, 0, 16, None, jnp.float32, None),
    "full_rep6_prefix_share_mid_page": (6, 2, 13, 16, None, jnp.float32,
                                        None),
    "full_rep8_last_chunk_short": (8, 1, 21, 5, None, jnp.float32, None),
    "full_rep2_block_past_the_prompt": (2, 2, 19, 5, None, jnp.float32, 32),
    "window_rep2_edge_inside_a_page": (2, 4, 37, 16, 12, jnp.float32, None),
    "window_rep6_longer_than_context": (6, 2, 5, 9, 12, jnp.float32, 48),
    "window_rep8_bf16_pairs_of_heads": (8, 2, 50, 16, 20, jnp.bfloat16,
                                        None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_twin_and_the_dense_attention(case, interpret,
                                                        monkeypatch):
    rep, nkv, ctx, n, window, dtype, rows = CASES[case]
    if rows is not None:
        monkeypatch.setattr(pca, "_CHUNK_ROWS", rows)
        pca._chunk_call.clear_cache()
    C, nh = 16, rep * nkv
    kp, vp, table = pools(nkv, dtype)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(C, nh, HD)), dtype)
    got = pca.kv_chunk_attention_kernel(q, kp, vp, table, ctx, n,
                                        window=window)
    pca._chunk_call.clear_cache()
    twin = pca.kv_chunk_attention_reference(q, kp, vp, table, ctx,
                                            window=window)
    # the same keys as a dense cache, the chunk its last C positions
    ck = kp[table].reshape(1, -1, nkv, HD)
    cv = vp[table].reshape(1, -1, nkv, HD)
    dense = gen._attn_with_cache(q[None, :n], ck, cv, ctx + n, nh,
                                 window=window)[0]
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    np.testing.assert_allclose(f32(twin[:n]), f32(dense), rtol=tol, atol=tol)
    np.testing.assert_allclose(f32(got[:n]), f32(twin[:n]), rtol=tol,
                               atol=tol)
    if rows is not None and rows // nh < C:
        # a block wholly past the valid tokens fetches nothing: zeros
        tq = pca._block_tokens(C, nh)
        past = -(-n // tq) * tq
        assert past < C and not np.any(f32(got[past:]))


def test_a_wide_dense_attention_takes_a_key_head_at_a_time(monkeypatch):
    """``generate._attn_with_cache`` (the prompt's prefill, the gathering
    chunk path) above ``SCORES_WHOLE_BYTES``: one key head's group of query
    heads at a time, the same result."""
    kp, vp, table = pools(2, jnp.float32)
    q = jnp.asarray(np.random.default_rng(2).normal(size=(1, 16, 12, HD)),
                    jnp.float32)
    ck, cv = (a[table].reshape(1, -1, 2, HD) for a in (kp, vp))
    whole = gen._attn_with_cache(q, ck, cv, 40, 12, window=12)
    monkeypatch.setattr(gen, "SCORES_WHOLE_BYTES", 1024)
    blocked = gen._attn_with_cache(q, ck, cv, 40, 12, window=12)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)


def test_the_dispatch_takes_the_twin_where_the_kernel_cannot_split_heads():
    kp = jnp.zeros((4, PAGE, 3, HD), jnp.bfloat16)
    assert not pca.kernel_reads(kp)
    assert pca.kernel_reads(kp.astype(jnp.float32))
    assert pca.kernel_reads(jnp.zeros((4, PAGE, 1, HD), jnp.bfloat16))
    assert pca.kernel_reads(jnp.zeros((4, PAGE, 8, HD), jnp.bfloat16))


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("pattern", [None, ("sliding", "full")],
                         ids=["plain", "sliding"])
def test_a_chunk_writes_its_rows_and_nothing_else(quant, pattern,
                                                  interpret):
    """The chunk program over pages against the gathering path (``fused``
    off the chip is its ``jnp`` composition): the same logits and rows, and
    every pool position but the chunk's valid ones and the trash page as it
    was."""
    cfg = llama.LlamaConfig.tiny(num_layers=2, layer_pattern=pattern,
                                 sliding_window=12 if pattern else None)
    params = llama.init_params(jax.random.key(3), cfg)
    paged = gen.init_paged_cache(cfg, 16, PAGE,
                                 kv_dtype="int8" if quant else None,
                                 window_pages=16 if pattern else None)
    rng = np.random.default_rng(5)
    paged = {n: jnp.asarray(rng.normal(size=a.shape) * 3, a.dtype)
             for n, a in paged.items()}
    row = {"": np.array([3, 7, 5, 9, 12], np.int32),
           "_w": np.array([1, 4, 11, 2, 6], np.int32)}
    table = {s: jnp.asarray(np.concatenate([r, np.zeros(3, np.int32)]))
             for s, r in row.items()}
    ctx, n, C = 13, 10, 16
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, C)), jnp.int32)
    run = lambda **kw: gen.paged_prefill_chunk(  # noqa: E731
        params, tokens, paged, table[""], cfg, ctx_cap=16, ctx_len=ctx,
        chunk_len=n, window_table=table["_w"] if pattern else None, **kw)
    logits, new = run(use_kernel=not quant)
    ref_logits, ref = run(fused=True)        # the gathering path
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-5, atol=2e-5)
    for name, a in new.items():
        sfx = "_w" if name.endswith("_w") else ""
        written = np.zeros((16, PAGE), bool)
        for p in range(ctx, ctx + n):
            written[row[sfx][p // PAGE], p % PAGE] = True
        keep = ~written
        keep[0] = False                      # the trash page takes padding
        a, before = np.asarray(a), np.asarray(paged[name])
        np.testing.assert_array_equal(a[:, keep], before[:, keep])
        np.testing.assert_allclose(a[:, written], np.asarray(ref[name])[
            :, written], rtol=2e-5, atol=2e-5)
        assert not np.allclose(a[:, written], before[:, written])


def test_a_dense_config_counts_the_keys_its_chunks_attend():
    """Every config that keeps keys and values counts its chunks' (query,
    key) pairs, a plain one too; the decode step's counts stay a windowed
    config's."""
    from paddle_tpu.inference.predictor import ContinuousBatchingEngine
    from paddle_tpu.serving import ServingScheduler
    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64)
    params = llama.init_params(jax.random.key(4), cfg)
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=PAGE,
                                   max_len=64, prefill_chunk=16)
    sched = ServingScheduler(eng)
    rng = np.random.default_rng(6)
    for n in (21, 30):
        sched.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                     max_new_tokens=3)
    sched.run()
    s = sched.stats()
    # however a prompt is cut into chunks, its token i sees i + 1 keys
    assert s["chunk_full_keys_attended_total"] == 2 * (21 * 22 + 30 * 31) // 2
    assert "chunk_window_keys_attended_total" not in s
    assert "full_keys_attended_total" not in s
