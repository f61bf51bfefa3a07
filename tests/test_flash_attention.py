"""Pallas flash-attention kernel tests (interpret mode on CPU)
(reference: test/legacy_test/test_flash_attention.py)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.ops.pallas.flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _ref(q, k, v, causal):
    B, S, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        m = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _check_fwd_bwd(q, k, v, causal, ref, tol=1e-4, **kw):
    out = fa.flash_attention(q, k, v, causal=causal, **kw)
    assert float(jnp.abs(out - ref(q, k, v, causal)).max()) < 2e-5
    g = jax.grad(lambda *a: (fa.flash_attention(*a, causal=causal, **kw)
                             ** 2).sum(), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (ref(*a, causal) ** 2).sum(), (0, 1, 2))(
        q, k, v)
    for a, b in zip(g, gr):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < tol, float(jnp.abs(a - b).max())


def _qkv(key, sq, sk, h, hk, d, b=1):
    ks = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(ks[0], (b, sq, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, sk, hk, d), jnp.float32),
            jax.random.normal(ks[2], (b, sk, hk, d), jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_bwd_matches_xla(causal):
    _check_fwd_bwd(*_qkv(0, 256, 256, 2, 2, 64), causal, _ref, tol=5e-5)


def test_flash_gqa():
    B, S, H, HK, D = 1, 128, 4, 2, 32
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, HK, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, HK, D), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    ref = _ref(q, kr, vr, True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_functional_flash_attention_api():
    q = paddle.randn([1, 128, 2, 32])
    out, _ = F.flash_attention(q, q, q, causal=True)
    assert out.shape == [1, 128, 2, 32]


def test_sdpa_with_mask():
    B, S, H, D = 1, 16, 2, 8
    q = paddle.randn([B, S, H, D])
    mask = paddle.to_tensor(np.tril(np.ones((S, S), bool)))
    out = F.scaled_dot_product_attention(q, q, q, attn_mask=mask)
    out_causal = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    np.testing.assert_allclose(out.numpy(), out_causal.numpy(), atol=1e-5)


def test_flash_attn_unpadded_segments():
    # two sequences of length 3 and 5 packed into 8 tokens: attention must
    # not cross the boundary
    T, H, D = 8, 1, 8
    q = paddle.randn([T, H, D])
    cu = paddle.to_tensor(np.array([0, 3, 8], np.int32))
    out, _ = F.flash_attn_unpadded(q, q, q, cu, cu, 5, 5,
                                   scale=1.0 / np.sqrt(D))
    # reference: blockwise softmax within segments
    qv = q.numpy()[:, 0]
    s = qv @ qv.T / np.sqrt(D)
    mask = np.zeros((T, T), bool)
    mask[:3, :3] = True
    mask[3:, 3:] = True
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = p @ qv
    np.testing.assert_allclose(out.numpy()[:, 0], ref, atol=1e-4)


def _ref_rect(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        m = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(160, 160), (128, 192), (192, 320)])
def test_flash_nondivisible_blocks(causal, sq, sk):
    """Sequence lengths NOT divisible by the block size: the last padded
    block must be masked out of the softmax and out of dq/dk/dv
    (ADVICE r1 high: unmasked Pallas out-of-bounds padding)."""
    _check_fwd_bwd(*_qkv(2, sq, sk, 2, 2, 64), causal, _ref_rect,
                   block_q=128, block_k=128)


# at 512 x 512 a head has interior, diagonal and skipped blocks at both
# block sizes (128/256: the diagonal is cut to the q block's 128); the
# other two shapes put the diagonal's end inside K and past it
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256)])
@pytest.mark.parametrize("sq,sk", [(512, 512), (256, 512), (512, 256)])
def test_flash_every_kind_of_block(causal, block_q, block_k, sq, sk):
    from paddle_tpu.models.llama import _attention_jnp
    if causal:
        kinds = fa.causal_blocks(sq, sk, block_q, block_k)
        assert kinds[1] > 0 and (sq != sk or min(kinds) > 0), kinds
    ref = _attention_jnp if sq == sk else _ref_rect
    _check_fwd_bwd(*_qkv(3, sq, sk, 2, 2, 64), causal, ref,
                   block_q=block_q, block_k=block_k)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_tiled_majors(causal, monkeypatch):
    """A sequence over the VMEM budget is tiled: two major blocks of K/V
    (forward, dq) and of q/do (dkv), the causal ones above the diagonal
    clamped by the index maps."""
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 256 * 64 * 4)
    assert fa._tiling(512, 128, 128, 64 * 4) == (256, 128)
    _check_fwd_bwd(*_qkv(4, 512, 512, 2, 2, 64), causal, _ref_rect,
                   block_q=128, block_k=128)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("rep", [2, 8])
def test_flash_gqa_through_the_index_map(rep):
    """K and V reach all three kernels with their own heads (no repeat in
    the program), and dk/dv come back summed over a KV head's query
    heads."""
    B, S, HK, D = 2, 256, 1, 64
    q, k, v = _qkv(5, S, S, HK * rep, HK, D, b=B)
    kw = dict(causal=True, block_q=128, block_k=128)

    def loss(q, k, v):
        return (fa.flash_attention(q, k, v, **kw) ** 2).sum()
    calls = list(_pallas_calls(jax.make_jaxpr(
        jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr))
    assert len(calls) == 3
    for eqn in calls:
        assert eqn.invars[0].aval.shape == (B * HK * rep, S, D)
        assert eqn.invars[1].aval.shape == (B * HK, S, D)
        assert eqn.invars[2].aval.shape == (B * HK, S, D)

    def repeated(q, k, v, causal):
        return _ref(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                    causal)
    _check_fwd_bwd(q, k, v, True, repeated, block_q=128, block_k=128)


def _brute_kinds(sq, sk, bq, bk, kept):
    """Blocks of the padded (sq, sk) square by what ``kept(r, c)`` holds."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    r, c = np.mgrid[:nq * bq, :nk * bk]
    m = kept(r, c).reshape(nq, bq, nk, bk)
    return m.all((1, 3)), m.any((1, 3))


@pytest.mark.parametrize("sq,sk,bq,bk", [
    (1024, 1024, 128, 256), (1024, 1024, 128, 128), (512, 512, 128, 256),
    (256, 512, 128, 128), (512, 256, 128, 128), (192, 320, 128, 128),
    (384, 384, 256, 128)])
def test_causal_blocks_against_a_mask(sq, sk, bq, bk):
    full, some = _brute_kinds(sq, sk, bq, bk,
                              lambda r, c: (r >= c) & (c < sk))
    assert fa.causal_blocks(sq, sk, bq, bk) == (
        int(full.sum()), int((some & ~full).sum()), int((~some).sum()))
    # the dkv kernel's mirror: for every key block the q steps it runs
    # masked and unmasked, against rows that exist
    full, some = _brute_kinds(sq, sk, bq, bk,
                              lambda r, c: (r >= c) & (r < sq))
    nq = full.shape[0]
    for j in range(full.shape[1]):
        t0, t1, t2, t3 = fa._q_ranges(j * bk, bk, sq, True, 0, nq * bq, bq)
        masked = set(range(t0, min(t1, t2))) | set(range(max(t0, t2), t3))
        clear = set(range(t1, t2))
        assert not masked & clear
        for i in range(nq):
            assert (i in clear) == bool(full[i, j]), (i, j)
            assert not some[i, j] or i in masked | clear, (i, j)


def test_causal_blocks_of_the_train_cell():
    # ernie45-0.3b.train-4k: what the parent stepped and what is run now
    assert fa.causal_blocks(4096, 4096, 512, 1024) == (12, 8, 12)
    assert fa.causal_blocks(4096, 4096, 512, 512) == (28, 8, 28)
