"""Pipeline schedule tests: interleaved (VPP), 1F1B, zero-bubble vs the
GPipe wavefront and a sequential (no-pipeline) reference
(reference: test/collective/fleet/hybrid_parallel_pp_* — parallel loss must
equal the single-card loss)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.distributed.fleet.meta_parallel import pp_spmd

P_ = 4          # pipeline stages
M = 8           # microbatches (interleave needs M % P == 0)
MB, D = 2, 8    # microbatch size, feature dim


def _mk(seed, shape):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32) * 0.3


def _stage_fn_w(p, x):
    return jnp.tanh(x @ p["w"])


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _loss_fn(head, y, label):
    return jnp.mean((y @ head["w"] - label) ** 2)


def _mesh():
    return Mesh(np.array(jax.devices()[:P_]), ("pp",))


def _stage_params(n):
    return [{"w": _mk(10 + i, (D, D)), "b": _mk(50 + i, (D,))}
            for i in range(n)]


def _seq_loss(per_stage, head, mbs, labels):
    def one(x, l):
        for p in per_stage:
            x = _stage_fn(p, x)
        return _loss_fn(head, x, l)
    return jnp.mean(jax.vmap(one)(mbs, labels))


@pytest.fixture
def data():
    mbs = _mk(1, (M, MB, D))
    labels = _mk(2, (M, MB, D))
    head = {"w": _mk(3, (D, D))}
    return mbs, labels, head


def test_interleave_matches_sequential(data):
    mbs, labels, head = data
    mesh = _mesh()
    chunks = 2
    per_stage = _stage_params(P_ * chunks)
    stacked = pp_spmd.stack_stage_params_interleaved(per_stage, mesh, chunks)

    outs = pipe = jax.jit(lambda sp, mb: pp_spmd.pipeline_interleave(
        _stage_fn, sp, mb, mesh, chunks))(stacked, mbs)

    def seq(x):
        for p in per_stage:
            x = _stage_fn(p, x)
        return x
    ref = jax.vmap(seq)(mbs)
    np.testing.assert_allclose(np.asarray(outs), np.asarray(ref),
                               atol=1e-5)


def test_interleave_grads_match_sequential(data):
    mbs, labels, head = data
    mesh = _mesh()
    chunks = 2
    per_stage = _stage_params(P_ * chunks)
    stacked = pp_spmd.stack_stage_params_interleaved(per_stage, mesh, chunks)

    def pp_loss(sp, hd, mb):
        outs = pp_spmd.pipeline_interleave(_stage_fn, sp, mb, mesh, chunks)
        return jnp.mean(jax.vmap(lambda y, l: _loss_fn(hd, y, l))(
            outs, labels))

    lv, g = jax.jit(jax.value_and_grad(pp_loss, argnums=(0, 1, 2)))(
        stacked, head, mbs)
    lr, gr = jax.value_and_grad(
        lambda sp, hd, mb: _seq_loss(
            [jax.tree.map(lambda a: a[s % P_, s // P_], sp)
             for s in range(P_ * chunks)], hd, mb, labels),
        argnums=(0, 1, 2))(stacked, head, mbs)
    assert abs(float(lv) - float(lr)) < 1e-6
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("defer_dw", [False, True])
def test_1f1b_matches_sequential_ad(data, defer_dw):
    mbs, labels, head = data
    mesh = _mesh()
    per_stage = _stage_params(P_)
    stacked = pp_spmd.stack_stage_params(per_stage, mesh)

    loss, dw, dhead, dmbs = jax.jit(
        lambda sp, hd, mb, lb: pp_spmd.pipeline_1f1b(
            _stage_fn, _loss_fn, sp, hd, mb, lb, mesh,
            defer_dw=defer_dw))(stacked, head, mbs, labels)

    def ref_loss(sp, hd, mb):
        return _seq_loss([jax.tree.map(lambda a: a[s], sp)
                          for s in range(P_)], hd, mb, labels)

    lr, (gw, gh, gm) = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        stacked, head, mbs)
    assert abs(float(loss) - float(lr)) < 1e-6
    for a, b in zip(jax.tree.leaves(dw), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    for a, b in zip(jax.tree.leaves(dhead), jax.tree.leaves(gh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    np.testing.assert_allclose(np.asarray(dmbs), np.asarray(gm), atol=2e-5)


def test_1f1b_matches_gpipe_loss(data):
    """Schedule equivalence: 1F1B loss equals the GPipe-path loss."""
    mbs, labels, head = data
    mesh = _mesh()
    per_stage = _stage_params(P_)
    stacked = pp_spmd.stack_stage_params(per_stage, mesh)
    l_gpipe = jax.jit(lambda sp, hd, mb: pp_spmd.pipeline_loss_spmd(
        _stage_fn, _loss_fn, sp, hd, mb, labels, mesh))(stacked, head, mbs)
    l_1f1b, _, _, _ = jax.jit(lambda sp, hd, mb, lb: pp_spmd.pipeline_1f1b(
        _stage_fn, _loss_fn, sp, hd, mb, lb, mesh))(stacked, head, mbs,
                                                    labels)
    assert abs(float(l_gpipe) - float(l_1f1b)) < 1e-6


def test_1f1b_residency_bounded_by_depth():
    """1F1B's activation residency must scale with pipeline depth (ring of
    2P-1 slots), not with the microbatch count M — grow M and the compiled
    peak temp memory of the fwd+bwd program should stay ~flat, unlike
    GPipe whose AD saves every tick's residuals."""
    mesh = _mesh()
    per_stage = _stage_params(P_)
    stacked = pp_spmd.stack_stage_params(per_stage, mesh)
    head = {"w": _mk(3, (D, D))}

    def temp_bytes(m, mode):
        mbs = jax.ShapeDtypeStruct((m, 64, D), jnp.float32)
        labels = jax.ShapeDtypeStruct((m, 64, D), jnp.float32)
        if mode == "1f1b":
            f = jax.jit(lambda sp, hd, mb, lb: pp_spmd.pipeline_1f1b(
                _stage_fn, _loss_fn, sp, hd, mb, lb, mesh))
        else:
            f = jax.jit(jax.grad(
                lambda sp, hd, mb, lb: pp_spmd.pipeline_loss_spmd(
                    _stage_fn, _loss_fn, sp, hd, mb, lb, mesh),
                argnums=0))
        comp = f.lower(stacked, head, mbs, labels).compile()
        ma = comp.memory_analysis()
        return ma.temp_size_in_bytes

    small, big = temp_bytes(8, "1f1b"), temp_bytes(64, "1f1b")
    gsmall, gbig = temp_bytes(8, "gpipe"), temp_bytes(64, "gpipe")
    mb_bytes = 64 * D * 4  # one [mb, D] f32 microbatch activation
    # 1f1b growth per extra microbatch must be IO-bound (the [M] feed/dx
    # buffers, ~1-2 activations) — NOT the per-tick residual chain
    assert (big - small) / 56 < 2.5 * mb_bytes, (small, big)
    # gpipe's AD saves residuals per tick: several activations per mb
    assert (gbig - gsmall) / 56 > 3.5 * mb_bytes, (gsmall, gbig)
    # and at M=64 the 1f1b program must be much leaner overall
    assert big < gbig / 2, (big, gbig)


def test_interleave_1f1b_matches_sequential(data):
    """Hand-written depth-bounded VPP backward (round-5): loss AND all
    grads equal the sequential formulation, like the plain-1F1B test."""
    mbs, labels, head = data
    mesh = _mesh()
    chunks = 2
    per_stage = _stage_params(P_ * chunks)
    stacked = pp_spmd.stack_stage_params_interleaved(per_stage, mesh,
                                                     chunks)

    loss, dw, dhead, dmbs = jax.jit(
        lambda sp, hd, mb, lb: pp_spmd.pipeline_interleave_1f1b(
            _stage_fn, _loss_fn, sp, hd, mb, lb, mesh, chunks))(
        stacked, head, mbs, labels)
    # ZB-V (deferred dW) must produce identical results
    loss_z, dw_z, dhead_z, dmbs_z = jax.jit(
        lambda sp, hd, mb, lb: pp_spmd.pipeline_interleave_1f1b(
            _stage_fn, _loss_fn, sp, hd, mb, lb, mesh, chunks,
            defer_dw=True))(stacked, head, mbs, labels)
    np.testing.assert_allclose(float(loss_z), float(loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(dw_z), jax.tree.leaves(dw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
    for a, b in zip(jax.tree.leaves(dhead_z), jax.tree.leaves(dhead)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(dmbs_z), np.asarray(dmbs),
                               atol=1e-5)

    def ref_loss(sp, hd, mb):
        # canonical virtual stage s lives at [s % P, s // P]
        return _seq_loss([jax.tree.map(lambda a: a[s % P_, s // P_], sp)
                          for s in range(P_ * chunks)], hd, mb, labels)

    lr, (gw, gh, gm) = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        stacked, head, mbs)
    assert abs(float(loss) - float(lr)) < 1e-6
    for a, b in zip(jax.tree.leaves(dw), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5)
    for a, b in zip(jax.tree.leaves(dhead), jax.tree.leaves(gh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(dmbs), np.asarray(gm),
                               atol=2e-5)


def test_interleave_1f1b_residency_bounded_by_depth():
    """The point of the hand-written VPP backward: temp memory must stay
    ~flat as M grows (ring of 2V-1 slots), unlike AD-VPP whose residuals
    grow with M (ROADMAP D5 keeps the prediction for a 13B recipe)."""
    mesh = _mesh()
    chunks = 2
    per_stage = _stage_params(P_ * chunks)
    stacked = pp_spmd.stack_stage_params_interleaved(per_stage, mesh,
                                                     chunks)
    head = {"w": _mk(3, (D, D))}

    def temp_bytes(m, mode):
        mbs = jax.ShapeDtypeStruct((m, 64, D), jnp.float32)
        labels = jax.ShapeDtypeStruct((m, 64, D), jnp.float32)
        if mode == "hand":
            f = jax.jit(
                lambda sp, hd, mb, lb: pp_spmd.pipeline_interleave_1f1b(
                    _stage_fn, _loss_fn, sp, hd, mb, lb, mesh, chunks))
        else:
            def ad_loss(sp, hd, mb, lb):
                outs = pp_spmd.pipeline_interleave(_stage_fn, sp, mb,
                                                   mesh, chunks)
                return jnp.mean(jax.vmap(
                    lambda y, l: _loss_fn(hd, y, l))(outs, lb))
            f = jax.jit(jax.grad(ad_loss, argnums=0))
        comp = f.lower(stacked, head, mbs, labels).compile()
        return comp.memory_analysis().temp_size_in_bytes

    small, big = temp_bytes(8, "hand"), temp_bytes(64, "hand")
    mb_bytes = 64 * D * 4
    assert (big - small) / 56 < 2.5 * mb_bytes, (small, big)
    asmall, abig = temp_bytes(8, "ad"), temp_bytes(64, "ad")
    assert (abig - asmall) > 2 * (big - small), (
        "AD-VPP was expected to grow with M", asmall, abig, small, big)


@pytest.mark.parametrize("p_, chunks, m", [(2, 3, 4), (4, 2, 4),
                                           (2, 2, 8), (2, 4, 2)])
def test_interleave_1f1b_closed_forms_sweep(p_, chunks, m):
    """Property sweep of the hand-written VPP schedule's closed forms
    over pipeline depth x chunk count x microbatch count — the unit
    indexing, ring sizing (2V-1), and wrap-around permute continuity
    must hold for ANY (P, C, M % P == 0), not just the C=2 shapes the
    main tests use."""
    mesh = Mesh(np.array(jax.devices()[:p_]), ("pp",))
    v = p_ * chunks
    rng = np.random.RandomState(p_ * 100 + chunks * 10 + m)

    per_stage = [{"w": jnp.asarray(rng.randn(D, D).astype("float32"))
                  * 0.3} for _ in range(v)]
    stacked = pp_spmd.stack_stage_params_interleaved(per_stage, mesh,
                                                     chunks)
    head = {"w": jnp.asarray(rng.randn(D, D).astype("float32"))}
    mbs = jnp.asarray(rng.randn(m, 2, D).astype("float32"))
    labels = jnp.asarray(rng.randn(m, 2, D).astype("float32"))

    loss, dw, dhead, dmbs = jax.jit(
        lambda sp, hd, mb, lb: pp_spmd.pipeline_interleave_1f1b(
            _stage_fn_w, _loss_fn, sp, hd, mb, lb, mesh, chunks))(
        stacked, head, mbs, labels)

    def ref_loss(sp, hd, mb):
        stages = [jax.tree.map(lambda a: a[s % p_, s // p_], sp)
                  for s in range(v)]

        def one(x, l):
            for pstage in stages:
                x = _stage_fn_w(pstage, x)
            return _loss_fn(hd, x, l)
        return jnp.mean(jax.vmap(one)(mb, labels))

    lr, (gw, gh, gm) = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        stacked, head, mbs)
    np.testing.assert_allclose(float(loss), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(dw), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5)
    for a, b in zip(jax.tree.leaves(dhead), jax.tree.leaves(gh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5)
    np.testing.assert_allclose(np.asarray(dmbs), np.asarray(gm),
                               atol=3e-5)
