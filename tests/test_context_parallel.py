"""Ring attention / Ulysses context-parallel tests.

Pattern: 4-device "cp" mesh on the CPU backend (SURVEY §4 implication (b));
parallel result must match single-device dense attention (fwd and grads) —
the same parity contract the reference's fleet tests assert for its
parallelisms.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from paddle_tpu.distributed.fleet.meta_parallel import context_parallel as cp
from paddle_tpu.models.llama import _attention


def make_mesh(n=4):
    return Mesh(np.asarray(jax.devices()[:n]), ("cp",))


def rand_qkv(b=2, s=32, h=4, hk=None, d=16, seed=0):
    rng = np.random.default_rng(seed)
    hk = hk or h
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def run_sharded(fn, mesh, q, k, v):
    spec = P(None, "cp", None, None)
    f = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec, check_vma=False)
    return jax.jit(f)(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(causal):
    mesh = make_mesh()
    q, k, v = rand_qkv()
    got = run_sharded(
        lambda a, b, c: cp.ring_attention(a, b, c, "cp", causal=causal),
        mesh, q, k, v)
    ref = _attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_gqa():
    mesh = make_mesh()
    q, k, v = rand_qkv(h=8, hk=2)
    got = run_sharded(
        lambda a, b, c: cp.ring_attention(a, b, c, "cp", causal=True),
        mesh, q, k, v)
    ref = _attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_grads_match_dense():
    mesh = make_mesh()
    q, k, v = rand_qkv(s=16)

    def loss_ring(q, k, v):
        spec = P(None, "cp", None, None)
        f = shard_map(
            lambda a, b, c: cp.ring_attention(a, b, c, "cp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return jnp.sum(f(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_attention(q, k, v, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense(causal):
    mesh = make_mesh()
    q, k, v = rand_qkv(h=8)  # heads divisible by cp=4
    got = run_sharded(
        lambda a, b, c: cp.ulysses_attention(a, b, c, "cp", causal=causal),
        mesh, q, k, v)
    ref = _attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_grads():
    mesh = make_mesh()
    q, k, v = rand_qkv(s=16, h=4)

    def loss_u(q, k, v):
        spec = P(None, "cp", None, None)
        f = shard_map(
            lambda a, b, c: cp.ulysses_attention(a, b, c, "cp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return jnp.sum(f(q, k, v) ** 2)

    g1 = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(lambda a, b, c: jnp.sum(
        _attention(a, b, c, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ring_partials_match_einsum_ring(causal):
    """The flash-kernel-backed ring fwd (pallas partials + lse merge)
    equals the einsum ring and dense attention — fwd AND grads (the
    einsum backward consumes the flash fwd's saved out/lse)."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    mesh = make_mesh()
    # flash gate needs S_local % 128 == 0 and D >= 64
    q, k, v = rand_qkv(b=1, s=512, h=2, d=64, seed=3)
    fa.set_interpret(True)
    try:
        assert cp._flash_ring_ok(
            jnp.zeros((1, 2, 128, 64)))      # the gate is actually on
        got = run_sharded(
            lambda a, b, c: cp.ring_attention(a, b, c, "cp",
                                              causal=causal),
            mesh, q, k, v)
        g1 = jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(run_sharded(
                lambda x, y, z: cp.ring_attention(x, y, z, "cp",
                                                  causal=causal),
                mesh, a, b, c) ** 2), argnums=(0, 1, 2)))(q, k, v)
    finally:
        fa.set_interpret(False)
    ref = _attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-4)
    g2 = jax.grad(lambda a, b, c: jnp.sum(
        _attention(a, b, c, causal=causal) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_ring_gqa_grads_match_dense():
    """GQA backward through the ring: the traveling dk/dv buffers carry
    only the UNREPEATED heads; grads must still match dense attention
    (whose kv-repeat autodiff sums over the query-head groups)."""
    mesh = make_mesh()
    q, k, v = rand_qkv(h=8, hk=2, seed=11)

    def loss_ring(q, k, v):
        spec = P(None, "cp", None, None)
        f = shard_map(
            lambda a, b, c: cp.ring_attention(a, b, c, "cp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return jnp.sum(f(q, k, v) ** 2)

    def loss_dense(q, k, v):
        kk = jnp.repeat(k, 4, axis=2)
        vv = jnp.repeat(v, 4, axis=2)
        return jnp.sum(_attention(q, kk, vv, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_flash_ring_gqa_fwd_and_grads():
    """The novel composition: flash forward with the kv-index-map GQA
    feed (unrepeated kv, kernel divides the batch-head index) producing
    the lse the GQA einsum backward consumes — fwd AND grads vs dense."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    mesh = make_mesh()
    q, k, v = rand_qkv(b=1, s=512, h=4, hk=2, d=64, seed=12)

    def dense(a, b, c):
        return _attention(a, jnp.repeat(b, 2, axis=2),
                          jnp.repeat(c, 2, axis=2), causal=True)

    fa.set_interpret(True)
    try:
        got = run_sharded(
            lambda a, b, c: cp.ring_attention(a, b, c, "cp", causal=True),
            mesh, q, k, v)
        g1 = jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(run_sharded(
                lambda x, y, z: cp.ring_attention(x, y, z, "cp",
                                                  causal=True),
                mesh, a, b, c) ** 2), argnums=(0, 1, 2)))(q, k, v)
    finally:
        fa.set_interpret(False)
    ref = dense(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-4)
    g2 = jax.grad(lambda a, b, c: jnp.sum(dense(a, b, c) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_ulysses_gqa_minimal_repeat():
    """Ulysses GQA: kv repeats only to n-divisibility (h=8,hk=2,n=4 ->
    rep 2, not 4); the local attention maps q-head groups to kv heads —
    result must match dense GQA attention, fwd and grads."""
    mesh = make_mesh()
    q, k, v = rand_qkv(s=16, h=8, hk=2, seed=13)

    def dense(a, b, c):
        return _attention(a, jnp.repeat(b, 4, axis=2),
                          jnp.repeat(c, 4, axis=2), causal=True)

    spec = P(None, "cp", None, None)
    f = shard_map(
        lambda a, b, c: cp.ulysses_attention(a, b, c, "cp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    got = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    g1 = jax.jit(jax.grad(lambda a, b, c: jnp.sum(f(a, b, c) ** 2),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(lambda a, b, c: jnp.sum(dense(a, b, c) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)
