"""Test harness config.

Forces CPU platform BEFORE jax backend init and presents 8 virtual devices
so sharding/collective tests run without TPU hardware — the reference's
no-cluster distributed-test pattern (SURVEY §4: TestDistBase subprocess
ranks ≙ xla_force_host_platform_device_count mesh).
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def _enable_compilation_cache():
    """Persistent XLA compilation cache for the suite: it compiles
    hundreds of tiny programs, many HLO-identical across test files
    (every serving test builds its own engine closures over the same tiny
    config) — deduping them cuts tier-1 wall-clock even on a cold cache,
    and a warmed cache survives into later runs in the same checkout."""
    from paddle_tpu._core.compile_cache import enable_compile_cache
    enable_compile_cache()


def _pre_cache(it):
    """Rank of a test that must run before any compilation-cache
    activity in its process, else None. test_host_tier moves KV through
    host memory like the offload suite and carries the same segfault
    guard (ISSUE 10): offload first (its module fixture assumes a
    completely cache-naive process)."""
    path = str(getattr(it, "fspath", it.nodeid))
    if "test_offload" in path:
        return 0
    if "test_host_tier" in path:
        return 1
    return None


def pytest_collection_modifyitems(config, items):
    """Run tests/test_offload.py and tests/test_host_tier.py FIRST: once
    the cache machinery has been active in a process, their
    host-memory-space programs segfault XLA:CPU (even with the cache
    re-disabled for that module). The rest of the suite keeps its
    collected order. PADDLE_TPU_TEST_NO_COMPCACHE=1 opts out (cache
    never enabled; original order kept)."""
    if os.environ.get("PADDLE_TPU_TEST_NO_COMPCACHE") or not items:
        return
    pre = sorted((it for it in items if _pre_cache(it) is not None),
                 key=_pre_cache)
    items[:] = pre + [it for it in items if _pre_cache(it) is None]
    config._compcache_pending = True


def pytest_runtest_setup(item):
    """Switch the cache on at this PROCESS's first test that is not an
    offload / host-tier one: under xdist every worker meets such a test
    after whatever share of those two files it was handed (a worker runs
    its items in collected order)."""
    if getattr(item.config, "_compcache_pending", False) \
            and _pre_cache(item) is None:
        item.config._compcache_pending = False
        _enable_compilation_cache()


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running subprocess/integration test")
