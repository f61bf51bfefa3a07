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


def pytest_collection_modifyitems(config, items):
    """Run tests/test_offload.py FIRST, then arm the compilation cache
    to switch on for everything after it: once the cache machinery has
    been active in a process, the offload suite's host-memory-space
    programs segfault XLA:CPU (even with the cache re-disabled for
    that module) — so offload runs before any cache activity and the
    REST of the suite (including the heavy op sweeps and distributed
    files) gets the dedup win. PADDLE_TPU_TEST_NO_COMPCACHE=1 opts
    out (cache never enabled; original order kept)."""
    if os.environ.get("PADDLE_TPU_TEST_NO_COMPCACHE") or not items:
        return

    def _pre_cache(it):
        # test_host_tier moves KV through host memory like the offload
        # suite and carries the same segfault guard (ISSUE 10): both
        # run before any compilation-cache activity, offload first
        # (its module fixture assumes a completely cache-naive process)
        path = str(getattr(it, "fspath", it.nodeid))
        if "test_offload" in path:
            return 0
        if "test_host_tier" in path:
            return 1
        return None

    pre = sorted((it for it in items if _pre_cache(it) is not None),
                 key=_pre_cache)
    rest = [it for it in items if _pre_cache(it) is None]
    if not rest:
        return
    # newest gate files LAST (ISSUE 12, extended by ISSUE 13): the
    # suite has brushed its tier-1 watchdog since PR 8, so a slow-box
    # run that gets truncated should lose the NEWEST gates first and
    # keep the long-established prefix comparable run-to-run — the
    # overlap/traffic gates still run (and pass) whenever the box
    # keeps pace. Order within the tail: older first, newest dead last.
    def _tail_rank(it):
        path = str(getattr(it, "fspath", it.nodeid))
        if "test_overlap" in path:
            return 0
        if "test_traffic" in path:
            return 1
        if "test_adapters" in path:
            return 2
        if "test_wal" in path:
            return 3
        if "test_tracing" in path:
            return 4
        if "test_tp2d" in path:
            return 5
        if "test_multiproc" in path:    # ISSUE 19 (the only spawner
            return 6                    # of worker process trees)
        if "test_tree_spec" in path:    # ISSUE 20: newest, dead last
            return 7
        return None
    tail = sorted((it for it in rest if _tail_rank(it) is not None),
                  key=_tail_rank)
    if tail and tail != rest:
        rest = [it for it in rest if _tail_rank(it) is None] + tail
    items[:] = pre + rest
    config._compcache_boundary = rest[0].nodeid


def pytest_runtest_setup(item):
    boundary = getattr(item.config, "_compcache_boundary", None)
    if boundary is not None and item.nodeid == boundary:
        item.config._compcache_boundary = None
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
