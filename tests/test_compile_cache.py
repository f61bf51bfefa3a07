"""The one compile-cache helper: a directory placed from outside wins, and
nothing in code overrides it."""
import os
import re

import jax

from paddle_tpu._core import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _updates(monkeypatch):
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    return seen


def test_env_directory_is_not_overridden(monkeypatch):
    seen = _updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in seen
    # every compile persists either way
    assert seen["jax_persistent_cache_min_compile_time_secs"] == 0
    assert seen["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_default_is_fixed_under_the_checkout(monkeypatch):
    seen = _updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, "artifacts", "xla_cache")
    assert compile_cache.enable_compile_cache() == want
    assert seen["jax_compilation_cache_dir"] == want


def test_no_other_cache_directory_is_set_in_code():
    """The tools, the worker entry and the test harness all go through
    the helper (``chipbench/`` keeps its own copy of the rule by design,
    ROADMAP D12, and is not walked): nothing else gives
    ``jax_compilation_cache_dir`` a directory (the offload suites only
    switch the cache off and restore it)."""
    sets_dir = re.compile(
        r'update\(\s*"jax_compilation_cache_dir",(?!\s*(None|prev))')
    helper = os.path.join(REPO, "paddle_tpu", "_core", "compile_cache.py")
    sources = [os.path.join(REPO, f) for f in os.listdir(REPO)]
    for root in ("paddle_tpu", "tools", "tests", "examples"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            sources += [os.path.join(dirpath, f) for f in files]
    offenders = []
    for path in sources:
        if path.endswith(".py") and path != helper:
            with open(path) as fh:
                if sets_dir.search(fh.read()):
                    offenders.append(os.path.relpath(path, REPO))
    assert offenders == []
