"""RNG state management on a stateless-PRNG substrate.

The reference keeps mutable per-device generator state
(reference: paddle/phi/core/generator.h, python/paddle/framework/random.py
``paddle.seed``). JAX PRNG is stateless, so the imperative surface keeps a
global ``Generator`` whose key is split on every draw (eager parity), while
jit-compiled code paths use an explicit *rng scope*: the training-step wrapper
threads a fresh traced key per step and ops derive per-call-site streams via
``fold_in`` with a static counter. This mirrors the determinism contract of
the reference's ``RNGStatesTracker``
(python/paddle/distributed/fleet/layers/mpu/random.py:34) without stateful
device RNG.
"""
from __future__ import annotations

import threading
from typing import Optional

import numpy as np
import jax

_state = threading.local()


class Generator:
    """Stateful key-splitting generator (reference: phi::Generator)."""

    def __init__(self, seed: int = 0):
        self._seed = seed
        self._key = None  # lazily created — constructing a key initializes
        self._offset = 0  # the JAX backend, which must not happen at import

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        return self._key

    def manual_seed(self, seed: int):
        self._seed = seed
        self._key = None
        self._offset = 0
        return self

    def initial_seed(self) -> int:
        return self._seed

    def get_state(self):
        return (self._seed, self._offset,
                np.asarray(jax.random.key_data(self.key)))

    def set_state(self, state):
        self._seed, self._offset, key_data = state
        self._key = jax.random.wrap_key_data(
            jax.numpy.asarray(key_data))

    def next_key(self):
        self._key, sub = jax.random.split(self.key)
        self._offset += 1
        return sub


# Created on first use, never at import: ``import paddle_tpu`` must not
# initialize the JAX backend (a backend that fails to start would poison
# every entry point otherwise, and a process that only imports must not
# claim the chip).
_default_generator: Optional[Generator] = None


def default_generator() -> Generator:
    global _default_generator
    if _default_generator is None:
        _default_generator = Generator(np.random.randint(0, 2**31 - 1))
    return _default_generator


def seed(s: int):
    """reference: python/paddle/framework/random.py ``paddle.seed``."""
    return default_generator().manual_seed(int(s))


def get_rng_state():
    return [default_generator().get_state()]


def set_rng_state(state):
    default_generator().set_state(state[0])


class rng_scope:
    """Bind an explicit (possibly traced) PRNG key for random ops in scope.

    Inside the scope every random op draws ``fold_in(key, counter)`` where
    ``counter`` is a static per-call sequence number — deterministic given the
    key, jit-safe, and unique per call site in a traced program.
    """

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        self._old = getattr(_state, "scope", None)
        _state.scope = [self.key, 0]
        return self

    def __exit__(self, *exc):
        _state.scope = self._old
        return False


def next_rng_key():
    """Get the next PRNG key: from the active scope if any, else the global
    generator."""
    scope = getattr(_state, "scope", None)
    if scope is not None:
        key, ctr = scope
        scope[1] = ctr + 1
        return jax.random.fold_in(key, ctr)
    return default_generator().next_key()


def in_rng_scope() -> bool:
    return getattr(_state, "scope", None) is not None


class use_generator:
    """Temporarily route random draws to ``gen`` (the hook RNGStatesTracker
    uses to give each model-parallel stream its own generator — reference:
    python/paddle/distributed/fleet/layers/mpu/random.py:34)."""

    def __init__(self, gen: Generator):
        self._gen = gen

    def __enter__(self):
        global _default_generator
        self._old = default_generator()
        _default_generator = self._gen
        return self._gen

    def __exit__(self, *exc):
        global _default_generator
        _default_generator = self._old
        return False
