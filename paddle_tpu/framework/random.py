"""Stateful RNG over jax's functional PRNG.

Eager mode keeps a global generator (paddle parity: paddle.seed,
python/paddle/framework/random.py). Under jit capture, random ops must be fed an
explicit key — the jit layer threads a per-step key through ``rng_context`` so
captured programs stay pure (fresh randomness each call instead of a baked-in
constant).
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np


class Generator:
    """Splittable PRNG stream (device generator parity:
    python/paddle/framework/random.py get_rng_state)."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        # the key is built on first use: jax.random.key initialises the
        # backend, and importing the package (which builds the default
        # generator) must leave the chip to whichever process needs it
        self._seed = seed
        self._key = None
        return self

    def _current(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        return self._key

    def next_key(self):
        with self._lock:
            self._key, sub = jax.random.split(self._current())
            return sub

    def get_state(self):
        with self._lock:
            return jax.random.key_data(self._current())

    def set_state(self, state):
        self._key = jax.random.wrap_key_data(np.asarray(state))


_default_generator = Generator(np.random.SeedSequence().entropy % (2**31))
_tls = threading.local()


def seed(s: int):
    """paddle.seed parity."""
    _default_generator.manual_seed(int(s))
    return _default_generator


def default_generator() -> Generator:
    return _default_generator


@contextlib.contextmanager
def rng_context(key):
    """Bind an explicit PRNG key for the dynamic extent (used by jit capture
    and by model-parallel RNG control, reference:
    fleet/layers/mpu/random.py model-parallel dropout seeds)."""
    prev = getattr(_tls, "generator", None)
    gen = _KeyGenerator(key)
    _tls.generator = gen
    try:
        yield gen
    finally:
        _tls.generator = prev


class _KeyGenerator:
    """Generator bound to an explicit (possibly traced) key."""

    def __init__(self, key):
        self._key = key
        self._count = 0

    def next_key(self):
        self._count += 1
        return jax.random.fold_in(self._key, self._count)


def next_key():
    gen = getattr(_tls, "generator", None)
    if gen is None:
        gen = _default_generator
    return gen.next_key()


def get_rng_state():
    return [_default_generator.get_state()]


def set_rng_state(states):
    _default_generator.set_state(states[0])
