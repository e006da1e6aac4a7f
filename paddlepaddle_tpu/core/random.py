"""RNG state management.

Reference: paddle.seed / Generator (paddle/phi/core/generator.h), plus the
three-level seed discipline used under tensor parallel
(python/paddle/distributed/fleet/layers/mpu/random.py get_rng_state_tracker).

TPU-native design: state is a jax PRNG key. Eager ops consume fresh subkeys by
splitting a process-global generator. Functional/jit paths should thread keys
explicitly (``Generator.key()`` inside jit returns a traced key when seeded
with a traced value via ``seed_for_jit``).
"""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np


class Generator:
    """The key is made on first use, not at construction: the default
    generator is a module global, and a key is a device array — importing
    the package must not take the chip (a launcher or supervisor that only
    spawns workers has to leave it free for them)."""

    def __init__(self, seed: int = 0):
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._lazy_key = None
        self._offset = 0
        return self

    @property
    def _key(self):
        if self._lazy_key is None:
            self._lazy_key = jax.random.PRNGKey(self._seed)
        return self._lazy_key

    @_key.setter
    def _key(self, value):
        self._lazy_key = value

    def initial_seed(self):
        return self._seed

    def split(self):
        """Return a fresh subkey (advances state)."""
        self._key, sub = jax.random.split(self._key)
        self._offset += 1
        return sub

    def get_state(self):
        return {"seed": self._seed, "key": np.asarray(self._key), "offset": self._offset}

    def set_state(self, state):
        self._seed = int(state["seed"])
        self._key = jax.numpy.asarray(state["key"])
        self._offset = int(state.get("offset", 0))


_default_generator = Generator(0)
_named_generators: Dict[str, Generator] = {}
_scope_stack = []  # innermost-wins stack of ["key", key] / ("gen", Generator)


from contextlib import contextmanager


@contextmanager
def key_scope(key):
    """Route next_key() to splits of ``key`` (possibly a tracer) inside jit.

    The functional path's answer to stateful RNG under tracing: a jitted train
    step takes an explicit key argument and wraps its forward in key_scope so
    dropout masks differ per step while staying compile-safe."""
    _scope_stack.append(["key", key])
    try:
        yield
    finally:
        _scope_stack.pop()


@contextmanager
def generator_scope(gen: Generator):
    """Route next_key() to ``gen`` (the mpu RNGStatesTracker mechanism: a
    named generator temporarily replaces the default stream). Innermost scope
    wins, so an rng_state() region inside a traced train step (key_scope)
    draws from the tracker as the fleet API documents — note the tracker key
    is a compile-time constant under jit."""
    _scope_stack.append(("gen", gen))
    try:
        yield
    finally:
        _scope_stack.pop()


def default_generator() -> Generator:
    return _default_generator


def seed(s: int):
    """paddle.seed equivalent: reseeds the global generator (and named ones)."""
    _default_generator.manual_seed(s)
    for i, g in enumerate(_named_generators.values()):
        g.manual_seed(s + i + 1)
    return _default_generator


def get_generator(name: str = None) -> Generator:
    if name is None:
        return _default_generator
    if name not in _named_generators:
        _named_generators[name] = Generator(_default_generator.initial_seed() + len(_named_generators) + 1)
    return _named_generators[name]


def next_key(name: str = None):
    if _scope_stack and name is None:
        top = _scope_stack[-1]
        if top[0] == "key":
            k, sub = jax.random.split(top[1])
            top[1] = k
            return sub
        return top[1].split()
    return get_generator(name).split()


def get_rng_state():
    return [_default_generator.get_state()] + [g.get_state() for g in _named_generators.values()]


def set_rng_state(states):
    gens = [_default_generator] + list(_named_generators.values())
    for g, s in zip(gens, states):
        g.set_state(s)
