"""Global RNG state.

Reference: paddle/phi/core/generator.cc + python/paddle/framework/random.py.
JAX randomness is functional (explicit keys); this module owns a global key
that eager random ops split from, giving paddle's stateful-RNG feel, while
jitted code paths take explicit keys (see distributed/fleet/random.py for the
TP-aware RNGStatesTracker).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp


class _RNGState(threading.local):
    """Key creation is LAZY: materializing a PRNGKey initializes the jax
    backend, and ``import paddle_tpu`` must never touch backend state (a
    process that has touched JAX holds the chip: launchers and parents
    that only import the library must stay off it)."""

    def __init__(self):
        self.key = None
        self.seed_value = 0

    def get_key(self):
        if self.key is None:
            self.key = jax.random.PRNGKey(self.seed_value)
        return self.key


_state = _RNGState()


def seed(s: int):
    """``paddle.seed``: reset the global generator."""
    _state.key = jax.random.PRNGKey(int(s))
    _state.seed_value = int(s)
    return _state


def get_rng_state():
    return [_state.get_key()]


def set_rng_state(state):
    _state.key = state[0] if isinstance(state, (list, tuple)) else state


def get_cuda_rng_state():  # source compat
    return get_rng_state()


def set_cuda_rng_state(state):
    set_rng_state(state)


def next_key() -> jax.Array:
    """Split the global key and return a fresh subkey (eager random ops)."""
    _state.key, sub = jax.random.split(_state.get_key())
    return sub


def default_seed() -> int:
    return _state.seed_value
