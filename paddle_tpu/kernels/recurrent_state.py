"""Per-slot recurrent state beside the paged KV pool.

A state-space layer keeps, for each sequence, a state of FIXED size that
every step changes in place. Unlike KV pages it is not addressed
through a block table and cannot be shared or grown: it lives in row
``slot`` of two arrays a layer, ``ssm`` (the recurrence's state,
float32: it accumulates over thousands of steps) and ``conv`` (the last
``d_conv - 1`` inputs of the causal convolution, in the model's dtype).

What a model says (``cache_spec()``): a :class:`RecurrentSpec` for such a
layer, ``(kv_heads, head_dim)`` for an attention layer. What rides a
compiled program: a :class:`RecurrentState` per recurrent layer, beside
the ``PagedDecodeState`` of the attention layers. What the engine owns:
one :class:`RecurrentStateCache`, next to its ``PagedKVCache``.
"""

from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


class RecurrentSpec(NamedTuple):
    """One recurrent layer's per-sequence state, by shape."""
    ssm_shape: Tuple[int, ...]      # the packed SSM state, float32
    conv_shape: Tuple[int, ...]     # (d_conv - 1, channels), model dtype


class RecurrentState(NamedTuple):
    """One recurrent layer's state as it rides a jitted call (a pytree,
    like ``PagedDecodeState``). ``ssm``/``conv`` hold ALL rows; which of
    them the call works on:

    ``slot`` given (a scalar): the ONE row of a b=1 prefill or chunk,
    which starts from what the row holds and leaves its end state there.
    ``n_valid`` (a scalar) is then the number of REAL positions of a
    padded chunk: the pad must not move the state.
    ``slot`` None: rows ``[:b]``, row for row with the batch (a decode
    step; or a dense batch outside the engine). ``live`` (b,) marks the
    rows that advance; the others keep their state bit for bit."""
    ssm: Any
    conv: Any
    slot: Any = None
    n_valid: Any = None
    live: Any = None


def is_recurrent_state(entry) -> bool:
    return isinstance(entry, RecurrentState)


def recurrent_layout(spec) -> Optional[List[bool]]:
    """Which layers of a model's ``cache_spec()`` are recurrent, or None
    where none is (a plain list of ``(kv_heads, head_dim)``: all
    pages)."""
    layout = [isinstance(e, RecurrentSpec) for e in spec]
    return layout if any(layout) else None


# Every program the store dispatches donates the arrays, so a row is
# written in place. Module-level and jitted on shapes: engines over the
# same model share one compilation.

@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_rows(arrays, slot):
    return [lax.dynamic_update_slice_in_dim(
        a, jnp.zeros((1,) + a.shape[1:], a.dtype), slot, 0) for a in arrays]


@functools.partial(jax.jit, donate_argnums=(0,))
def _move_rows(arrays, src, dst):
    return [lax.dynamic_update_slice_in_dim(
        a, lax.dynamic_slice_in_dim(a, src, 1, 0), dst, 0) for a in arrays]


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(arrays, slot, rows):
    return [lax.dynamic_update_slice_in_dim(a, r[None].astype(a.dtype),
                                            slot, 0)
            for a, r in zip(arrays, rows)]


@jax.jit
def _read_rows(arrays, slot):
    return [lax.dynamic_index_in_dim(a, slot, 0, keepdims=False)
            for a in arrays]


class RecurrentStateCache:
    """The store: per recurrent layer ``(max_batch, *ssm_shape)`` float32
    and ``(max_batch, *conv_shape)``, indexed by SLOT.

    ``reset(slot)`` zeroes a row (admission); ``move(src, dst)`` copies
    one row over another on the device (the ladder compacting slots);
    ``export(slot)`` / ``import_(slot, bundle)`` take and seat a host
    snapshot (a request handed to another engine);
    ``take_arrays()`` / ``install_arrays()`` hand the arrays to a
    donating program and take them back, as ``PagedKVCache.take_pools``
    does for the pools."""

    def __init__(self, specs: List[RecurrentSpec], max_batch: int,
                 dtype=jnp.bfloat16):
        self.specs = list(specs)
        self.max_batch = int(max_batch)
        self.conv_dtype = jnp.dtype(dtype)
        self._arrays: Optional[list] = []
        for s in self.specs:
            self._arrays.append(
                jnp.zeros((max_batch,) + tuple(s.ssm_shape), jnp.float32))
            self._arrays.append(
                jnp.zeros((max_batch,) + tuple(s.conv_shape),
                          self.conv_dtype))
        self.bytes_per_slot = sum(
            int(np.prod(s.ssm_shape)) * 4
            + int(np.prod(s.conv_shape)) * self.conv_dtype.itemsize
            for s in self.specs)
        # compile the two row programs now, on rows that hold nothing
        # yet: none is left to compile inside a serving window
        self.reset(0)
        self.move(0, 0)

    @property
    def nbytes(self) -> int:
        return self.bytes_per_slot * self.max_batch

    @property
    def detached(self) -> bool:
        return self._arrays is None

    # ----------------------------------------------------- row programs
    def reset(self, slot: int) -> None:
        self._arrays = _reset_rows(self.take_arrays(flat=True),
                                   jnp.int32(slot))

    def move(self, src: int, dst: int) -> None:
        self._arrays = _move_rows(self.take_arrays(flat=True),
                                  jnp.int32(src), jnp.int32(dst))

    def export(self, slot: int) -> List[np.ndarray]:
        """Row ``slot`` of every array, on the host (ssm, conv per
        layer, in layer order)."""
        if self._arrays is None:
            raise RuntimeError("export: the state arrays are detached")
        return [np.asarray(r)
                for r in _read_rows(self._arrays, jnp.int32(slot))]

    def import_(self, slot: int, bundle: List[np.ndarray]) -> None:
        shapes = [tuple(a.shape[1:]) for a in self._arrays or ()]
        if [tuple(r.shape) for r in bundle] != shapes:
            raise ValueError(
                "import_: the bundle's rows do not have this store's "
                f"shapes ({[tuple(r.shape) for r in bundle][:2]}... vs "
                f"{shapes[:2]}...)")
        self._arrays = _write_rows(self.take_arrays(flat=True),
                                   jnp.int32(slot),
                                   [jnp.asarray(r) for r in bundle])

    # ------------------------------------------------- donation handoff
    def take_arrays(self, flat: bool = False):
        """Detach and return the arrays for a donating dispatch: per
        layer ``(ssm, conv)`` (``flat``: one list). Until
        :meth:`install_arrays` the store is empty, and says so."""
        if self._arrays is None:
            raise RuntimeError(
                "take_arrays: state arrays already detached (a donating "
                "dispatch is in flight or failed without install_arrays)")
        arrays, self._arrays = self._arrays, None
        if flat:
            return arrays
        return [(arrays[i], arrays[i + 1]) for i in range(0, len(arrays), 2)]

    def install_arrays(self, pairs) -> None:
        self._arrays = [a for pair in pairs for a in pair]
