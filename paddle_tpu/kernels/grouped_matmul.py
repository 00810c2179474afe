"""Grouped (per-expert) matmul for a dropless expert layer.

The rows of ``x`` are token-to-expert assignments SORTED by expert:
the first ``group_sizes[0]`` rows belong to the first expert held, the
next ``group_sizes[1]`` to the second, and so on; the product is each
run of rows against its expert's slice of the stacked weights
``(E, K, N)``. No ``(T, E, C)`` dispatch tensor, no capacity, no dropped
token. Rows past the groups' sum (the padding to whole row tiles, and
assignments to experts not held) are NOT computed and hold whatever the
buffer held: the caller never reads them.

On the TPU this is JAX's own Pallas kernel,
``jax.experimental.pallas.ops.tpu.megablox.gmm``: a grid step is one
(row tile, expert) pair, an expert's weight tiles are fetched once for
its consecutive row tiles and an expert that got no row is never
visited; bf16 operands, float32 accumulation. Its instruction in a
device trace carries upstream's name, ``gmm`` (``KERNEL_NAME``).
Everywhere else ``jax.lax.ragged_dot`` over the same rows.
``KERNEL_DECISIONS.md`` ("Grouped expert matmul") has the measurements:
this repo's first kernel of its own gave the cell the same
``serve_tok_s`` and went.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

# the name upstream's kernel has in a device trace
KERNEL_NAME = "gmm"
# rows of one tile of the TPU kernel: ``x`` holds whole tiles
TILE_ROWS = 128
# the contraction whole (up to 2048) and 512 output columns a grid step:
# the widest of the tilings measured at hidden 2048 / expert width 768
_TILE_K, _TILE_N = 2048, 512


def padded_rows(n_assign: int) -> int:
    """``n_assign`` assignments rounded up to whole row tiles."""
    return -(-n_assign // TILE_ROWS) * TILE_ROWS


def grouped_matmul_tpu(x, w, group_sizes, interpret=False):
    """Upstream's kernel at this module's tiling (``interpret``: the
    CPU tests run it so against the other branch)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    # bf16 x bf16 is exact in float32, and Mosaic takes no float32-
    # precision request on bf16 operands: pinned against a process-wide
    # ``jax_default_matmul_precision``
    pinned = (jax.default_matmul_precision("default")
              if x.dtype == w.dtype == jnp.bfloat16
              else contextlib.nullcontext())
    with pinned:
        return gmm(x, w, group_sizes, preferred_element_type=jnp.float32,
                   tiling=(TILE_ROWS, min(x.shape[1], _TILE_K),
                           min(w.shape[2], _TILE_N)), interpret=interpret)


def grouped_matmul(x, w, group_sizes):
    """``x`` (rows, K), sorted by expert and ``rows`` whole tiles
    (:func:`padded_rows`), against the stacked ``w`` (E_held, K, N);
    ``group_sizes`` (E_held,) int32 rows an expert. Returns (rows, N)
    float32; rows past ``sum(group_sizes)`` are not computed."""
    from ..flags import is_tpu_backend
    if x.shape[0] % TILE_ROWS:
        raise ValueError(f"{x.shape[0]} rows are not whole tiles of "
                         f"{TILE_ROWS} (padded_rows' count is)")
    if is_tpu_backend():
        return grouped_matmul_tpu(x, w, group_sizes)
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32)
