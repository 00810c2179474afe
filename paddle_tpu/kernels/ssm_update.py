"""The Mamba-2 (SSD) state recurrence: one decode step as an aliased
Pallas kernel, and the chunked scan that prefill runs.

Per head ``p`` with ``x_t in R^P``, ``B_t, C_t in R^N`` (shared by the
heads of one group) and the state ``h in R^(P x N)``:

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (x) B_t
    y_t = h_t C_t + D * x_t

(Dao & Gu, "Transformers are SSMs", arXiv:2405.21060; the layer is the
one ``granitemoehybrid`` takes from Bamba.)

**The stored layout.** A sequence's state is kept *packed*:
``(H / pack, N, pack * P)`` float32 with ``pack = 128 // P`` heads side
by side on the 128 lanes — element ``(r, n, q * P + p)`` is
``h[r * pack + q, p, n]`` (:func:`pack_state` / :func:`unpack_state`).
The same bytes as ``(H, P, N)``, arranged so that one decode step is
elementwise: ``dt * x`` and the decay are lane rows broadcast over the
sublanes, ``B`` and ``C`` are sublane columns broadcast over the lanes,
and ``h C`` is a sum over sublanes. With ``(H, P, N)`` the same step
needs ``x`` as a column, which is a transposition in the kernel or an
operand padded 128 times in memory.

**Decode** (:func:`ssm_decode_update`): the store of one layer,
``(slots, H / pack, N, pack * P)``, goes in and comes out as ONE buffer
(``input_output_aliases``); the grid visits the first ``b`` slots only,
so the rows past them are neither read nor copied. Left to XLA the
in-place update of a donated buffer was what cost the KV pool three
copies a program (PR 26). On the TPU with ``FLAGS_use_pallas`` the
kernel runs; everywhere else its ``jnp`` twin
(:func:`ssm_decode_update_xla`), as the page writers choose theirs.

**Prefill** (:func:`ssd_chunk_scan`): the same recurrence in the chunked
form, plain XLA matmuls, carrying the state from chunk to chunk under
``jax.named_scope("ssm_prefill_scan")``. :func:`ssd_sequential_scan` is
the recurrence position by position, for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
KERNEL_NAME = "ssm_decode_update"


# ------------------------------------------------------------ the layout
def heads_per_row(n_heads: int, d_head: int, n_groups: int = 1) -> int:
    """How many heads share one 128-lane row of the packed state: as
    many as fit, if that divides the heads of one group (the heads of a
    row share ``B`` and ``C``); else one."""
    pack = _LANES // d_head if d_head < _LANES and _LANES % d_head == 0 else 1
    return pack if (n_heads // n_groups) % pack == 0 else 1


def packed_shape(n_heads: int, d_head: int, d_state: int,
                 n_groups: int = 1) -> tuple:
    pack = heads_per_row(n_heads, d_head, n_groups)
    return (n_heads // pack, d_state, pack * d_head)


def pack_state(h, pack: int):
    """``(..., H, P, N)`` -> ``(..., H / pack, N, pack * P)``."""
    *lead, n_heads, p, n = h.shape
    h = h.reshape(*lead, n_heads // pack, pack, p, n)
    h = jnp.moveaxis(h, -1, -3)                  # (.., R, N, pack, P)
    return h.reshape(*lead, n_heads // pack, n, pack * p)


def unpack_state(h, pack: int):
    """The inverse of :func:`pack_state`."""
    *lead, rows, n, width = h.shape
    h = h.reshape(*lead, rows, n, pack, width // pack)
    h = jnp.moveaxis(h, -3, -1)                  # (.., R, pack, P, N)
    return h.reshape(*lead, rows * pack, width // pack, n)


# ---------------------------------------------------------------- decode
def _rows(v, pack: int):
    """``(b, H, P)`` -> ``(b, H / pack, 1, pack * P)``: a head row of
    the packed layout, with a unit sublane axis to broadcast over."""
    b, n_heads, p = v.shape
    return v.reshape(b, n_heads // pack, 1, pack * p)


def _decode_operands(x, dt, A, B, C, pack: int):
    """The step's small operands in the packed layout, float32: the
    decay and ``dt * x`` as head rows, ``B`` and ``C`` as columns."""
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dt * A.astype(jnp.float32))              # (b, H)
    decay = jnp.broadcast_to(decay[..., None], x.shape)
    return (_rows(decay, pack), _rows(dt[..., None] * x, pack),
            B.astype(jnp.float32)[..., None], C.astype(jnp.float32)[..., None])


def _finish(y_rows, x, D):
    """Packed ``h C`` rows back to ``(b, H, P)``, plus the skip term."""
    return (y_rows.reshape(x.shape)
            + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32))


def _update_kernel(h_ref, decay_ref, dx_ref, b_ref, c_ref, hout_ref, y_ref):
    """One grid step = ``rb`` head rows of one slot. ``h`` (rb, N, W);
    decay and dt*x (rb, 1, W) broadcast over the sublanes; B and C
    (N, 1) broadcast over the lanes; ``h C`` sums the sublanes."""
    h = decay_ref[0] * h_ref[0] + b_ref[0, 0][None] * dx_ref[0]
    hout_ref[0] = h
    y_ref[0] = jnp.sum(h * c_ref[0, 0][None], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_pallas(ssm, x, dt, A, B, C, D, *, interpret):
    _, rows, n, width = ssm.shape
    b, n_heads, _ = x.shape
    pack = n_heads // rows
    groups = B.shape[1]
    per_group = rows // groups                   # head rows of one group
    # head rows a grid step takes: 8 (a 512 KiB block at 128 x 128)
    # where that divides a group's, else the whole group
    rb = 8 if per_group % 8 == 0 else per_group
    if rows % rb:
        raise ValueError(f"{rows} head rows do not divide into {groups} "
                         f"groups of whole blocks of {rb}")
    decay, dx, bcol, ccol = _decode_operands(x, dt, A, B, C, pack)

    state_spec = pl.BlockSpec((1, rb, n, width), lambda i, j: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, rb, 1, width), lambda i, j: (i, j, 0, 0))
    # B and C are COLUMNS (N, 1): the state's sublane axis, broadcast
    # over the lanes. N x 4 bytes a slot a layer padded to a lane tile
    # is 64 KiB beside the 2 MiB of state
    col_spec = pl.BlockSpec(
        # kernelcheck: disable=KRN001
        (1, 1, n, 1), lambda i, j: (i, (j * rb) // per_group, 0, 0))
    ssm, y = pl.pallas_call(
        _update_kernel,
        grid=(b, rows // rb),
        in_specs=[state_spec, row_spec, row_spec, col_spec, col_spec],
        out_specs=[state_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((b, rows, 1, width), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(ssm, decay, dx, bcol, ccol)
    return ssm, _finish(y, x, D)


def ssm_decode_update_pallas(ssm, x, dt, A, B, C, D, *, interpret=None):
    """The kernel (``interpret``: on the CPU, for the tests)."""
    if interpret is None:
        from .paged_attention import _interpret
        interpret = _interpret()
    return _update_pallas(ssm, x, dt, A, B, C, D, interpret=interpret)


def ssm_decode_update_xla(ssm, x, dt, A, B, C, D):
    """The ``jnp`` twin: the same arithmetic on rows ``[:b]`` of the
    store, written back with one ``dynamic_update_slice``."""
    rows = ssm.shape[1]
    b, n_heads, _ = x.shape
    pack = n_heads // rows
    per_group = rows // B.shape[1]
    decay, dx, bcol, ccol = _decode_operands(x, dt, A, B, C, pack)
    bcol, ccol = (jnp.repeat(v, per_group, axis=1) for v in (bcol, ccol))
    h = decay * ssm[:b] + bcol * dx
    y = jnp.sum(h * ccol, axis=2, keepdims=True)
    return (lax.dynamic_update_slice(ssm, h.astype(ssm.dtype), (0, 0, 0, 0)),
            _finish(y, x, D))


def ssm_decode_update(ssm, x, dt, A, B, C, D):
    """One decode step of one layer for the first ``b`` slots.

    ``ssm`` (slots, H / pack, N, pack * P): the layer's whole store,
    updated in rows ``[:b]``; ``x`` (b, H, P); ``dt`` (b, H), after the
    softplus, ZERO for a row that must not move (decay 1, input 0);
    ``A`` (H,), negative; ``B``, ``C`` (b, G, N); ``D`` (H,). Returns
    ``(ssm, y)``, ``y`` (b, H, P) float32."""
    from ..flags import is_tpu_backend, snapshot
    if snapshot(("use_pallas",)).use_pallas and is_tpu_backend():
        return ssm_decode_update_pallas(ssm, x, dt, A, B, C, D,
                                        interpret=False)
    return ssm_decode_update_xla(ssm, x, dt, A, B, C, D)


# --------------------------------------------------------------- prefill
def _per_head(v, n_heads: int):
    """``(b, s, G, N)`` -> ``(b, s, H, N)``: each head its group's."""
    return jnp.repeat(v, n_heads // v.shape[2], axis=2)


def ssd_sequential_scan(x, dt, A, B, C, D, h0):
    """The recurrence position by position (``lax.scan``), float32.
    ``x`` (b, s, H, P); ``dt`` (b, s, H); ``B``, ``C`` (b, s, G, N);
    ``h0`` (b, H, P, N). Returns ``(y (b, s, H, P), h_s)``."""
    n_heads = x.shape[2]
    x, dt, A, D = (v.astype(jnp.float32) for v in (x, dt, A, D))
    B = _per_head(B.astype(jnp.float32), n_heads)
    C = _per_head(C.astype(jnp.float32), n_heads)

    def step(h, args):
        x_t, dt_t, b_t, c_t = args
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h, y = lax.scan(step, h0.astype(jnp.float32),
                    tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x, h


def ssd_chunk_scan(x, dt, A, B, C, D, h0, chunk: int):
    """The same recurrence in the chunked (SSD) form: inside a chunk of
    ``chunk`` positions the outputs are matmuls against a decay-weighted
    causal mask, and only the state crosses chunks. Shapes as
    :func:`ssd_sequential_scan`; ``s`` is padded up to a multiple of
    ``chunk`` with ``dt = 0``, which moves neither outputs nor state."""
    b, s, n_heads, p = x.shape
    g, n = B.shape[2:]
    x, dt, A, B, C, D = (v.astype(jnp.float32) for v in (x, dt, A, B, C, D))
    L = min(chunk, s)
    pad = -s % L
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    c = (s + pad) // L
    # heads as (group, head of the group): B and C are a group's
    xc = x.reshape(b, c, L, g, n_heads // g, p)
    dtc = dt.reshape(b, c, L, g, n_heads // g)
    Bc, Cc = B.reshape(b, c, L, g, n), C.reshape(b, c, L, g, n)
    cum = jnp.cumsum(dtc * A.reshape(g, -1), axis=2)           # (b,c,L,g,h)
    dx = dtc[..., None] * xc                                   # (b,c,L,g,h,p)
    # inside a chunk: y_t += sum_{u<=t} exp(cum_t - cum_u) (C_t.B_u) dx_u
    seg = cum[:, :, :, None] - cum[:, :, None]                 # (b,c,t,u,g,h)
    causal = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :, None, None]
    # mask BEFORE the exp: above the diagonal seg > 0 and may overflow
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("bctgn,bcugn->bctug", Cc, Bc)[..., None] * decay
    y = jnp.einsum("bctugh,bcughp->bctghp", scores, dx)
    # what each chunk adds to the state, and how much of the old survives
    to_end = jnp.exp(cum[:, :, -1:] - cum)                     # (b,c,L,g,h)
    add = jnp.einsum("bcughp,bcugn->bcghpn", to_end[..., None] * dx, Bc)
    keep = jnp.exp(cum[:, :, -1])                              # (b,c,g,h)

    with jax.named_scope("ssm_prefill_scan"):
        def carry(h, args):
            keep_c, add_c = args
            return keep_c[..., None, None] * h + add_c, h      # h ENTERING

        h_end, h_in = lax.scan(
            carry, h0.astype(jnp.float32).reshape(b, g, -1, p, n),
            (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(add, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                            # (b,c,g,h,p,n)
    y = y + jnp.einsum("bctgn,bcghpn->bctghp", Cc, h_in) \
        * jnp.exp(cum)[..., None]
    y = y.reshape(b, s + pad, n_heads, p)[:, :s]
    return y + D[:, None] * x[:, :s], h_end.reshape(b, n_heads, p, n)
