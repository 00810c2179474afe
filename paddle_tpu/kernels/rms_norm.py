"""Fused RMSNorm in Pallas.

Reference: paddle/phi/kernels/gpu/rms_norm_kernel.cu (fused residual-add +
rms_norm used by the Llama path). XLA already fuses the jnp composition well;
this kernel exists for the long-row case (hidden >= 8k) where keeping the
row resident in VMEM for the two passes (moment + normalize) beats XLA's
fusion, and as the pattern template for the kernel tier.

fwd:  r = rsqrt(mean(x^2) + eps);  y = x * r * w        (saves r)
bwd:  dx = r * g*w - x * r^3/H * sum(g*w*x)   (Pallas, row blocks)
      dw = sum_rows(g * x * r)                (jnp — XLA reduces fine)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK_ROWS = 256
# The bwd kernel keeps ~5 f32 row-block temporaries live (x, g, gw, the
# dot term, dx); scoped VMEM is 16 MB, so scale rows down as hidden grows.
# 256 rows x 1024 hidden measured safe on v5e (r05 rmsnorm bench); 256 x
# 4096 overflowed by 2.1 MB (18.1 MB requested) — hold the product at the
# known-good 256k elements per block.
_MAX_BLOCK_ELEMS = 256 * 1024


def _block_rows(n: int, h: int) -> int:
    """0 means "too wide for the kernel" (even the 8-row sublane minimum
    busts the VMEM budget) — the caller falls back to the XLA composition."""
    if 8 * h > _MAX_BLOCK_ELEMS:
        return 0
    cap = max(8, (_MAX_BLOCK_ELEMS // max(h, 1)) // 8 * 8)
    block = min(_BLOCK_ROWS, cap)
    return block if n >= block else max(8, n)


def _interpret() -> bool:
    from ..flags import is_tpu_backend
    return not is_tpu_backend()


def _fwd_kernel(x_ref, w_ref, y_ref, r_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y_ref[:] = (x * r * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    r_ref[:] = r


def _bwd_kernel(x_ref, w_ref, g_ref, r_ref, dx_ref, *, h: int):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    r = r_ref[:]
    gw = g * w
    dot = jnp.sum(gw * x, axis=-1, keepdims=True)
    dx = r * gw - x * (r ** 3) * (dot / h)
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _row_call(kernel, name, n, h, block, n_out, out_shapes, args):
    grid = (pl.cdiv(n, block),)
    in_specs = []
    for a in args:
        if a.shape == (1, h):                 # weight: replicated per block
            in_specs.append(pl.BlockSpec((1, h), lambda i: (0, 0)))
        elif a.shape[-1] == 1:                # saved r: (N, 1)
            # the saved-r stat column is one f32 per row by definition
            # kernelcheck: disable=KRN001
            in_specs.append(pl.BlockSpec((block, 1), lambda i: (i, 0)))
        else:
            in_specs.append(pl.BlockSpec((block, h), lambda i: (i, 0)))
    out_specs = []
    for s in out_shapes:
        if s.shape[-1] == 1:
            # saved-r stat column (see above)
            # kernelcheck: disable=KRN001
            out_specs.append(pl.BlockSpec((block, 1), lambda i: (i, 0)))
        else:
            out_specs.append(pl.BlockSpec((block, h), lambda i: (i, 0)))
    if n_out == 1:
        out_specs, out_shapes = out_specs[0], out_shapes[0]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shapes, interpret=_interpret(), name=name)(*args)


def _fwd(x2, w, eps, block):
    n, h = x2.shape
    return _row_call(
        functools.partial(_fwd_kernel, eps=eps), "rms_norm", n, h, block, 2,
        [jax.ShapeDtypeStruct((n, h), x2.dtype),
         jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        [x2, w])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm(x2, w, eps, block):
    y, _ = _fwd(x2, w, eps, block)
    return y


def _rms_fwd_rule(x2, w, eps, block):
    y, r = _fwd(x2, w, eps, block)
    return y, (x2, w, r)


def _rms_bwd_rule(eps, block, res, g):
    x2, w, r = res
    n, h = x2.shape
    dx = _row_call(
        functools.partial(_bwd_kernel, h=h), "rms_norm_bwd", n, h, block, 1,
        [jax.ShapeDtypeStruct((n, h), x2.dtype)],
        [x2, w, g, r])
    dw = jnp.einsum("nh,nh->h", g.astype(jnp.float32),
                    (x2.astype(jnp.float32) * r)).astype(w.dtype)
    return dx, dw.reshape(w.shape)


_rms_norm.defvjp(_rms_fwd_rule, _rms_bwd_rule)


def rms_norm_ref(x, weight, epsilon: float = 1e-6):
    """Pure-jnp twin of :func:`rms_norm_pallas` — the parity oracle
    (and the XLA fallback composition for rows too wide for VMEM)."""
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                      + float(epsilon))
    return (xf * r * weight.astype(jnp.float32)).astype(x.dtype)


def rms_norm_pallas(x, weight, epsilon: float = 1e-6):
    """Normalize over the last axis; any leading shape."""
    orig = x.shape
    h = orig[-1]
    n = 1
    for s in orig[:-1]:
        n *= s
    block = _block_rows(n, h)
    if block == 0:   # row too wide for scoped VMEM: XLA composes fine
        return rms_norm_ref(x, weight, epsilon)
    x2 = x.reshape(n, h)
    pad = (-n) % block
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, h), x2.dtype)], axis=0)
    y = _rms_norm(x2, weight.reshape(1, h), float(epsilon), block)
    if pad:
        y = y[:n]
    return y.reshape(orig)
