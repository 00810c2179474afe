"""On-chip rms_norm Pallas-vs-XLA microbench (fwd+bwd).

Companion to tools/attn_bench.py (VERDICT round-2 item 1c). Emits one JSON
line per (rows, hidden) shape: pallas vs plain-jnp rms_norm median time over
5 runs of a jitted grad step.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels.rms_norm import rms_norm_pallas


def rms_norm_xla(x, w, eps=1e-6):
    # Must return x.dtype like the pallas kernel does — an f32 output would
    # double the store bytes and skew the comparison.
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return y.astype(x.dtype)


def bench(fn, x, w):
    # float() of a jitted scalar closes each run.
    # Sum ALL grads into the scalar — returning only gx lets XLA DCE prune
    # the dW computation and understate the backward cost.
    loss = lambda x, w: fn(x, w).astype(jnp.float32).sum()

    def step(x, w):
        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
        return gx.astype(jnp.float32).sum() + gw.astype(jnp.float32).sum()

    g = jax.jit(step)
    float(g(x, w))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(g(x, w))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[2]


def main():
    backend = jax.default_backend()
    rng = np.random.default_rng(0)
    for rows, h in ((8192, 1024), (8192, 4096), (32768, 4096), (8192, 8192)):
        x = jnp.asarray(rng.standard_normal((rows, h)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
        tp = bench(rms_norm_pallas, x, w)
        tx = bench(rms_norm_xla, x, w)
        print(json.dumps({"rows": rows, "hidden": h,
                          "pallas_ms": round(tp * 1e3, 3),
                          "xla_ms": round(tx * 1e3, 3),
                          "speedup": round(tx / tp, 2),
                          "backend": backend}), flush=True)


if __name__ == "__main__":
    main()
