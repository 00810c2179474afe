"""On-chip flash-attention microbench.

``python tools/attn_bench.py`` — flash vs XLA dense, fwd+bwd, a block
sweep at 4,096 and the GQA rows (what ATTN_BENCH_r05.json was made by).

``python tools/attn_bench.py --kernels bh=256,s=1024,d=64 ...`` — the
three training kernels ALONE at the given (BH, S, D) shapes (``h``/``hkv``
for GQA, ``scale`` for a softmax scale other than 1/sqrt(d), ``bq``/``bk``
for blocks other than ``flash_tiling``'s, ``w`` for a causal window: the
band's grids, ``flash_fwd_window`` and so on): device time of ``flash_fwd`` /
``flash_bwd_dq`` / ``flash_bwd_dkv`` a call, read from a profiler trace of
ten fwd+bwd calls, beside the host's fwd+bwd time; a spec whose blocks
the compiler refuses prints its error and the run goes on. The
package it times is the first ``paddle_tpu`` on ``sys.path`` (the
checkout's own unless ``PYTHONPATH`` names another, e.g. an unpacked
parent commit), so two commits are two runs of this one file.
"""
import functools
import glob
import json
import os
import sys
import tempfile
import time

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                flash_attention_bshd)

_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def dense_bshd(q, k, v):
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    s = jnp.einsum("bhsd,bhtd->bhst", qt, kt) / np.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones(s.shape[-2:], bool))
    s = jnp.where(causal, s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhst,bhtd->bhsd", p, vt), 1, 2)


def grad_sum(fn):
    # Sum ALL of dq/dk/dv: summing only dq lets XLA DCE prune the dk/dv
    # backward kernels and understate the backward cost.
    loss = lambda *a: fn(*a).astype(jnp.float32).sum()
    return jax.jit(lambda *a: sum(t.astype(jnp.float32).sum()
                                  for t in jax.grad(loss, argnums=(0, 1, 2))(*a)))


def bench(fn, *args):
    return timed(grad_sum(fn), *args)


def timed(g, *args):
    # Time a jitted scalar and float() it (the host transfer closes the
    # run).
    float(g(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(g(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[2]


def dense_gqa_bshd(q, k, v):
    rep = q.shape[2] // k.shape[2]
    return dense_bshd(q, jnp.repeat(k, rep, axis=2),
                      jnp.repeat(v, rep, axis=2))


def kernel_ms(g, args, calls=10):
    """Device milliseconds a call of each flash kernel, from a trace of
    ``calls`` runs of the compiled ``g``. A Pallas call's op is named
    after its ``name=`` inside JAX's transform wrappers
    (``transpose_jvp_flash_bwd_dq__``): the longest kernel name found in
    the instruction's own name counts it."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(calls):
            float(g(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        data = ProfileData.from_file(path)
    total = dict.fromkeys(_KERNELS, 0.0)
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                inst = e.name.split(" = ", 1)[0]
                hit = [k for k in _KERNELS if k in inst]
                if hit:
                    total[max(hit, key=len)] += e.duration_ns
    return {k: round(v / calls / 1e6, 4) for k, v in total.items()}


def kernels(specs):
    rng = np.random.default_rng(0)
    for spec in specs:
        kv = dict(item.split("=") for item in spec.split(","))
        bh, s, d = int(kv["bh"]), int(kv["s"]), int(kv["d"])
        h = int(kv.get("h", 1))
        hkv = int(kv.get("hkv", h))
        scale = float(kv["scale"]) if "scale" in kv else None
        window = int(kv["w"]) if "w" in kv else None
        dtype = jnp.dtype(kv.get("dtype", "bfloat16"))
        # the spec's blocks, else the package's own: ``flash_tiling``'s
        # (an unpacked parent's package may predate it: shown as None)
        bq, bk = (int(kv[b]) if b in kv else None for b in ("bq", "bk"))
        tiling = getattr(fa, "flash_tiling", None)
        derived = (tiling(s, s, d, dtype.itemsize) if tiling
                   else (None, None))
        q = jnp.asarray(rng.standard_normal((bh, s, d)), dtype)
        k, v = (jnp.asarray(rng.standard_normal((bh // h * hkv, s, d)), dtype)
                for _ in range(2))
        fn = functools.partial(flash_attention, causal=True, sm_scale=scale,
                               block_q=bq, block_k=bk, n_heads=h,
                               n_kv_heads=hkv,
                               **({} if window is None else
                                  {"window": window}))
        g = grad_sum(fn)
        rec = {"spec": spec, "block_q": bq or derived[0],
               "block_k": bk or derived[1],
               "package": os.path.dirname(os.path.dirname(
                   sys.modules[flash_attention.__module__].__file__)),
               "backend": jax.default_backend()}
        try:
            rec["fwd_bwd_host_ms"] = round(timed(g, q, k, v) * 1e3, 3)
        except Exception as e:              # blocks past what VMEM holds
            rec["error"] = repr(e)[:300]
            print(json.dumps(rec), flush=True)
            continue
        rec.update(kernel_ms(g, (q, k, v)))
        print(json.dumps(rec), flush=True)


if __name__ == "__main__" and sys.argv[1:2] == ["--kernels"]:
    kernels(sys.argv[2:])
    sys.exit(0)

rng = np.random.default_rng(0)
tf_4096 = None
for s in (1024, 2048, 4096, 8192):
    b = max(1, 8192 // s)
    h, d = 16, 64
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
               for _ in range(3))
    tf = bench(functools.partial(flash_attention_bshd, causal=True), q, k, v)
    if s == 4096:
        tf_4096 = tf
    rec = {"seq": s, "batch": b, "flash_ms": round(tf*1e3, 2),
           "backend": jax.default_backend()}
    if s <= 4096:
        # dense fwd+bwd at 8k needs ~9 GB of (B,H,S,S) f32 transients —
        # an OOM risk on a 16 GB chip; at 8k flash stands alone
        td = bench(dense_bshd, q, k, v)
        rec.update(dense_ms=round(td*1e3, 2), speedup=round(td/tf, 2))
    print(json.dumps(rec), flush=True)

# Block-size sweep at the north-star shape (seq 4096), against the
# blocks ``flash_tiling`` derives there (what the main loop's s=4096
# record ran): a winner that is not those is a finding for the tiling
# rule (KERNEL_DECISIONS.md "Flash attention tiling")
s, b, h, d = 4096, 2, 16, 64
q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
           for _ in range(3))
seed_bq, seed_bk = fa.flash_tiling(s, s, d, q.dtype.itemsize)
best = (tf_4096, seed_bq, seed_bk) if tf_4096 is not None else None
for bq, bk in ((128, 128), (256, 256), (512, 512), (512, 1024),
               (1024, 512), (1024, 1024), (1024, 2048), (2048, 1024),
               (2048, 2048)):
    if best is not None and (bq, bk) == (seed_bq, seed_bk):
        continue
    try:
        t = bench(functools.partial(flash_attention_bshd, causal=True,
                                    block_q=bq, block_k=bk), q, k, v)
    except Exception as e:                 # a combo may not fit VMEM
        print(json.dumps({"sweep_block_q": bq, "sweep_block_k": bk,
                          "error": repr(e)[:160],
                          "backend": jax.default_backend()}), flush=True)
        continue
    print(json.dumps({"sweep_block_q": bq, "sweep_block_k": bk,
                      "seq": s, "flash_ms": round(t*1e3, 2),
                      "backend": jax.default_backend()}), flush=True)
    if best is None or t < best[0]:
        best = (t, bq, bk)
if best is not None:
    print(json.dumps({"best_block_q": best[1], "best_block_k": best[2],
                      "flash_ms": round(best[0]*1e3, 2), "seq": s,
                      "backend": jax.default_backend()}), flush=True)

# GQA (the 70B north-star layout: rep=8): unexpanded-kv kernel vs
# repeat_interleave + dense
for s in (2048, 4096):
    b, h, hkv, d = max(1, 8192 // s), 16, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)
            for _ in range(2))
    tf = bench(functools.partial(flash_attention_bshd, causal=True), q, k, v)
    td = bench(dense_gqa_bshd, q, k, v)
    print(json.dumps({"seq": s, "batch": b, "gqa_rep": h // hkv,
                      "flash_gqa_ms": round(tf*1e3, 2),
                      "dense_expand_ms": round(td*1e3, 2),
                      "speedup": round(td/tf, 2),
                      "backend": jax.default_backend()}), flush=True)
