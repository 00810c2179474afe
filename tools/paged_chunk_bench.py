"""On-chip microbench of ``paged_chunk_attention`` (the chunked prefill's
attention read), one layer's call at a serving cell's shapes.

``python tools/paged_chunk_bench.py [shape ...] [--tilings a,b,...]``
prints one JSON line a (shape, cursor, tiling): microseconds a call, the
mean of ``--calls`` calls chained on the device inside one program (each
call's output is the next one's query), so no dispatch is timed; with
``--check`` also the largest gap to the XLA twin's output.

Shapes are named after the cells (``trinity-global``, ``trinity-window``,
``gpt3``, ``sdar``, ``granite``); a tiling is ``keys:temp_mb``, the two
constants ``chunk_tiling`` cuts a call by (``_GROUP_KEYS`` keys a block,
``_TEMP_VMEM_BYTES`` of score temporaries a tile), ``default`` for the
file's own. The package timed is the first ``paddle_tpu`` on ``sys.path``
(the checkout's own unless ``PYTHONPATH`` names another, e.g. an unpacked
parent commit, whose kernel has no tiling to set: ``default`` only).

It refuses any platform but ``tpu`` (elsewhere the kernel runs in Pallas
interpret mode, whose time says nothing). The file's own tiling has to
run: a failure there is raised. A tiling of a ``--tilings`` sweep that
Mosaic refuses is what the sweep is there to find: it prints an ``error``
line, the sweep goes on, and the exit code is 1.
"""
import argparse
import json
import os
import sys
import time

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import paged_attention as pa

# s, h, hkv, d (the pool's), table width, keywords, cursors
SHAPES = {
    "trinity-global": (1024, 32, 4, 128, 384, {}, (1024, 11264, 21504)),
    "trinity-window": (1024, 32, 4, 128, 384, {"window": 2048},
                       (1024, 11264, 21504)),
    "gpt3": (256, 16, 16, 128, 16, {}, (0, 256, 704)),
    "sdar": (256, 32, 4, 128, 20, {"block": 4}, (0, 512, 1024)),
    "granite": (256, 32, 8, 128, 20, {}, (0, 512, 1024)),
}
PAGE = 64


def set_tiling(spec: str) -> None:
    if spec == "default":
        return
    keys, temp = (int(x) for x in spec.split(":"))
    pa._GROUP_KEYS = keys
    pa._TEMP_VMEM_BYTES = temp << 20


def run(shape: str, tiling: str, calls: int, repeats: int, check: bool):
    s, h, hkv, d, width, kw, cursors = SHAPES[shape]
    rng = np.random.default_rng(0)
    n_pages = width + 1
    kp = jnp.asarray(rng.standard_normal((hkv, n_pages, PAGE, d)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((hkv, n_pages, PAGE, d)),
                     jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))[None],
                     jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, s, h, d)), jnp.bfloat16)
    # the wrapper's own jit caches one trace a shape: under another tiling
    # the same shapes have to be traced again
    attend = getattr(getattr(pa, "_paged_chunk", None), "__wrapped__", None)

    def one(q, kp, vp, bt, st):
        if attend is None or tiling == "default":
            return pa.paged_chunk_attention(q, kp, vp, bt, st, **kw)
        return attend(q, kp, vp, bt, st, sm_scale=d ** -0.5,
                      block=kw.get("block", 1), interpret=pa._interpret(),
                      **({"window": kw["window"]} if "window" in kw else {}))

    @jax.jit
    def chained(q, kp, vp, bt, st):
        return jax.lax.fori_loop(
            0, calls, lambda _, x: one(x, kp, vp, bt, st), q)

    desc = None
    if hasattr(pa, "chunk_tiling"):
        tl = pa.chunk_tiling(s, h // hkv, PAGE, width,
                             window=kw.get("window"),
                             block=kw.get("block", 1))
        desc = dict(tile=tl.tile, keys=tl.keys, n_blk=tl.n_blk)
    for cursor in cursors:
        st = jnp.asarray([cursor], jnp.int32)
        chained(q, kp, vp, bt, st).block_until_ready()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            chained(q, kp, vp, bt, st).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        line = dict(shape=shape, cursor=cursor, tiling=tiling, cut=desc,
                    us_per_call=round(best / calls * 1e6, 1))
        if check:
            # against the XLA twin (float32 throughout), largest gap
            want = pa.paged_chunk_attention_xla(q, kp, vp, bt, st, **kw)
            got = jax.jit(one)(q, kp, vp, bt, st)
            line["max_abs_gap"] = float(jnp.max(jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32))))
        if desc is not None:
            line["tile_pairs"] = pa.chunk_tile_pairs(tl, cursor)
        print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--tilings", default="default")
    ap.add_argument("--calls", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"paged_chunk_bench needs a TPU; JAX found {dev.platform!r} "
            "(a time from Pallas interpret mode is no measurement)")
    print(json.dumps(dict(device=dev.device_kind, platform=dev.platform,
                          package=os.path.dirname(pa.__file__))), flush=True)
    refused = 0
    for tiling in args.tilings.split(","):
        set_tiling(tiling)
        for shape in args.shapes:
            if tiling == "default":
                run(shape, tiling, args.calls, args.repeats, args.check)
                continue
            try:
                run(shape, tiling, args.calls, args.repeats, args.check)
            except Exception as e:  # a tiling Mosaic refuses is a finding
                refused += 1
                print(json.dumps(dict(shape=shape, tiling=tiling,
                                      error=repr(e)[:400])), flush=True)
    sys.exit(1 if refused else 0)


if __name__ == "__main__":
    main()
