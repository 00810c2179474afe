"""Profile the GPT-345M train step on the ambient backend and summarize
where the step time goes (MFU diagnosis — BASELINE.md north star).

Captures a jax.profiler trace around a few steps, parses the XPlane proto
dumped under --out, and prints a per-op-category time breakdown as JSON
lines (matmul vs attention kernel vs elementwise vs copy/infeed), plus the
top-N individual ops, next to a wall-clock phase split (per-step synced
vs pipelined dispatch).

Usage: python tools/train_profile.py [--steps 6] [--out .cache/profile]
Env: BENCH_MODEL/BENCH_BATCH/BENCH_SEQ as bench.py.
"""
import glob
import json
import os
import sys
import time

_BACKEND = "unknown"


def emit(d: dict) -> None:
    """Print one JSON line; every line names the backend it ran on."""
    d.setdefault("backend", _BACKEND)
    print(json.dumps(d), flush=True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    steps = 6
    out = os.path.join(REPO, ".cache", "profile")
    argv = sys.argv[1:]
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    if "--out" in argv:
        out = argv[argv.index("--out") + 1]

    import numpy as np

    import jax
    import paddle_tpu as paddle
    import bench as bench_mod

    global _BACKEND
    _BACKEND = jax.default_backend()
    emit({"phase": "init", "devices": [str(d) for d in jax.devices()]})

    # bench.py's recipe verbatim, so the profiled step IS the benchmarked
    # step (same dtype policy, master weights, remat knob)
    cfg, batch, seq, build, on_tpu = bench_mod.build_train_setup(
        os.environ.get("BENCH_MODEL", "gpt345m"))
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    model, step = build(remat)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
    y = paddle.to_tensor(ids[:, 1:].astype(np.int32))

    # ALL step calls run under the same auto_cast as bench.py's measured
    # loop: the traced program must be the benchmarked program (and must
    # hit the persistent compile cache the train step warmed)
    amp = lambda: paddle.amp.auto_cast(enable=on_tpu, level="O1",
                                       dtype="bfloat16")
    t0 = time.perf_counter()
    with amp():
        float(step(x, y))   # compile + one step
    emit({"phase": "compile", "s": round(time.perf_counter() - t0, 2)})

    # wall-clock phase split: per-step synced vs pipelined — the
    # pipelined side is the trainer's own async window (dispatch without
    # blocking, TrainStep.sync() as the closing barrier), so the split
    # measures exactly what Model.fit's async-by-default loop removes
    with amp():
        for _ in range(2):
            step(x, y)
            step.pull_metrics(lag=0)
        t0 = time.perf_counter()
        for _ in range(steps):
            step(x, y)
            step.pull_metrics(lag=0)   # metrics_every=1: per-step sync
        synced = (time.perf_counter() - t0) / steps
        # the pipelined arm must fit in the dispatch window: a throttled
        # call host-syncs inside __call__ and would be banked as
        # "pipelined" time (bench.py asserts the same invariant)
        step.max_in_flight = max(step.max_in_flight, steps)
        t0 = time.perf_counter()
        for _ in range(steps):
            step(x, y)
        step.sync()
        piped = (time.perf_counter() - t0) / steps
    emit({"phase": "wallclock", "synced_step_s": round(synced, 4),
          "pipelined_step_s": round(piped, 4),
          "per_step_sync_overhead_s": round(synced - piped, 4),
          "step_traces": step.trace_count,
          "step_throttles": step.throttle_count})

    # device trace. Only files CREATED BY THIS RUN count — a stale dump
    # from an earlier (possibly CPU) run must never be summarized and
    # banked as this run's evidence. Errors emit ok:false so the sprint's
    # failed-check retry machinery re-runs the step on a later window.
    os.makedirs(out, exist_ok=True)
    pattern = os.path.join(out, "**", "*.xplane.pb")
    before = set(glob.glob(pattern, recursive=True))
    try:
        with jax.profiler.trace(out), amp():
            for _ in range(steps):
                loss = step(x, y)
            float(loss)
    except Exception as e:
        emit({"phase": "trace", "ok": False, "error": repr(e)[:300]})
        return 0

    fresh = sorted(set(glob.glob(pattern, recursive=True)) - before,
                   key=os.path.getmtime)
    if not fresh:
        emit({"phase": "trace", "ok": False, "error": "no xplane dumped"})
        return 0
    summarize_xplane(fresh[-1], steps)
    return 0


def _categorize(name: str) -> str:
    n = name.lower()
    if "custom-call" in n or "pallas" in n or "flash" in n:
        return "pallas/custom"
    if "fusion" in n:
        return "fusion"
    # "convert" (dtype cast) must not hit the "conv"olution check: casts
    # around bf16/f32 master weights are exactly the overhead this tool
    # exists to surface
    if any(k in n for k in ("copy", "transpose", "bitcast", "reshape",
                            "convert")):
        return "copy/layout"
    if "convolution" in n or "dot" in n or "matmul" in n or "einsum" in n:
        return "matmul"
    if any(k in n for k in ("all-reduce", "all-gather", "reduce-scatter",
                            "collective", "permute")):
        return "collective"
    if any(k in n for k in ("infeed", "outfeed", "transfer")):
        return "host-transfer"
    return "other"


def _read_varint(buf, i):
    r = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def _fields(buf):
    """Yield (field_no, wire_type, value_bytes_or_int) of a proto message."""
    i = 0
    n = len(buf)
    while i < n:
        key, i = _read_varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _read_varint(buf, i)
            yield fno, wt, v
        elif wt == 2:
            ln, i = _read_varint(buf, i)
            yield fno, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:
            yield fno, wt, int.from_bytes(buf[i:i + 4], "little")
            i += 4
        elif wt == 1:
            yield fno, wt, int.from_bytes(buf[i:i + 8], "little")
            i += 8
        else:  # unsupported group etc.
            return


def summarize_xplane(path: str, steps: int) -> None:
    """Minimal XPlane proto walk (no tensorboard dependency): decode the
    XSpace wire format enough to sum event durations per TPU op name."""
    with open(path, "rb") as f:
        space = f.read()
    # XSpace: repeated XPlane planes = 1. Device planes ("/device:TPU:0")
    # exist for real-chip captures; a CPU run only dumps
    # the "/host:CPU" plane, whose XLA op executions still carry op names —
    # summarize every plane separately and let the reader pick.
    per_plane = {}
    for fno, wt, plane in _fields(space):
        if fno != 1 or wt != 2:
            continue
        # XPlane: name=2(str), lines=3, event_metadata=11 (map<int64,XEventMetadata>)
        pname = ""
        metas = {}
        lines = []
        for f2, w2, v in _fields(plane):
            if f2 == 2 and w2 == 2:
                pname = v.decode("utf-8", "replace")
            elif f2 == 3 and w2 == 2:
                lines.append(v)
            elif f2 == 4 and w2 == 2:
                # map entry: key=1 varint, value=2 XEventMetadata{id=1,name=2}
                k = None
                mname = ""
                for f3, w3, v3 in _fields(v):
                    if f3 == 1 and w3 == 0:
                        k = v3
                    elif f3 == 2 and w3 == 2:
                        for f4, w4, v4 in _fields(v3):
                            if f4 == 2 and w4 == 2:
                                mname = v4.decode("utf-8", "replace")
                if k is not None:
                    metas[k] = mname
        if pname in ("/host:metadata", "Task Environment"):
            continue
        # A device plane carries several OVERLAPPING lines (XLA Modules,
        # XLA Ops, Steps) spanning the same wall time — summing all of
        # them double/triple-counts. Prefer the per-op line when present.
        named = []
        for line in lines:
            lname = ""
            for f3, w3, v3 in _fields(line):
                if f3 == 2 and w3 == 2:
                    lname = v3.decode("utf-8", "replace")
            named.append((lname, line))
        op_lines = [l for n, l in named if "xla ops" in n.lower()]
        use = op_lines or [l for _, l in named]
        totals, op_totals = per_plane.setdefault(pname, ({}, {}))
        for line in use:
            # XLine: events = 4
            for f3, w3, ev in _fields(line):
                if f3 != 4 or w3 != 2:
                    continue
                # XEvent: metadata_id=1, duration_ps=3
                mid = dur = 0
                for f4, w4, v4 in _fields(ev):
                    if f4 == 1 and w4 == 0:
                        mid = v4
                    elif f4 == 3 and w4 == 0:
                        dur = v4
                name = metas.get(mid, f"op_{mid}")
                cat = _categorize(name)
                totals[cat] = totals.get(cat, 0) + dur
                op_totals[name] = op_totals.get(name, 0) + dur
    device_planes = [p for p in per_plane if "TPU" in p or "/device" in p.lower()]
    show = device_planes or list(per_plane)
    for pname in show:
        totals, op_totals = per_plane[pname]
        tot = sum(totals.values()) or 1
        emit({"phase": "categories", "plane": pname,
              "total_ms": round(tot / 1e9, 2),
              "per_step_ms": round(tot / 1e9 / max(steps, 1), 2),
              **{k: round(v / tot, 4)
                 for k, v in sorted(totals.items(),
                                    key=lambda kv: -kv[1])}})
        top = sorted(op_totals.items(), key=lambda kv: -kv[1])[:15]
        for name, dur in top:
            emit({"phase": "top_op", "plane": pname, "name": name[:120],
                  "ms": round(dur / 1e9, 2), "frac": round(dur / tot, 4)})


if __name__ == "__main__":
    sys.exit(main())
