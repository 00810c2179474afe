"""Synced-vs-async training-loop overhead A/B through Model.fit itself.

Measures what the async-by-default fit loop buys, with three arms all
driven through the trainer's real code path (hapi/model.py +
train_step.py), not a hand-rolled pipeline:

  eager   the pre-r07 ``Model.fit`` inner loop (``jit=False``: per-step
          ``train_batch`` + ``float(loss)`` — what a naive user got);
  synced  the jitted step with a per-step host pull
          (``metrics_every=1``, the TRAIN_AB_r05 "mfu_synced" arm);
  async   the dispatch-ahead loop (``metrics_every=k`` — stale-by-k
          pulls, hard sync only at epoch end; the new default).

On a shared-core CPU box the synced/async jitted arms are expected to be
CLOSE (the host thread blocked on a pull frees the core the "device"
compute needs — there is no idle chip to run ahead of); the pair is
banked anyway as the honest CPU datapoint, and the TPU window re-banks
the same A/B where the TRAIN_AB_r05 gap (MFU 0.4627 vs 0.2772) lives.
The eager arm is the loop the async default actually replaced.

Emits one JSON line per phase and a FINAL line in the standard bench.py
schema ({"metric", "value", "unit", "vs_baseline", ...}):

    value        = async steady-state step time, ms
    vs_baseline  = synced_step_ms / async_step_ms (the jitted A/B;
                   ~1.0 on CPU, the pipelining win on chip)

``--bank PATH`` additionally writes the banked-artifact payload
({"step", "backend", "ts", "n_failed_checks", "results"}) every
BENCH_*.json in the repo root carries.

Env knobs: LOOP_BENCH_STEPS (default 64), LOOP_BENCH_K (8),
LOOP_BENCH_REPEATS (3), BENCH_BATCH (8), BENCH_SEQ (32).
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_BACKEND = "unknown"
BENCH_SCHEMA = 1
_LINES = []


def emit(d: dict) -> None:
    d.setdefault("backend", _BACKEND)
    _LINES.append(dict(d))
    print(json.dumps(d), flush=True)


def main() -> int:
    import numpy as np

    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.flags import is_tpu_backend
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import Dataset
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    global _BACKEND
    _BACKEND = jax.default_backend()
    steps = int(os.environ.get("LOOP_BENCH_STEPS", "64"))
    k = int(os.environ.get("LOOP_BENCH_K", "8"))
    repeats = int(os.environ.get("LOOP_BENCH_REPEATS", "3"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "32"))
    on_tpu = is_tpu_backend()

    cfg = GPTConfig.tiny()
    emit({"phase": "init", "steps": steps, "metrics_every": k,
          "batch": batch, "seq": seq, "repeats": repeats,
          "n_params": cfg.num_params()})

    class LMDataset(Dataset):
        def __init__(self):
            rng = np.random.default_rng(0)
            self.data = rng.integers(0, cfg.vocab_size,
                                     (steps * batch, seq + 1)).astype(np.int32)

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return self.data[i, :-1], self.data[i, 1:]

    ds = LMDataset()

    def ce(logits, y):
        return F.cross_entropy(logits.reshape([-1, logits.shape[-1]]),
                               y.reshape([-1]))

    def build():
        paddle.seed(0)
        net = GPTForCausalLM(cfg)
        if on_tpu:
            net.to(dtype="bfloat16")
        model = Model(net)
        model.prepare(
            paddle.optimizer.AdamW(1e-4, parameters=net.parameters(),
                                   multi_precision=on_tpu),
            loss=ce)
        return model

    def fit_once(metrics_every):
        model = build()
        t0 = time.perf_counter()
        model.fit(ds, batch_size=batch, epochs=1, metrics_every=1,
                  num_iters=2, verbose=0)           # compile (untimed)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        model.fit(ds, batch_size=batch, epochs=1,
                  metrics_every=metrics_every, verbose=0)
        wall = time.perf_counter() - t0
        ts = model._train_step
        return {"wall_s": wall, "compile_s": compile_s,
                "syncs": ts.sync_count, "traces": ts.trace_count,
                "throttles": ts.throttle_count}

    def arm(metrics_every, name):
        runs = [fit_once(metrics_every) for _ in range(repeats)]
        best = min(runs, key=lambda r: r["wall_s"])
        rec = {"phase": name, "metrics_every": metrics_every,
               "step_ms": round(best["wall_s"] / steps * 1000, 3),
               "wall_s": round(best["wall_s"], 3),
               "all_wall_s": [round(r["wall_s"], 3) for r in runs],
               "syncs_per_epoch": best["syncs"],
               "traces": best["traces"],
               "throttles": best["throttles"],
               "ok": best["traces"] == 1 and best["throttles"] == 0}
        emit(rec)
        return rec

    # alternating arms would halve cache-thermal bias, but each fit is
    # already best-of-N with a fresh Model; interleave at the run level
    synced = arm(1, "synced")
    is_async = arm(k, "async")

    # the pre-r07 loop: eager per-step train_batch + float(loss). Scaled
    # down (it is ~30x slower on CPU); step_ms is the comparable figure.
    eager_steps = min(steps, int(os.environ.get("LOOP_BENCH_EAGER_STEPS",
                                                "16")))
    model = build()
    model.fit(ds, batch_size=batch, epochs=1, jit=False, num_iters=2,
              verbose=0)                            # warm eager caches
    t0 = time.perf_counter()
    model.fit(ds, batch_size=batch, epochs=1, jit=False,
              num_iters=eager_steps, verbose=0)
    eager_wall = time.perf_counter() - t0
    eager = {"phase": "eager", "steps": eager_steps,
             "step_ms": round(eager_wall / eager_steps * 1000, 3),
             "wall_s": round(eager_wall, 3)}
    emit(eager)

    speedup = (round(synced["step_ms"] / is_async["step_ms"], 3)
               if is_async["step_ms"] else None)
    # the banked row carries its own sync/throttle/retrace evidence
    # (tools/telemetry_dump.py renders it back)
    from paddle_tpu import observability as obs
    telemetry = obs.registry().snapshot()
    emit({
        "metric": "fit_async_step_ms",
        "telemetry": telemetry,
        "value": is_async["step_ms"],
        "unit": "ms_per_step",
        "vs_baseline": speedup,
        "synced_step_ms": synced["step_ms"],
        "async_step_ms": is_async["step_ms"],
        "eager_step_ms": eager["step_ms"],
        "speedup_vs_eager_loop": round(
            eager["step_ms"] / is_async["step_ms"], 2),
        "metrics_every": k,
        "fit_steps": steps,
        "async_syncs_per_epoch": is_async["syncs_per_epoch"],
        "synced_syncs_per_epoch": synced["syncs_per_epoch"],
        "zero_retrace": is_async["traces"] == 1 and synced["traces"] == 1,
        "n_chips": jax.device_count(),
        "bench_schema": BENCH_SCHEMA,
        "step": "loop_overhead",
    })

    if "--bank" in sys.argv:
        path = sys.argv[sys.argv.index("--bank") + 1]
        bad = [l for l in _LINES if l.get("ok") is False]
        payload = {"step": "loop_overhead", "backend": _BACKEND,
                   "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
                   "n_failed_checks": len(bad), "results": _LINES}
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
