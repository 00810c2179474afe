"""Fused-vs-unfused transformer-block decode A/B on the serving engine.

Measures the ONE number the fused_block_decode work exists for: the
steady-state per-step latency of `ServingEngine.step()` with the fused
one-kernel-per-layer program (FLAGS_fused_block_decode=1,
kernels/fused_block_decode.py) against the generic op-chain step
(FLAGS_fused_block_decode=0), same model, same batch, same backend — plus
the decode program cache's trace counts, asserting the zero-retrace
contract holds over the whole run.

Emits one JSON line per phase and a FINAL line in the standard bench.py
schema ({"metric", "value", "unit", "vs_baseline", ...}) so the sprint
harness banks it into the BENCH_*.json ledger unchanged:

    value        = fused steady-state step time, ms
    vs_baseline  = unfused_step_ms / fused_step_ms (the speedup; >= 1.0
                   is the acceptance bar "fused <= unfused")

Timing follows bench.py's decode protocol: compile on the first step,
then wall-clock the drain loop (each step() host-syncs by pulling the
argmax tokens). Test mode (CHIP_SPRINT_TEST=1): LlamaConfig.tiny() on
CPU validates plumbing + schema.

r17 adds the cross-layer N-sweep: the same A/B repeated at
FLAGS_fused_block_layers=N for each N in FUSED_BENCH_NLAYERS (default
"1,2,4" — N=1 is the per-layer fused kernel, N>1 the grouped
one-pallas_call-per-N-layers program; on CPU both run their pure-jnp
refs, so the sweep is an apples-to-apples program-structure A/B on any
backend). The FINAL row carries ``nlayer_sweep`` ({N: step_ms}) and
``nlayer_ok`` (best grouped step <= per-layer step AND zero retraces at
every rung) — banked as FUSED_DECODE_BENCH_r17.json.

Env knobs: FUSED_BENCH_MODEL (llama_tiny|llama2_7b), BENCH_DECODE_TOKENS,
BENCH_DECODE_BATCH, BENCH_PROMPT_LEN, FUSED_BENCH_NLAYERS.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_BACKEND = "unknown"
BENCH_SCHEMA = 1


def emit(d: dict) -> None:
    d.setdefault("backend", _BACKEND)
    print(json.dumps(d), flush=True)


def main() -> int:
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import flags
    from paddle_tpu.flags import is_tpu_backend
    from paddle_tpu.generation.program_cache import decode_program_cache
    from paddle_tpu.generation.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    global _BACKEND
    _BACKEND = jax.default_backend()
    test_mode = (os.environ.get("CHIP_SPRINT_TEST") == "1"
                 or not is_tpu_backend())
    name = os.environ.get("FUSED_BENCH_MODEL",
                          "llama_tiny" if test_mode else "llama2_7b")
    if name == "llama_tiny":
        cfg = LlamaConfig.tiny()
    elif name == "llama_small":
        # CPU A/B workhorse for the N-sweep: ~30x the matmul work of
        # tiny per step, so the grouped-vs-per-layer program delta rises
        # above the engine's fixed host overhead; 4 layers lets N=4 form
        # a single full group
        cfg = LlamaConfig(vocab_size=1024, hidden_size=256,
                          num_hidden_layers=4, num_attention_heads=8,
                          num_key_value_heads=4, intermediate_size=512,
                          max_position_embeddings=256)
    else:
        cfg = LlamaConfig.llama2_7b()
    batch = int(os.environ.get("BENCH_DECODE_BATCH", "4"))
    steps = int(os.environ.get("BENCH_DECODE_TOKENS",
                               "16" if name.startswith("llama_t") else "64"))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN",
                                    "24" if name == "llama_tiny" else "128"))
    page = 8 if name == "llama_tiny" else 64
    max_seq = prompt_len + steps + page

    emit({"phase": "init", "model": name, "batch": batch,
          "decode_tokens": steps, "prompt_len": prompt_len})

    t0 = time.perf_counter()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if is_tpu_backend():
        model.to(dtype="bfloat16")
    model.eval()
    emit({"phase": "build", "s": round(time.perf_counter() - t0, 2),
          "n_params": cfg.num_params()})

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               .astype(np.int32) for _ in range(batch)]

    def run(fused: bool, nlayers: int = 1) -> dict:
        flags.set_flags({"fused_block_decode": fused,
                         "fused_block_layers": nlayers})
        eng = ServingEngine(model, max_batch=batch, page_size=page,
                            max_seq_len=max_seq)
        for p in prompts:
            eng.submit(p, steps)
        t_compile = time.perf_counter()
        eng.step()                    # prefills + first decode: compiles
        compile_s = time.perf_counter() - t_compile
        traces_before = decode_program_cache().trace_count(eng.decode_key)
        n = 0
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()                # host-syncs on the argmax pull
            n += 1
        wall = time.perf_counter() - t0
        traces = decode_program_cache().trace_count(eng.decode_key)
        return {"kind": eng.decode_key.kind,
                "step_ms": round(wall / max(n, 1) * 1000, 3),
                "steps_timed": n,
                "first_step_s": round(compile_s, 3),
                "tokens_per_sec": round(batch * n / wall, 1) if wall else None,
                "traces": traces,
                "retraces_during_run": traces - traces_before}

    sweep_ns = [int(s) for s in os.environ.get(
        "FUSED_BENCH_NLAYERS", "1,2,4").split(",") if s.strip()]
    prior = {"fused_block_decode": flags.get_flag("fused_block_decode"),
             "fused_block_layers": flags.get_flag("fused_block_layers")}
    sweep = {}
    try:
        fused = run(True)
        unfused = run(False)
        # r17 cross-layer sweep: N=1 is the per-layer fused program
        # (== `fused` modulo timing noise but re-measured so every rung
        # shares one warm process), N>1 the grouped program. Each rung
        # keeps its best-of-k step time, and the k repeats are
        # round-robin-interleaved across rungs — host noise is temporally
        # correlated, so sequential per-rung blocks bias whole rungs
        repeats = int(os.environ.get("FUSED_BENCH_REPEATS", "3"))
        runs_by_n = {n: [] for n in sweep_ns}
        for _ in range(max(repeats, 1)):
            for n in sweep_ns:
                runs_by_n[n].append(run(True, nlayers=n))
        for n in sweep_ns:
            runs = runs_by_n[n]
            best = min(runs, key=lambda r: r["step_ms"])
            best["retraces_during_run"] = max(
                r["retraces_during_run"] for r in runs)
            sweep[n] = best
            emit({"phase": f"nlayer_{n}", "repeats": len(runs), **best})
    finally:
        flags.set_flags(prior)
    emit({"phase": "fused", **fused})
    emit({"phase": "unfused", **unfused})

    speedup = (round(unfused["step_ms"] / fused["step_ms"], 3)
               if fused["step_ms"] else None)
    per_layer_ms = sweep.get(1, fused)["step_ms"]
    grouped = {n: r for n, r in sweep.items() if n > 1}
    best_n = (min(grouped, key=lambda n: grouped[n]["step_ms"])
              if grouped else None)
    nlayer_ok = bool(
        grouped
        and grouped[best_n]["step_ms"] <= per_layer_ms
        and all(r["retraces_during_run"] == 0 for r in sweep.values()))
    # the banked row carries its own retrace/cache/latency evidence
    # (tools/telemetry_dump.py renders it back)
    from paddle_tpu import observability as obs
    telemetry = obs.registry().snapshot()
    emit({
        "metric": "fused_decode_step_ms",
        "telemetry": telemetry,
        "memory": obs.memory.section(),
        "value": fused["step_ms"],
        "unit": "ms_per_step",
        "vs_baseline": speedup,
        "fused_step_ms": fused["step_ms"],
        "unfused_step_ms": unfused["step_ms"],
        "fused_tokens_per_sec": fused["tokens_per_sec"],
        "unfused_tokens_per_sec": unfused["tokens_per_sec"],
        "decode_batch": batch,
        "decode_tokens": steps,
        "model": name,
        "fused_kind": fused["kind"],
        "nlayer_sweep": {str(n): r["step_ms"] for n, r in sweep.items()},
        "nlayer_kinds": {str(n): r["kind"] for n, r in sweep.items()},
        "nlayer_best": best_n,
        "nlayer_vs_per_layer": (round(per_layer_ms
                                      / grouped[best_n]["step_ms"], 3)
                                if grouped and grouped[best_n]["step_ms"]
                                else None),
        "nlayer_ok": nlayer_ok,
        "zero_retrace": fused["retraces_during_run"] == 0
        and unfused["retraces_during_run"] == 0,
        "bench_schema": BENCH_SCHEMA,
        "step": "fused_decode",
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
