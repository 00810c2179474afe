#!/usr/bin/env python
"""Train-step benchmark: GPT-3 345M (BASELINE.json configs[0]) through the
jitted ``hapi.TrainStep`` on whatever backend JAX has, in this one process.

Prints ONE JSON line that names the device it ran on (``platform``,
``device_kind``, ``n_devices``). Any error is a traceback and a non-zero
exit: there is no probe child, no re-exec on another backend, no retry
under a different recipe. MFU is reported only against a published peak
(``paddle_tpu.utils.metrics.PEAK_FLOPS``), so a CPU run carries
throughput and no MFU.

Env knobs: BENCH_MODEL (gpt345m|gpt_tiny|llama_tiny), BENCH_STEPS,
BENCH_BATCH, BENCH_SEQ, BENCH_REMAT.
"""

import json
import os
from typing import Optional

REPO = os.path.dirname(os.path.abspath(__file__))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; call
    BEFORE importing jax. ``JAX_COMPILATION_CACHE_DIR`` wins where it is
    set; otherwise the cache lives at the fixed ``<checkout>/.cache/xla``
    (the path is part of the cache key: a directory that moves never
    hits). Every program is kept, however quick its compile."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".cache", "xla"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def build_train_setup(model_name: Optional[str] = None):
    """Single source of the bench's model/optimizer/TrainStep recipe.
    tools/train_profile.py and chip_smoke.py reuse it so the profiled and
    the smoked step ARE the benchmarked step (same dtype policy, weight
    decay, master weights). Returns (cfg, batch, seq, build, on_tpu) with
    ``build(remat) -> (model, TrainStep)``."""
    import paddle_tpu as paddle
    from paddle_tpu.flags import is_tpu_backend
    from paddle_tpu.hapi import TrainStep
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                                   LlamaForCausalLM)

    if model_name is None:
        model_name = os.environ.get("BENCH_MODEL", "gpt345m")
    on_tpu = is_tpu_backend()
    if model_name == "gpt345m":
        cfg = GPTConfig.gpt3_345m()
        batch = int(os.environ.get("BENCH_BATCH", "8"))
        seq = int(os.environ.get("BENCH_SEQ", "1024"))
        model_cls = GPTForCausalLM
    elif model_name == "gpt_tiny":
        cfg = GPTConfig.tiny()
        batch = int(os.environ.get("BENCH_BATCH", "4"))
        seq = int(os.environ.get("BENCH_SEQ", "64"))
        model_cls = GPTForCausalLM
    else:
        cfg = LlamaConfig.tiny()
        batch = int(os.environ.get("BENCH_BATCH", "4"))
        seq = int(os.environ.get("BENCH_SEQ", "64"))
        model_cls = LlamaForCausalLM

    def build(remat: bool):
        paddle.seed(0)
        model = model_cls(cfg)
        if on_tpu:
            # bf16 params + fp32 master weights: the TPU training recipe
            model.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(
            1e-4, parameters=model.parameters(), weight_decay=0.01,
            multi_precision=on_tpu)
        return model, TrainStep(model, opt, remat=remat)

    return cfg, batch, seq, build, on_tpu


def run_bench() -> dict:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.utils.metrics import SpeedMeter

    model_name = os.environ.get("BENCH_MODEL", "gpt345m")
    steps = int(os.environ.get("BENCH_STEPS", "12"))
    cfg, batch, seq, build, on_tpu = build_train_setup(model_name)
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    model, step = build(remat)
    n_params = sum(p.size for p in model.parameters())

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
    y = paddle.to_tensor(ids[:, 1:].astype(np.int32))

    meter = SpeedMeter(
        n_params=n_params, n_layers=cfg.num_hidden_layers,
        hidden=cfg.hidden_size, seq_len=seq,
        n_chips=jax.device_count(), warmup=2)
    losses = []
    meter.start()
    for _ in range(steps):
        with paddle.amp.auto_cast(enable=on_tpu, level="O1",
                                  dtype="bfloat16"):
            step(x, y)
        # the loss is wanted on the host; pulling it closes the step
        losses.append(step.pull_metrics(lag=0)["loss"])
        meter.step(batch * seq)

    dev = jax.devices()[0]
    result = {
        "model": model_name,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        **meter.summary(),
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "remat": remat,
        # probe-visible loop health: one trace for the whole run, and the
        # async window never throttled
        "step_traces": step.trace_count,
        "step_throttles": step.throttle_count,
    }
    return result


if __name__ == "__main__":
    use_compile_cache()
    print(json.dumps(run_bench()))
