"""The north-star program build (BASELINE configs[2], VERDICT r2 item 3):
Llama-2-70B under GroupSharded stage3 + mp x pp on a simulated TPU
v5p-128 — the full sharded train step is constructed abstractly (LazyGuard
meta params + AbstractMesh) and lowered for the real 'tpu' platform, and
the per-device resident state is asserted to fit v5p HBM.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLMPipe
from paddle_tpu.optimizer import AdamW

V5P_HBM_BYTES = 95 * 10**9          # public v5p spec: 95 GB HBM per chip


def _build_70b_step(dp=2, pp=8, mp=8, microbatches=8):
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import (
        PipelineTrainStep)

    cfg = LlamaConfig.llama2_70b()
    with paddle.LazyGuard():
        pipe = LlamaForCausalLMPipe(cfg, num_stages=pp, tensor_parallel=True)
    n_params = sum(int(np.prod(p.shape)) for p in pipe.parameters())
    assert n_params > 6.8e10, n_params          # ~68.98B

    mesh = AbstractMesh((dp, pp, mp), ("dp", "pp", "mp"))
    opt = AdamW(learning_rate=1e-4, parameters=pipe.parameters(),
                weight_decay=0.1, multi_precision=True)
    step = PipelineTrainStep(
        pipe, opt, mesh, num_microbatches=microbatches,
        remat=True, sharding_level=3, sharding_axis="dp",
        abstract=True, param_dtype=jnp.bfloat16)
    return cfg, step, n_params


class TestLlama70BNorthStar:
    def test_state_fits_v5p_hbm(self):
        cfg, step, n_params = _build_70b_step()
        by = step.per_device_state_bytes()
        # sanity: totals reconstruct the real model scale
        total_params_bytes = by["params"] * 1  # per-device
        assert by["params"] > 0 and by["slots"] > 0 and by["master"] > 0
        # bf16 params + f32 moments(2x) + f32 master = 14 bytes/param,
        # spread over the 128-chip state shardings
        assert by["total"] < 0.25 * V5P_HBM_BYTES, (
            f"resident state {by['total']/1e9:.1f} GB leaves no activation "
            f"headroom on a 95 GB chip")
        # the dominant stacked-block state must be sharded over all three
        # axes (pp stack dim, mp TP dim, dp ZeRO-3): within 2x of perfect
        # 128-way sharding of the 14n bytes
        perfect = 14 * n_params / 128
        assert by["total"] < 2 * perfect, (by, perfect)

    def test_lowers_for_tpu_with_full_mesh(self):
        cfg, step, _ = _build_70b_step()
        b, s = 16, 4096
        x = jax.ShapeDtypeStruct((b, s), jnp.int32)
        y = jax.ShapeDtypeStruct((b, s), jnp.int32)
        lowered = step.lower(x, y)
        text = lowered.as_text()
        assert "sdy.sharding" in text or "mhlo.sharding" in text
        assert ('"dp"=2' in text and '"pp"=8' in text and '"mp"=8' in text) \
            or "num_partitions = 128" in text
        # the pp-sharded stacked-block annotation must be in the program
        # (shardy lowers pre-SPMD: the ring collective-permutes appear
        # after sdy propagation at compile time)
        assert '{"pp"}' in text or "collective_permute" in text
