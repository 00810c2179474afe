"""Hybrid-parallel Llama pretraining: dp x mp (TP) via the fleet API.

Run on the CPU-simulated 8-device mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_llama_hybrid.py

The same script runs unchanged on a real TPU slice — the mesh comes from
the hybrid topology, the shardings from the Megatron dist_attr
annotations, and XLA inserts the collectives (GSPMD).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.base_topology import (
        create_hybrid_communicate_group)
    from paddle_tpu.hapi import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama import annotate_llama_tp

    n = len(jax.devices())
    mp = 2 if n % 2 == 0 else 1
    dp = n // mp
    hcg = create_hybrid_communicate_group(dp_degree=dp, mp_degree=mp)
    mesh = hcg.get_mesh()
    print(f"mesh: dp={dp} x mp={mp} over {n} devices")

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    annotate_llama_tp(model)           # Megatron TP layout as dist_attr
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = TrainStep(model, opt, mesh=mesh, data_axes=("dp",))

    rng = np.random.default_rng(0)
    batch = 2 * dp
    for i in range(10):
        ids = rng.integers(0, cfg.vocab_size, (batch, 33))
        loss = step(paddle.to_tensor(ids[:, :-1].astype(np.int32)),
                    paddle.to_tensor(ids[:, 1:].astype(np.int32)))
        print(f"step {i}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
