"""Pipeline-parallel Llama with the zero-bubble (ZBH1) schedule.

Run on the CPU-simulated 8-device mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_pipeline_zbh1.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel \
        import PipelineTrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLMPipe

    if len(jax.devices()) < 8:
        sys.exit("need 8 devices: run with JAX_PLATFORMS=cpu "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                      num_attention_heads=4, num_key_value_heads=2,
                      intermediate_size=128, max_position_embeddings=128)
    pipe = LlamaForCausalLMPipe(cfg, num_stages=4)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "pp"))
    step = PipelineTrainStep(
        pipe, paddle.optimizer.AdamW(1e-3, parameters=pipe.parameters()),
        mesh, num_microbatches=4, schedule="zbh1")
    print("mesh: dp=2 x pp=4, schedule=zbh1")

    rng = np.random.default_rng(0)
    for i in range(5):
        ids = rng.integers(0, cfg.vocab_size, (8, 33))
        loss = step(paddle.to_tensor(ids[:, :-1].astype(np.int32)),
                    paddle.to_tensor(ids[:, 1:].astype(np.int32)))
        print(f"step {i}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
