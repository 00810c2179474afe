"""Single-host GPT pretraining with the jitted TrainStep.

Run (CPU mesh):   JAX_PLATFORMS=cpu python examples/train_gpt.py
Run (TPU chip):   python examples/train_gpt.py

Mirrors the reference's gpt pretrain loop (tools/train.py style): config,
synthetic data, AdamW + cosine LR + global-norm clip, AMP on TPU, a
checkpoint save/restore at the end.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.hapi import TrainStep
from paddle_tpu.models import GPTConfig, GPTForCausalLM


def main():
    import jax

    on_tpu = paddle.flags.is_tpu_backend()
    cfg = GPTConfig.gpt3_345m() if on_tpu else GPTConfig.tiny()
    batch, seq, steps = (8, 1024, 50) if on_tpu else (4, 64, 20)

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    sched = paddle.optimizer.lr.CosineAnnealingDecay(1e-4, T_max=steps)
    opt = paddle.optimizer.AdamW(
        sched, parameters=model.parameters(), weight_decay=0.01,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
        multi_precision=on_tpu)
    step = TrainStep(model, opt)

    rng = np.random.default_rng(0)
    for i in range(steps):
        ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
        x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
        y = paddle.to_tensor(ids[:, 1:].astype(np.int32))
        loss = step(x, y)
        if i % 5 == 0 or i == steps - 1:
            print(f"step {i:3d}  loss {float(loss):.4f}  "
                  f"lr {opt.get_lr():.2e}")
        # NB: TrainStep steps the LR scheduler itself — do not also call
        # sched.step() here (it would run the schedule at 2x speed)

    step.sync_to_model()
    paddle.save(model.state_dict(), "/tmp/gpt_example.pdparams")
    model.set_state_dict(paddle.load("/tmp/gpt_example.pdparams"))
    print("checkpoint round-trip OK")


if __name__ == "__main__":
    main()
