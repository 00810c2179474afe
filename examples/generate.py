"""KV-cache decoding with the jitted generate() loop.

Run: JAX_PLATFORMS=cpu python examples/generate.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()
    prompt = paddle.to_tensor(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          (2, 8)).astype(np.int32))
    out = model.generate(prompt, max_new_tokens=24, do_sample=False)
    print("greedy :", out.numpy()[0][:16].tolist(), "...")
    out = model.generate(prompt, max_new_tokens=24, do_sample=True,
                         top_k=8, temperature=0.9)
    print("sampled:", out.numpy()[0][:16].tolist(), "...")
    out = model.generate(prompt, max_new_tokens=24, num_beams=4,
                         length_penalty=0.8)
    print("beam-4 :", out.numpy()[0][:16].tolist(), "...")
    out = model.generate(prompt, max_new_tokens=24, do_sample=False,
                         repetition_penalty=1.3)
    print("penalty:", out.numpy()[0][:16].tolist(), "...")

    # weight-only int8 serving: half the weight bytes per decode step
    from paddle_tpu.nn.quant import quantize_linears
    quantize_linears(model)
    out = model.generate(prompt, max_new_tokens=24, do_sample=False)
    print("int8   :", out.numpy()[0][:16].tolist(), "...")


if __name__ == "__main__":
    main()
