"""Speculative decoding: a small draft proposes, the target verifies.

Greedy speculation is LOSSLESS — the output equals the target's own
greedy decode token for token; the win is wall-clock (up to gamma+1
tokens per target forward when the draft agrees).

Since r16 speculation is a first-class ServingEngine decode mode:
pass ``draft_model=`` and every admitted request speculates whenever
the decode-slot budget affords it (a speculating request prices as
gamma+1 slots, and gamma adapts per request to the observed accept
rate). The standalone ``generate_speculative`` loop is still shown
at the end for the single-request API.

Run: JAX_PLATFORMS=cpu python examples/speculative.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM


def main():
    paddle.seed(0)
    cfg = GPTConfig.tiny()
    target = GPTForCausalLM(cfg)
    target.eval()
    # a cheaper draft: half width, one layer, same vocab
    paddle.seed(1)
    draft = GPTForCausalLM(GPTConfig(
        vocab_size=cfg.vocab_size, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, max_position_embeddings=128))
    draft.eval()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
               for _ in range(3)]

    # --- engine path: speculation as a decode MODE, not a loop
    plain = ServingEngine(target, max_batch=2, page_size=8,
                          max_seq_len=64)
    rids = [plain.submit(p, max_new_tokens=16) for p in prompts]
    ref = plain.run()

    spec = ServingEngine(target, max_batch=2, page_size=8,
                         max_seq_len=64, draft_model=draft)
    srids = [spec.submit(p, max_new_tokens=16) for p in prompts]
    out = spec.run()

    for rid, srid in zip(rids, srids):
        print("greedy     :", ref[rid])
        print("speculative:", out[srid])
        assert ref[rid] == out[srid]
    acc = spec.spec_tokens_accepted
    rej = spec.spec_tokens_rejected
    print(f"engine rounds={spec.spec_rounds} accepted={acc} "
          f"rejected={rej} (accept rate "
          f"{acc / max(1, acc + rej):.2f})")
    print("identical output — the draft only changes the SCHEDULE")

    # --- the single-request API is the same contract
    prompt = paddle.to_tensor(prompts[0][None])
    solo = target.generate(prompt, max_new_tokens=16, do_sample=False)
    assert solo.numpy()[0, 8:].tolist() == ref[rids[0]]
    spec1 = target.generate_speculative(prompt, draft, max_new_tokens=16,
                                        num_speculative_tokens=4)
    assert (solo.numpy() == spec1.numpy()).all()
    print("generate_speculative agrees with the engine path")


if __name__ == "__main__":
    main()
