"""Continuous-batching serving: requests admit mid-decode, pages recycle.

Run: JAX_PLATFORMS=cpu python examples/serve_engine.py
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
    model = GPTForCausalLM(cfg)
    rng = np.random.default_rng(0)

    eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=64)

    # four requests, two slots: admission is continuous — r2/r3 enter the
    # moment earlier requests finish and return their pages
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 10, 4, 8)]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        active = sum(s is not None for s in eng._slots)
        print(f"step {steps:2d}: active slots={active} "
              f"free pages={eng.pool.free_page_count()}")
    results = eng.run()

    for rid, prompt in zip(rids, prompts):
        solo = model.generate(
            paddle.to_tensor(prompt[None]), max_new_tokens=6,
            do_sample=False, return_full_sequence=False).numpy()[0].tolist()
        assert results[rid] == solo
        print(f"request {rid}: {results[rid]}  (== solo greedy)")

    # ---- automatic prefix caching: a shared system prompt is prefilled
    # ONCE; later requests adopt its pages read-only (copy-on-write pool)
    eng2 = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=64,
                         prefix_cache=True)
    system = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    users = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
             for n in (3, 5, 4)]
    for i, u in enumerate(users):
        p = np.concatenate([system, u]).astype(np.int32)
        rid = eng2.submit(p, max_new_tokens=5)
        out = eng2.run()[rid]
        solo = model.generate(
            paddle.to_tensor(p[None]), max_new_tokens=5,
            do_sample=False, return_full_sequence=False).numpy()[0].tolist()
        assert out == solo
        hit = eng2._prefix.lookup(p)[1]
        print(f"prefix-cache request {i}: cached prefix {hit} tokens, "
              f"tokens {out}  (== solo greedy)")


if __name__ == "__main__":
    main()
