"""Paged-KV-cache serving: shared page pool, block tables, page recycling.

The vLLM-style serving substrate (reference: block_multihead_attention):
requests draw cache pages from ONE shared pool and return them on
completion, so HBM holds ceil(len/page) pages per live request instead of
a max-length ring buffer each.

Run: JAX_PLATFORMS=cpu python examples/paged_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM


def main():
    paddle.seed(0)
    cfg = GPTConfig.tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)

    # request 1: batch of two prompts decoding over a paged pool
    prompt = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (2, 7)).astype(np.int32))
    out = model.generate_paged(prompt, max_new_tokens=8, page_size=8)
    print("request 1:", out.numpy()[0].tolist())

    # the ring-buffer scan path produces the identical tokens
    ring = model.generate(prompt, max_new_tokens=8, do_sample=False)
    assert (out.numpy() == ring.numpy()).all()
    print("matches ring-buffer generate token-for-token")

    # page accounting: the pool-level API that a continuous-batching
    # scheduler drives directly (allocate/append/attend/free)
    from paddle_tpu.kernels.paged_attention import PagedKVCache
    import jax.numpy as jnp
    pool = PagedKVCache(num_layers=cfg.num_hidden_layers, num_pages=32,
                        page_size=8, num_kv_heads=cfg.num_attention_heads,
                        head_dim=cfg.hidden_size // cfg.num_attention_heads,
                        max_batch=4, max_seq_len=64, dtype=jnp.float32)
    pool.allocate(0, 30)
    print("after admit:   free pages =", pool.free_page_count())
    pool.free_sequence(0)
    print("after release: free pages =", pool.free_page_count())


if __name__ == "__main__":
    main()
