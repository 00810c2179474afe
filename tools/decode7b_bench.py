"""Llama-2-7B weight-only-int8 serving on ONE v5e chip.

BASELINE configs[1] names Llama-2 7B as the v5e model; its bf16 weights
(13.4 GB) cannot even materialize next to an int8 copy on a 16 GB chip.
This bench exists because the framework's streaming quantization path
(nn/quant.py QuantizedLinear.from_linear over LazyGuard meta params)
makes the model loadable at all: Linears materialize one at a time,
quantize to int8 on device, and free their bf16 — peak HBM is the int8
weights accumulated so far plus one dense layer (~90 MB).

Measures the serving path end to end on the ambient backend:
  1. build+quantize wall time and resulting weight bytes;
  2. paged-KV greedy decode (kernels/paged_attention.py block tables —
     the block_multihead_attention serving machinery) at batch 1 and 8;
  3. the int8 HBM roofline these numbers chase: a single decode token
     must stream every int8 weight byte once, so tokens/sec tops out
     near bandwidth / weight_bytes (~819 GB/s / 6.6 GB ~ 124 tok/s
     single-stream on v5e; batching amortizes the same bytes).

Timing protocol: warm compile first, a host pull of the tokens closes
every run, steady-state rate = (N-1) tokens / (t_full - t_prefill_plus_1).

Test mode (CHIP_SPRINT_TEST=1): LlamaConfig.tiny() on CPU validates the
full plumbing — lazy build, quantize, paged decode, JSON schema.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_BACKEND = "unknown"


def emit(d: dict) -> None:
    d.setdefault("backend", _BACKEND)
    print(json.dumps(d), flush=True)


def main() -> int:
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.framework import materialize
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn.quant import quantize_linears

    global _BACKEND
    _BACKEND = jax.default_backend()
    test_mode = os.environ.get("CHIP_SPRINT_TEST") == "1"
    cfg = LlamaConfig.tiny() if test_mode else LlamaConfig.llama2_7b()
    decode_tokens = 8 if test_mode else int(
        os.environ.get("BENCH_DECODE_TOKENS", "128"))
    prompt_len = 8 if test_mode else 128
    page_size = 8 if test_mode else 64

    emit({"phase": "init", "model": "llama2_7b" if not test_mode
          else "llama_tiny", "devices": [str(d) for d in jax.devices()]})

    t0 = time.perf_counter()
    paddle.seed(0)
    with paddle.LazyGuard():
        model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    quantize_linears(model)   # streams each Linear: bf16 -> int8 -> free
    materialize(model)        # embeddings + norms (bf16, kept dense)
    model.eval()

    def nbytes(t):
        v = t._value
        return v.size * v.dtype.itemsize

    int8_bytes = sum(nbytes(b) for bname, b in model.named_buffers()
                     if "quant_weight" in bname or "weight_scale" in bname)
    dense_bytes = sum(nbytes(p) for p in model.parameters())
    # sync on the LAST-dispatched buffer (lm_head's int8 weight): device
    # ops complete in dispatch order, so this waits for the whole
    # streamed quantize, not just the first materialized array
    from paddle_tpu.nn.quant import QuantizedLinear
    last_q = [l for l in model.sublayers()
              if isinstance(l, QuantizedLinear)][-1]
    np.asarray(last_q.quant_weight._value[:1, :1])
    emit({"phase": "build_quantize", "s": round(time.perf_counter() - t0, 2),
          "int8_weight_gb": round(int8_bytes / 2**30, 3),
          "dense_param_gb": round(dense_bytes / 2**30, 3)})

    from paddle_tpu.flags import is_tpu_backend
    bw = 819e9 if is_tpu_backend() else 50e9
    roofline = bw / (int8_bytes + dense_bytes)
    emit({"phase": "roofline", "hbm_gb_per_s": bw / 1e9,
          "single_stream_tokens_per_sec_ceiling": round(roofline, 1)})

    rng = np.random.default_rng(0)

    def timed_paged(batch, n_tokens, repeats=2):
        prompt = paddle.to_tensor(rng.integers(
            0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32))

        def run(n):
            out = model.generate_paged(prompt, max_new_tokens=n,
                                       page_size=page_size)
            np.asarray(out.value)      # host pull closes the run

        run(n_tokens)                  # warm: compile prefill + decode step
        best = float("inf")
        for _ in range(repeats):
            t = time.perf_counter()
            run(n_tokens)
            best = min(best, time.perf_counter() - t)
        run(1)
        t = time.perf_counter()
        run(1)
        t_one = time.perf_counter() - t
        dt = best - t_one
        steady = (n_tokens - 1) * batch / dt if dt > 0.05 * best else None
        return {"batch": batch, "new_tokens": n_tokens,
                "e2e_s": round(best, 3),
                "prefill_plus_1_s": round(t_one, 3),
                "paged_decode_tokens_per_sec":
                    round(steady, 1) if steady else None}

    for batch in (1, 8):
        rec = timed_paged(batch, decode_tokens)
        rec["phase"] = "paged_decode"
        emit(rec)

    return 0


if __name__ == "__main__":
    sys.exit(main())
