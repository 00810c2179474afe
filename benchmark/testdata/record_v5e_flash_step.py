#!/usr/bin/env python3
"""How ``v5e_flash_step.xplane.pb`` was recorded (on the chip, PR 23):

    python benchmark/testdata/record_v5e_flash_step.py <output directory>

Eight calls of one small jitted step (the program's causal flash
attention over bf16[2,1024,16,64], a matmul, their gradient with respect
to the matmul's weight), each under a ``bench.step`` span with a
``bench.pull`` inside and a ``bench.sleep`` of 2 ms after. The trace is
small enough (73 KB) to keep beside the tests of the reduction.
"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("a device trace needs a TPU")

    def loss(w, q, k, v):
        o = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            is_causal=True)._value
        y = o.reshape(o.shape[0], o.shape[1], -1) @ w
        return jnp.mean(y.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss))
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 1024, 16, 64), jnp.bfloat16)
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16)
    float(step(w, q, q, q)[0])                          # compile
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir)
    for _ in range(8):
        with jax.profiler.TraceAnnotation("bench.step"):
            value, _grad = step(w, q, q, q)
            with jax.profiler.TraceAnnotation("bench.pull"):
                float(value)
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    shutil.copy(found, os.path.join(out_dir, "v5e_flash_step.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
