"""Install sanity check (reference: python/paddle/utils/install_check.py)."""

from __future__ import annotations

import numpy as np


def run_check() -> None:
    """``paddle.utils.run_check`` analogue: verifies device visibility, a
    compiled matmul on the default device, and (if >1 device) a psum across
    all devices."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as ptpu

    devices = jax.devices()
    print(f"paddle_tpu {ptpu.__version__} is installed; "
          f"found {len(devices)} device(s): {[str(d) for d in devices]}")

    from paddle_tpu.generation.program_cache import \
        clear_decode_program_cache

    x = ptpu.randn([128, 128], dtype="float32")
    # correctness probe at full precision (the MXU's default bf16-accumulated
    # path is intentionally inexact vs numpy); tpu_matmul_precision rides
    # compiled serving programs (PROGRAM_FLAGS), so re-arm the program
    # cache around the flag flip
    ptpu.set_flags({"tpu_matmul_precision": "highest"})
    clear_decode_program_cache()
    try:
        y = ptpu.matmul(x, x)
        assert tuple(y.shape) == (128, 128)
        np.testing.assert_allclose(
            y.numpy(), np.asarray(x._value) @ np.asarray(x._value),
            rtol=1e-3, atol=1e-3)
    finally:
        ptpu.set_flags({"tpu_matmul_precision": "default"})
        clear_decode_program_cache()
    print("paddle_tpu single-device matmul: OK")

    if len(devices) > 1:
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(devices), axis_names=("x",))
        f = jax.shard_map(lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                          in_specs=P("x"), out_specs=P())
        out = f(jnp.ones((len(devices), 8)))
        assert float(out.ravel()[0]) == float(len(devices))
        print(f"paddle_tpu {len(devices)}-device collective (psum): OK")
    print("paddle_tpu is installed successfully!")
