"""reference: python/paddle/device/ — device management. The TPU rebuild
maps device queries onto the jax backend; CUDA-specific queries answer
honestly (False / none present)."""

from __future__ import annotations

from ..core.place import (  # noqa: F401
    device_count, get_device, is_compiled_with_cuda, set_device,
)
from . import cuda  # noqa: F401


def cuda_device_count() -> int:
    return 0


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if not d.startswith(("cpu", "gpu"))]


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = None) -> bool:
    # the TPU backend IS the custom device of this build
    return device_type in (None, "tpu")


def synchronize(device=None):
    import jax
    (jax.device_put(0) + 0).block_until_ready()
