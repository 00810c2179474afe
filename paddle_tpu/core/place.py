"""Device placement facade.

Reference: phi::Place / DeviceContext (paddle/phi/common/place.h,
paddle/phi/core/device_context.h). On TPU, PJRT owns streams and memory, so
a Place is a thin handle to a ``jax.Device`` and the DeviceContext reduces
to device selection + default-dtype state. ``set_device('tpu')`` /
``get_device()`` mirror ``paddle.set_device`` / ``paddle.get_device``.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

from .enforce import (InvalidArgumentError, OutOfRangeError,
                      UnavailableError)


class Place:
    """Base place: a handle to a jax device."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self) -> str:
        return f"Place({self.device_type}:{self._device_id})"

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self) -> int:
        return hash((self.device_type, self._device_id))

    def jax_device(self) -> jax.Device:
        """The device this place names. A platform JAX does not have, or
        an id past its device count, raises: a tensor asked onto tpu:3
        never lands silently on the CPU or on tpu:0."""
        try:
            devs = jax.devices(self.device_type)
        except RuntimeError as exc:
            raise UnavailableError(
                f"{self!r}: no {self.device_type!r} backend here "
                f"(default backend is {jax.default_backend()!r})") from exc
        if not 0 <= self._device_id < len(devs):
            raise OutOfRangeError(
                f"{self!r}: only {len(devs)} {self.device_type} device(s)")
        return devs[self._device_id]

    def is_cpu_place(self) -> bool:
        return self.device_type == "cpu"

    def is_tpu_place(self) -> bool:
        return self.device_type == "tpu"

    # GPU never exists in this stack; kept for source compatibility.
    def is_gpu_place(self) -> bool:
        return False


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


# Source-compat aliases: code written against the reference's CUDA places
# runs unchanged on the TPU build.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
CustomPlace = TPUPlace


class _DeviceState(threading.local):
    def __init__(self):
        self.place: Optional[Place] = None
        self.default_dtype = "float32"


_state = _DeviceState()


def _default_place() -> Place:
    return TPUPlace(0) if jax.default_backend() == "tpu" else CPUPlace(0)


def set_device(device: str) -> Place:
    """``paddle.set_device`` analogue. Accepts 'cpu', 'tpu', 'tpu:N';
    'gpu'/'xpu' map to tpu for source compatibility."""
    dev = device.lower()
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        idx = int(idx)
    else:
        kind, idx = dev, 0
    if kind in ("tpu", "gpu", "cuda", "xpu", "npu", "custom_device"):
        place: Place = TPUPlace(idx)
    elif kind == "cpu":
        place = CPUPlace(idx)
    else:
        raise InvalidArgumentError(f"Unknown device {device!r}")
    _state.place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.get_device_id()}"


def current_place() -> Place:
    if _state.place is None:
        _state.place = _default_place()
    return _state.place


def set_default_dtype(dtype) -> None:
    from .dtype import to_paddle_dtype

    _state.default_dtype = to_paddle_dtype(dtype).name


def get_default_dtype() -> str:
    return _state.default_dtype


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_xpu() -> bool:
    return False


def device_count() -> int:
    return len(jax.devices())
