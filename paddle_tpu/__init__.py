"""paddle_tpu — a TPU-native deep-learning framework with the API surface of
PaddlePaddle, rebuilt on jax/XLA/Pallas.

The compute path is jax (XLA + Pallas kernels); parallelism is
jax.sharding over ICI/DCN meshes; the user API mirrors ``paddle.*`` so code
written against the reference ports with an import swap.
"""

__version__ = "0.1.0"

from . import flags  # noqa: F401  (flag registry first: ops read flags)
from .flags import get_flags, set_flags  # noqa: F401

from .core.dtype import (  # noqa: F401
    bfloat16, bool_ as bool8, complex64, complex128, DType,
    float16, float32, float64, float8_e4m3fn, float8_e5m2,
    int8, int16, int32, int64, uint8,
)
from .core.dtype import bool_, finfo, iinfo  # noqa: F401


def __getattr__(name):
    # paddle.bool without shadowing the builtin inside this module's own
    # function bodies (PEP 562)
    if name == "bool":
        return bool_
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
from .core.place import (  # noqa: F401
    CPUPlace, CUDAPlace, Place, TPUPlace, XPUPlace, device_count,
    get_default_dtype, get_device, is_compiled_with_cuda,
    is_compiled_with_tpu, is_compiled_with_xpu, set_default_dtype, set_device,
)
from .core.tensor import Parameter, Tensor  # noqa: F401
from .nn.param_attr import ParamAttr  # noqa: F401
from .core.autograd import enable_grad, no_grad, set_grad_enabled  # noqa: F401
from .core import autograd as _autograd_mod

is_grad_enabled = _autograd_mod.is_grad_enabled

# the op surface: paddle.add / paddle.reshape / ... (also binds Tensor methods)
from .ops import *  # noqa: F401,F403
from . import ops  # noqa: F401

from .framework.random import get_cuda_rng_state, get_rng_state, seed, set_cuda_rng_state, set_rng_state  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import vision  # noqa: F401
from . import distribution  # noqa: F401
from . import inference  # noqa: F401
from . import sparse  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import device  # noqa: F401
from . import regularizer  # noqa: F401
from . import version  # noqa: F401
from . import hub  # noqa: F401
from . import geometric  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import onnx  # noqa: F401
from .hapi import Model  # noqa: F401
from .hapi import callbacks as callbacks  # noqa: F401


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """reference: paddle.set_printoptions — numpy-backed display options
    (tensors print via numpy here)."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)
from . import autograd  # noqa: F401
from .framework.io import load, save  # noqa: F401
from .framework.lazy import LazyGuard  # noqa: F401
from . import distributed  # noqa: F401
from . import hapi  # noqa: F401
from .hapi.summary import flops, summary  # noqa: F401
import importlib as _importlib
# NB: `from . import linalg` would return the ops.linalg SUBMODULE already
# bound on the package by `from .ops import *`; force the rich module
linalg = _importlib.import_module(".linalg", __name__)
from . import models  # noqa: F401
from . import incubate  # noqa: F401
from . import profiler  # noqa: F401
from . import observability  # noqa: F401  (host-side metrics + spans)
from .utils.install_check import run_check  # noqa: F401
from . import quantization  # noqa: F401


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False):
    """``paddle.grad``: returns grads of outputs w.r.t. inputs without
    touching .grad on other leaves (implemented via a scoped backward)."""
    outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    saved = [(p, p.grad, p._retain_grads) for p in ins]
    for p in ins:
        p.grad = None
        p._retain_grads = True
    from .core.autograd import backward as _backward
    _backward(list(outs), grad_outputs, retain_graph=bool(retain_graph))
    grads = []
    for p, old_grad, old_retain in saved:
        g = p.grad
        if g is None and not allow_unused:
            raise RuntimeError(f"input {p.name} is unused in the graph "
                               "(pass allow_unused=True to permit)")
        grads.append(g)
        p.grad = old_grad
        p._retain_grads = old_retain
    return grads


class dtype(DType):  # alias so paddle.dtype comparisons work
    pass


def rank(x) -> int:
    return x.ndim


_static_mode = False


def in_dynamic_mode() -> bool:
    return not _static_mode


def disable_static(place=None):
    global _static_mode
    _static_mode = False
    return None


def enable_static():
    """Static-graph compatibility mode: build under
    ``paddle.static.program_guard`` (ops are recorded by execution) and
    run with ``paddle.static.Executor``. The mode flag only flips
    ``in_dynamic_mode()`` — recording is scoped by program_guard."""
    global _static_mode
    _static_mode = True
    return None


def disable_signal_handler():
    return None


def device_guard(device=None):
    import contextlib
    return contextlib.nullcontext()


def synchronize():
    import jax
    (jax.device_put(0) + 0).block_until_ready()


# paddle.device is the real submodule (imported above); the former class
# facade is gone — everything it offered lives in device/__init__.py


def batch(reader, batch_size, drop_last=False):
    """reference: paddle.batch (deprecated reader decorator)."""
    def gen():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return gen
