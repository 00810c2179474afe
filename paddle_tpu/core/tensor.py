"""Tensor: the user-facing array type.

Reference: phi::DenseTensor + the pybind eager Tensor
(paddle/phi/core/dense_tensor.h, paddle/fluid/pybind/eager_method.cc).
Here a Tensor is a thin mutable handle around an immutable ``jax.Array``
(or a jax tracer inside jit), carrying paddle-style metadata: ``name``,
``stop_gradient``, ``persistable``, ``grad``. All math dispatches through
``apply_op`` so the eager tape (core/autograd.py) can record.

Most operator methods are monkey-bound by ``paddle_tpu.ops`` at import time,
mirroring how the reference patches generated methods onto the pybind Tensor.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import autograd
from .dtype import DType, to_jax_dtype, to_paddle_dtype
from .place import CPUPlace, Place, TPUPlace, current_place, get_default_dtype

_name_counter = threading.local()


def _auto_name(prefix="generated_tensor"):
    n = getattr(_name_counter, "n", 0)
    _name_counter.n = n + 1
    return f"{prefix}_{n}"


# jit/sot capture hooks: a creation sequence number distinguishes tensors
# born during a capture from pre-existing free variables, and the force
# listener observes every tensor-data -> Python crossing (guard points)
_seq = 0
_force_listener = None   # set by jit/sot during a capture run
_sot_recorder = None     # set by jit/sot during a capture run


def _next_seq() -> int:
    global _seq
    _seq += 1
    return _seq


def _notify_force(t, kind: str, value):
    if _force_listener is not None:
        _force_listener(t, kind, value)
    return value


class Tensor:
    __slots__ = (
        "_value",
        "stop_gradient",
        "persistable",
        "name",
        "grad",
        "_grad_node",
        "_out_index",
        "_retain_grads",
        "_backward_hooks",
        "_seq",             # creation sequence number (jit/sot capture)
        "_static_var_id",   # static Program variable id (static/program.py)
        "dist_attr",        # sharding annotation (auto_parallel): PartitionSpec
        "process_mesh",     # auto_parallel ProcessMesh (shard_tensor output)
        "placements",       # auto_parallel placements list (shard_tensor)
        "__weakref__",
    )

    def __init__(
        self,
        value,
        dtype=None,
        place: Optional[Place] = None,
        stop_gradient: bool = True,
        name: Optional[str] = None,
        persistable: bool = False,
    ):
        if isinstance(value, Tensor):
            value = value._value
        if isinstance(value, jax.ShapeDtypeStruct):
            # meta tensor (LazyGuard): shape+dtype metadata, no storage
            pass
        elif not isinstance(value, jax.Array) and not isinstance(value, jax.core.Tracer):
            value = jnp.asarray(value, dtype=to_jax_dtype(dtype))
        elif dtype is not None and jnp.result_type(value) != to_jax_dtype(dtype):
            value = value.astype(to_jax_dtype(dtype))
        if place is not None and isinstance(value, jax.Array):
            value = jax.device_put(value, place.jax_device())
        self._value = value
        self.stop_gradient = stop_gradient
        self.persistable = persistable
        self.name = name or _auto_name()
        self.grad = None
        self._grad_node = None
        self._out_index = 0
        self._retain_grads = False
        self._backward_hooks = []
        self._seq = _next_seq()

    # ------------------------------------------------------------------ meta
    @property
    def value(self):
        return self._value

    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self) -> int:
        return self._value.ndim

    @property
    def size(self) -> int:
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self) -> DType:
        return to_paddle_dtype(jnp.result_type(self._value))

    @property
    def place(self) -> Place:
        """Where the storage actually is (the lowest device id of a
        sharded array); a tracer or meta tensor has no storage and
        answers with the current place."""
        v = self._value
        if isinstance(v, jax.core.Tracer) or not isinstance(v, jax.Array):
            return current_place()
        dev = min(v.devices(), key=lambda d: d.id)
        return TPUPlace(dev.id) if dev.platform == "tpu" else CPUPlace(0)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def numel(self) -> int:
        return self.size

    def element_size(self) -> int:
        return self.dtype.itemsize

    # -------------------------------------------------------------- convert
    def numpy(self) -> np.ndarray:
        return _notify_force(self, "array", np.asarray(self._value))

    def item(self):
        v = self._value.item() if hasattr(self._value, "item") else np.asarray(self._value).item()
        return _notify_force(self, "item", v)

    def tolist(self):
        return _notify_force(self, "array", np.asarray(self._value).tolist())

    def __array__(self, dtype=None):
        arr = np.asarray(self._value)
        return _notify_force(
            self, "array", arr.astype(dtype) if dtype is not None else arr)

    def astype(self, dtype) -> "Tensor":
        return apply_op("cast", lambda x: x.astype(to_jax_dtype(dtype)), self)

    cast = astype

    def cpu(self) -> "Tensor":
        return Tensor(jax.device_put(self._value, jax.devices("cpu")[0]),
                      stop_gradient=self.stop_gradient, name=self.name)

    def to(self, *args, **kwargs) -> "Tensor":
        dtype = kwargs.get("dtype")
        device = kwargs.get("device")
        for a in args:
            if isinstance(a, str) and a.split(":")[0] in ("cpu", "tpu", "gpu", "cuda"):
                device = a
            elif isinstance(a, (str, DType)):
                dtype = a
            elif isinstance(a, Place):
                device = f"{a.device_type}:{a.get_device_id()}"
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            from .place import set_device  # noqa: F401  (validates names)
            kind = device.split(":")[0]
            plat = "cpu" if kind == "cpu" else None
            devs = jax.devices(plat) if plat else jax.devices()
            idx = int(device.split(":")[1]) if ":" in device else 0
            out = Tensor(
                jax.device_put(out._value, devs[min(idx, len(devs) - 1)]),
                stop_gradient=out.stop_gradient,
                name=out.name,
            )
        return out

    # ------------------------------------------------------------- autograd
    def backward(self, grad_tensor=None, retain_graph: bool = False) -> None:
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def retain_grads(self) -> None:
        self._retain_grads = True

    def clear_grad(self) -> None:
        self.grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        t = Tensor(self._value, stop_gradient=True, name=self.name + ".detach")
        if _sot_recorder is not None:
            _sot_recorder.on_alias(self, t, stopped=True)
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        if _sot_recorder is not None:
            _sot_recorder.on_alias(self, self, stopped=True)
        return self

    def _accumulate_grad(self, gval) -> None:
        if gval.dtype != jnp.result_type(self._value):
            gval = gval.astype(jnp.result_type(self._value))
        for hook in self._backward_hooks:
            out = hook(Tensor(gval, stop_gradient=True))
            if out is not None:
                gval = out._value if isinstance(out, Tensor) else out
        if self.grad is None:
            self.grad = Tensor(gval, stop_gradient=True, name=self.name + "@GRAD")
        else:
            self.grad = Tensor(self.grad._value + gval, stop_gradient=True,
                               name=self.name + "@GRAD")

    def register_hook(self, hook: Callable) -> Callable:
        """Hook called with the gradient when it is accumulated into this
        tensor (paddle's Tensor.register_hook)."""
        self._backward_hooks.append(hook)

        def remove():
            if hook in self._backward_hooks:
                self._backward_hooks.remove(hook)

        remove.remove = remove
        return remove

    # ---------------------------------------------------------- in-place ops
    # every in-place path funnels through set_value/_inplace/__setitem__;
    # an active jit/sot capture cannot represent mutation, so notify it
    def set_value(self, value) -> None:
        if _sot_recorder is not None:
            _sot_recorder.on_mutation(self)
        if isinstance(value, Tensor):
            value = value._value
        self._value = jnp.asarray(value, dtype=jnp.result_type(self._value))

    def copy_(self, other: "Tensor") -> "Tensor":
        self.set_value(other)
        return self

    def _inplace(self, new_value) -> "Tensor":
        if _sot_recorder is not None:
            _sot_recorder.on_mutation(self)
        self._value = new_value
        return self

    def add_(self, y) -> "Tensor":
        return self._inplace(self._value + _val(y))

    def subtract_(self, y) -> "Tensor":
        return self._inplace(self._value - _val(y))

    def multiply_(self, y) -> "Tensor":
        return self._inplace(self._value * _val(y))

    def scale_(self, scale: float, bias: float = 0.0) -> "Tensor":
        return self._inplace(self._value * scale + bias)

    def zero_(self) -> "Tensor":
        return self._inplace(jnp.zeros_like(self._value))

    def fill_(self, v) -> "Tensor":
        return self._inplace(jnp.full_like(self._value, v))

    def clip_(self, min=None, max=None) -> "Tensor":
        return self._inplace(jnp.clip(self._value, min, max))

    # ------------------------------------------------------------- indexing
    def __getitem__(self, idx) -> "Tensor":
        idx = _val_index(idx)
        return apply_op("getitem", lambda x: x[idx], self)

    def __setitem__(self, idx, v) -> None:
        if _sot_recorder is not None:
            _sot_recorder.on_mutation(self)
        idx = _val_index(idx)
        self._value = self._value.at[idx].set(_val(v))

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -------------------------------------------------------------- display
    def __repr__(self) -> str:
        sg = self.stop_gradient
        if isinstance(self._value, jax.core.Tracer):
            return f"Tensor(shape={self.shape}, dtype={self.dtype.name}, traced)"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"place={self.place}, stop_gradient={sg},\n{np.asarray(self._value)})"
        )

    def __bool__(self) -> bool:
        return _notify_force(self, "bool", bool(np.asarray(self._value)))

    def __int__(self) -> int:
        return _notify_force(self, "int", int(np.asarray(self._value)))

    def __float__(self) -> float:
        return _notify_force(self, "float", float(np.asarray(self._value)))

    def __index__(self) -> int:
        # lets a scalar int Tensor drive range()/slicing; under tracing
        # jax raises its concretization error, which to_static's guard
        # turns into guidance (instead of range()'s bare TypeError)
        return _notify_force(self, "int", self._value.__index__())

    def __hash__(self):
        return id(self)

    # Arithmetic dunders are bound in paddle_tpu/ops/__init__.py.


# ----------------------------------------------------- sot mutation watch
# During a jit/sot capture, EVERY reassignment of an existing tensor's
# ``_value`` (in-place ops spread across the op modules, optimizer steps,
# BatchNorm stat updates, functional_call swaps of nested jits) is a
# mutation the pure replay tape cannot represent. Rather than patching
# every site, the capture temporarily replaces the ``_value`` slot
# descriptor with a watching property — zero overhead outside capture,
# complete coverage during it. Initial assignment (slot still unset, i.e.
# tensor construction) stays silent.
_VALUE_MEMBER = Tensor.__dict__["_value"]


def _watched_get(self):
    return _VALUE_MEMBER.__get__(self, Tensor)


def _watched_set(self, v):
    try:
        _VALUE_MEMBER.__get__(self, Tensor)
        existed = True
    except AttributeError:
        existed = False
    if existed and _sot_recorder is not None:
        _sot_recorder.on_mutation(self)
    _VALUE_MEMBER.__set__(self, v)


_WATCH_PROPERTY = property(_watched_get, _watched_set)


def _install_mutation_watch() -> None:
    Tensor._value = _WATCH_PROPERTY


def _remove_mutation_watch() -> None:
    Tensor._value = _VALUE_MEMBER


class Parameter(Tensor):
    """Trainable tensor (paddle's EagerParamBase): stop_gradient=False,
    persistable, optionally ``trainable`` togglable."""

    __slots__ = ("optimize_attr", "is_distributed", "split_axis",
                 "sequence_parallel", "_lazy_init")

    def __init__(self, value, dtype=None, name=None, trainable: bool = True):
        super().__init__(
            value,
            dtype=dtype,
            stop_gradient=not trainable,
            name=name or _auto_name("param"),
            persistable=True,
        )
        self.optimize_attr = {"learning_rate": 1.0}
        self.is_distributed = False
        self.split_axis = None  # set by TP layers: axis this param is sharded on

    @property
    def trainable(self) -> bool:
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v: bool) -> None:
        self.stop_gradient = not v


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def _val_index(idx):
    if isinstance(idx, tuple):
        return tuple(_val(i) for i in idx)
    return _val(idx)


# set by static/program.py while a program_guard is active: every op
# through this dispatch point is then also recorded into the Program
_static_recorder = None


def apply_op(name: str, fn: Callable, *args, **kwargs) -> Any:
    """Single dispatch point for every eager op.

    ``args`` may mix Tensors and raw values; ``kwargs`` are static (shapes,
    axes). Executes via jax, records a GradNode when grads are required
    (see core/autograd.py), and wraps outputs as Tensors. Under an active
    ``paddle.static.program_guard`` the op is additionally recorded for
    Executor replay.
    """
    from .. import flags

    tensor_args = [a if isinstance(a, Tensor) else None for a in args]
    values = tuple(a._value if isinstance(a, Tensor) else a for a in args)
    values = _maybe_amp_cast(name, values)
    out, node = autograd.record_op(name, fn, tensor_args, values, kwargs)

    # deliberate per-op registry read: check_nan_inf is a runtime-
    # toggleable debug switch (set_flags mid-run must take effect on the
    # next eager op) and the check itself skips tracers, so no value is
    # ever baked into a compiled program  # tracecheck: disable=TRC001
    if flags.get_flag("check_nan_inf"):
        _check_nan_inf(name, out)

    multi = isinstance(out, (tuple, list))
    outs = list(out) if multi else [out]
    wrapped = []
    for i, o in enumerate(outs):
        # tuples pass through un-wrapped even when they expose a .dtype
        # (QuantizedPages rides ops as an array-of-arrays NamedTuple)
        if o is None or isinstance(o, tuple) or not hasattr(o, "dtype"):
            wrapped.append(o)
            continue
        t = Tensor(o, stop_gradient=(node is None), name=f"{name}_out")
        if node is not None:
            t._grad_node = node
            t._out_index = i
        wrapped.append(t)
    if _static_recorder is not None:
        _static_recorder.record(name, fn, args, kwargs, wrapped)
    if _sot_recorder is not None:
        _sot_recorder.record(name, fn, args, kwargs, wrapped, multi)
    return tuple(wrapped) if multi else wrapped[0]


def _maybe_amp_cast(name: str, values):
    """AMP casting at the dispatch point — the reference does this in C++
    eager dispatch (paddle/fluid/eager/amp_utils.h)."""
    from ..amp.auto_cast import amp_state, black_list, white_list

    st = amp_state()
    if st is None:
        return values
    from .dtype import to_jax_dtype

    target = to_jax_dtype(st.dtype)
    if st.level == "O2":
        do_cast = name not in black_list()
    else:
        do_cast = name in white_list()
    if not do_cast:
        # black-listed ops promote low-precision inputs to fp32
        if name in black_list():
            return tuple(
                v.astype(jnp.float32)
                if hasattr(v, "dtype") and jnp.result_type(v) in (jnp.bfloat16, jnp.float16)
                else v
                for v in values)
        return values
    return tuple(
        v.astype(target)
        if hasattr(v, "dtype") and jnp.result_type(v) == jnp.float32
        else v
        for v in values)


def _check_nan_inf(op_name: str, out) -> None:
    """FLAGS_check_nan_inf analogue (reference: nan_inf_utils_detail)."""
    import numpy as _np

    from ..amp.debugging import record_op_stats
    record_op_stats(op_name, out)  # no-op unless a dump dir is configured

    outs = out if isinstance(out, (tuple, list)) else (out,)
    for o in outs:
        if o is None or not hasattr(o, "dtype"):
            continue
        if not jnp.issubdtype(jnp.result_type(o), jnp.floating):
            continue
        if isinstance(o, jax.core.Tracer):
            continue
        arr = _np.asarray(o)
        if not _np.isfinite(arr).all():
            from .. import flags as _flags
            msg = f"Operator {op_name!r} output contains NaN or Inf."
            # error-path only, tracers already filtered above
            # tracecheck: disable=TRC001
            if _flags.get_flag("check_nan_inf_level") == 0:
                raise FloatingPointError(msg)
            print("WARNING:", msg)
