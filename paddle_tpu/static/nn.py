"""Structured control flow usable under ``to_static`` tracing.

Reference: python/paddle/static/nn/control_flow.py (``cond``,
``while_loop``, ``case``, ``switch_case`` build ConditionalBlock/While
ops into the static Program). Under XLA the same constructs map to
``lax.cond`` / ``lax.while_loop`` / ``lax.switch`` — these are the
supported replacements for data-dependent Python ``if``/``while``, which
cannot be traced (see jit.to_static's semantics table).
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
from jax import lax

from ..core.tensor import Tensor
from ..jit import tree_to_tensors, tree_to_values


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def cond(pred, true_fn: Callable, false_fn: Callable, name=None,
         return_names=None):
    """``paddle.static.nn.cond``: only the taken branch executes at
    runtime; both branches must return the same structure/shapes."""
    out = lax.cond(_val(pred).astype(bool).reshape(()),
                   lambda: tree_to_values(true_fn()),
                   lambda: tree_to_values(false_fn()))
    return tree_to_tensors(out)


def while_loop(cond_fn: Callable, body_fn: Callable, loop_vars: Sequence,
               is_test: bool = False, name=None) -> List:
    """``paddle.static.nn.while_loop``: loop_vars must keep their
    shapes/dtypes across iterations (XLA compiles one body)."""
    init = tuple(tree_to_values(tuple(loop_vars)))

    def c(vals):
        return _val(cond_fn(*tree_to_tensors(vals))).astype(bool).reshape(())

    def b(vals):
        out = body_fn(*tree_to_tensors(vals))
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return tuple(tree_to_values(tuple(out)))

    out = lax.while_loop(c, b, init)
    return list(tree_to_tensors(out))


def case(pred_fn_pairs: Sequence[Tuple], default: Callable = None,
         name=None):
    """``paddle.static.nn.case``: first matching predicate wins (lowered
    as a chain of lax.cond)."""
    if default is None:
        *pred_fn_pairs, last = pred_fn_pairs
        default = last[1] if isinstance(last, (tuple, list)) else last

    def build(pairs):
        if not pairs:
            return tree_to_values(default())
        (p, fn), *rest = pairs
        return lax.cond(_val(p).astype(bool).reshape(()),
                        lambda: tree_to_values(fn()),
                        lambda: build(rest))

    return tree_to_tensors(build(list(pred_fn_pairs)))


def switch_case(branch_index, branch_fns, default: Callable = None,
                name=None):
    """``paddle.static.nn.switch_case`` over ``lax.switch``."""
    if isinstance(branch_fns, dict):
        keys = sorted(branch_fns)
        fns = [branch_fns[k] for k in keys]
        index_map = {k: i for i, k in enumerate(keys)}
        idx = _val(branch_index).reshape(())
        # map sparse indices onto dense switch slots
        import jax.numpy as jnp
        dense = jnp.full((), len(fns), jnp.int32)
        for k, i in index_map.items():
            dense = jnp.where(idx == k, i, dense)
        idx = dense
    else:
        fns = list(branch_fns)
        idx = _val(branch_index).astype("int32").reshape(())
    if default is not None:
        fns = fns + [default]
        # any out-of-range index (negative included) runs default —
        # reference switch_case semantics
        import jax.numpy as jnp
        idx = jnp.where((idx < 0) | (idx >= len(fns) - 1),
                        len(fns) - 1, idx)
    idx = lax.clamp(0, idx, len(fns) - 1)
    out = lax.switch(idx, [lambda f=f: tree_to_values(f()) for f in fns])
    return tree_to_tensors(out)


# --------------------------------------------------- layer-builder helpers
# reference: python/paddle/static/nn/common.py — the static-mode layer
# builders. Under the trace-by-execution Program each call CREATES the
# layer once at build time (its Parameters persist and are recorded by
# reference) and applies it, exactly the reference's
# parameter-in-global-block behavior.

def fc(x, size, num_flatten_dims=1, weight_attr=None, bias_attr=None,
       activation=None, name=None):
    from ..nn.layers.common import Linear
    from ..ops import manipulation
    from .. import nn as _nn
    in_features = 1
    for s in x.shape[num_flatten_dims:]:
        in_features *= int(s)
    if tuple(x.shape[num_flatten_dims:]) != (in_features,):
        x = manipulation.flatten(x, start_axis=num_flatten_dims)
    layer = Linear(in_features, size, weight_attr=weight_attr,
                   bias_attr=bias_attr)
    out = layer(x)
    if activation:
        out = getattr(_nn.functional, activation)(out)
    return out


def embedding(input, size, is_sparse=False, padding_idx=None,
              param_attr=None, dtype="float32"):
    from ..nn.layers.common import Embedding
    layer = Embedding(size[0], size[1], padding_idx=padding_idx,
                      weight_attr=param_attr)
    return layer(input)


def batch_norm(input, momentum=0.9, epsilon=1e-5, param_attr=None,
               bias_attr=None, data_layout="NCHW", is_test=False,
               name=None, **kwargs):
    from ..nn.layers.extra import BatchNorm
    c_axis = 1 if data_layout == "NCHW" else -1
    num = int(input._value.shape[c_axis])
    layer = BatchNorm(num, momentum=momentum, epsilon=epsilon)
    if is_test:
        layer.eval()
    return layer(input)


def conv2d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           data_format="NCHW", name=None):
    from ..nn.layers.extra import Conv2D
    in_ch = int(input._value.shape[1 if data_format == "NCHW" else -1])
    layer = Conv2D(in_ch, num_filters, filter_size, stride=stride,
                   padding=padding, dilation=dilation, groups=groups,
                   weight_attr=param_attr, bias_attr=bias_attr)
    return layer(input)


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           data_format="NCDHW", name=None):
    from ..nn.layers.extra import Conv3D
    in_ch = int(input._value.shape[1 if data_format == "NCDHW" else -1])
    layer = Conv3D(in_ch, num_filters, filter_size, stride=stride,
                   padding=padding, dilation=dilation, groups=groups)
    return layer(input)


def layer_norm(input, begin_norm_axis=1, epsilon=1e-5, param_attr=None,
               bias_attr=None, name=None):
    from ..nn.layers.common import LayerNorm
    shape = tuple(int(s) for s in input._value.shape[begin_norm_axis:])
    layer = LayerNorm(shape, epsilon=epsilon)
    return layer(input)


def group_norm(input, groups, epsilon=1e-5, param_attr=None,
               bias_attr=None, data_layout="NCHW", name=None):
    from ..nn.layers.extra import GroupNorm
    ch = int(input._value.shape[1 if data_layout == "NCHW" else -1])
    layer = GroupNorm(groups, ch, epsilon=epsilon)
    return layer(input)


def prelu(x, mode="all", param_attr=None, data_format="NCHW", name=None):
    from ..nn.layers.extra import PReLU
    if mode == "all":
        n = 1
    elif mode == "channel":
        n = int(x._value.shape[1 if data_format == "NCHW" else -1])
    else:
        n = int(x._value.shape[-1])
    layer = PReLU(num_parameters=n)
    return layer(x)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    from ..nn import functional as F
    return F.spectral_norm(weight, dim=dim, power_iters=power_iters,
                           eps=eps) if hasattr(F, "spectral_norm") else \
        _spectral_norm_value(weight, dim, power_iters, eps)


def _spectral_norm_value(w, dim, power_iters, eps):
    import jax.numpy as jnp
    from ..core.tensor import apply_op

    def fn(a):
        mat = jnp.moveaxis(a, dim, 0).reshape(a.shape[dim], -1)
        u = jnp.ones((mat.shape[0],), a.dtype)
        v = None
        for _ in range(max(1, power_iters)):
            v = mat.T @ u
            v = v / (jnp.linalg.norm(v) + eps)
            u = mat @ v
            u = u / (jnp.linalg.norm(u) + eps)
        sigma = u @ mat @ v
        return a / sigma
    return apply_op("spectral_norm", fn, weight)


def sequence_expand(x, y, ref_level=-1, name=None):
    """reference: paddle.static.nn.sequence_expand. LoD sequences do not
    exist in this build (static shapes; pack with segment ids instead —
    see flash attention varlen)."""
    raise NotImplementedError(
        "LoD sequence ops are a non-goal on TPU (static shapes); pack "
        "ragged batches with segment ids instead")


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None, name=None,
        **kwargs):
    """reference: paddle.static.nn.nce — noise-contrastive estimation.
    TPU-native replacement is sampled/full softmax; raising with that
    guidance (the reference op's CPU-only sampler has no XLA analogue)."""
    raise NotImplementedError(
        "nce: use full softmax_with_cross_entropy (cheap on the MXU) or "
        "class_center_sample + margin_cross_entropy for large vocab")


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """reference: paddle.static.nn.py_func — host-side python op via
    jax.pure_callback."""
    import jax
    import jax.numpy as jnp
    from ..core.tensor import Tensor, apply_op
    xs = x if isinstance(x, (list, tuple)) else [x]
    out_t = out if isinstance(out, (list, tuple)) else [out]
    sds = [jax.ShapeDtypeStruct(tuple(o._value.shape), o._value.dtype)
           for o in out_t]

    def fn(*vals):
        res = jax.pure_callback(
            lambda *a: func(*[np_asarray(v) for v in a]),
            sds[0] if len(sds) == 1 else sds, *vals)
        return res

    def np_asarray(v):
        import numpy as np
        return np.asarray(v)

    return apply_op("py_func", fn, *xs)
