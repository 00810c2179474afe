"""nn functional ops (reference: python/paddle/nn/functional/).

All implemented directly over jax/XLA; the fused hot ops (flash attention,
fused rms_norm, …) live in paddle_tpu/incubate/nn/functional.py as Pallas
kernels with these as reference fallbacks.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.dtype import to_jax_dtype
from ..core.tensor import Tensor, apply_op, _val
from ..framework.random import next_key

# ------------------------------------------------------------- activations


def _unary(op_name, jfn):
    def op(x, name=None):
        return apply_op(op_name, jfn, x)

    op.__name__ = op_name
    return op


relu = _unary("relu", jax.nn.relu)
relu6 = _unary("relu6", jax.nn.relu6)
sigmoid = _unary("sigmoid", jax.nn.sigmoid)
tanh = _unary("tanh", jnp.tanh)
silu = _unary("silu", jax.nn.silu)
swish = silu
mish = _unary("mish", lambda x: x * jnp.tanh(jax.nn.softplus(x)))
hardswish = _unary("hardswish", jax.nn.hard_swish)
hardsigmoid = _unary("hardsigmoid", lambda x: jnp.clip(x / 6.0 + 0.5, 0.0, 1.0))
tanhshrink = _unary("tanhshrink", lambda x: x - jnp.tanh(x))
softsign = _unary("softsign", jax.nn.soft_sign)
selu_ = _unary("selu", jax.nn.selu)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply_op("selu", lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)), x)


def gelu(x, approximate=False, name=None):
    return apply_op("gelu", lambda a: jax.nn.gelu(a, approximate=approximate), x)


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply_op("leaky_relu", lambda a: jax.nn.leaky_relu(a, negative_slope), x)


def elu(x, alpha=1.0, name=None):
    return apply_op("elu", lambda a: jax.nn.elu(a, alpha), x)


def celu(x, alpha=1.0, name=None):
    return apply_op("celu", lambda a: jax.nn.celu(a, alpha), x)


def prelu(x, weight, data_format="NCHW", name=None):
    def fn(a, w):
        if w.size == 1:
            return jnp.where(a > 0, a, w.reshape(()) * a)
        shape = [1] * a.ndim
        ch_axis = 1 if data_format.startswith("NC") else a.ndim - 1
        shape[ch_axis] = w.size
        return jnp.where(a > 0, a, w.reshape(shape) * a)
    return apply_op("prelu", fn, x, weight)


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply_op("hardtanh", lambda a: jnp.clip(a, min, max), x)


def hardshrink(x, threshold=0.5, name=None):
    return apply_op("hardshrink", lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0), x)


def softshrink(x, threshold=0.5, name=None):
    return apply_op(
        "softshrink",
        lambda a: jnp.where(a > threshold, a - threshold,
                            jnp.where(a < -threshold, a + threshold, 0.0)), x)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply_op(
        "softplus",
        lambda a: jnp.where(beta * a > threshold, a, jax.nn.softplus(beta * a) / beta), x)


def log_sigmoid(x, name=None):
    return apply_op("log_sigmoid", jax.nn.log_sigmoid, x)


def softmax(x, axis=-1, dtype=None, name=None):
    jd = to_jax_dtype(dtype)
    def fn(a):
        if jd is not None:
            a = a.astype(jd)
        return jax.nn.softmax(a, axis=axis)
    return apply_op("softmax", fn, x)


def log_softmax(x, axis=-1, dtype=None, name=None):
    jd = to_jax_dtype(dtype)
    def fn(a):
        if jd is not None:
            a = a.astype(jd)
        return jax.nn.log_softmax(a, axis=axis)
    return apply_op("log_softmax", fn, x)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    g = jax.random.gumbel(next_key(), tuple(_val(x).shape), jnp.result_type(_val(x)))
    def fn(a):
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y).at[
                tuple(jnp.indices(y.shape)[i] if i != (axis % y.ndim) else idx
                      for i in range(y.ndim))].set(1.0)
            y = jax.lax.stop_gradient(y_hard - y) + y
        return y
    return apply_op("gumbel_softmax", fn, x)


def glu(x, axis=-1, name=None):
    def fn(a):
        a1, a2 = jnp.split(a, 2, axis=axis)
        return a1 * jax.nn.sigmoid(a2)
    return apply_op("glu", fn, x)


def swiglu(x, y=None, name=None):
    """SwiGLU: silu(x) * y (fused gate for Llama-style FFN).
    Reference analogue: paddle.incubate.nn.functional.swiglu."""
    if y is None:
        def fn(a):
            a1, a2 = jnp.split(a, 2, axis=-1)
            return jax.nn.silu(a1) * a2
        return apply_op("swiglu", fn, x)
    return apply_op("swiglu", lambda a, b: jax.nn.silu(a) * b, x, y)


# ------------------------------------------------------------------ linear
def linear(x, weight, bias=None, name=None):
    """y = x @ W (+ b). Weight layout [in, out] (paddle convention —
    reference: python/paddle/nn/functional/common.py::linear)."""
    if bias is None:
        return apply_op("linear", lambda a, w: a @ w, x, weight)
    return apply_op("linear", lambda a, w, b: a @ w + b, x, weight, bias)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    from .. import flags

    idx = _val(x)
    # one snapshot at the trace boundary (tracecheck TRC001): a bare
    # get_flag here would bake per-trace and bypass program-cache keys
    snap = flags.snapshot(("embedding_matmul_grad",))
    mode = snap.embedding_matmul_grad
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"FLAGS_embedding_matmul_grad must be 'auto', 'on' or 'off', "
            f"got {mode!r}")
    matmul_grad = (mode == "on"
                   or (mode == "auto" and flags.is_tpu_backend()))
    if padding_idx is not None and padding_idx < 0:
        # paddle semantics: negative padding_idx counts from the end
        padding_idx = int(weight.shape[0]) + int(padding_idx)

    def take(w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    if not matmul_grad:
        return apply_op("embedding", take, weight)

    # custom vjp: d_w as a one-hot matmul on the MXU. jnp.take's native
    # vjp is a scatter-add, which XLA lowers to a serialized while loop
    # on TPU — PROFILE_r05 showed those loops (carrying the whole
    # bf16[50304,1024] table) among the top ops of the 345M step. The
    # one-hot contraction is the same math (sum of grads per token id),
    # runs as one matmul, and accumulates in f32 for free on the MXU.
    @jax.custom_vjp
    def lookup(w):
        return take(w)

    def fwd(w):
        return take(w), w.shape[0]

    def bwd(vocab, g):
        flat_idx = idx.reshape(-1)
        flat_g = g.reshape(-1, g.shape[-1])
        if padding_idx is not None:
            keep = (flat_idx != padding_idx)[:, None]
            flat_g = jnp.where(keep, flat_g, 0.0)
        oh = jax.nn.one_hot(flat_idx, vocab, dtype=flat_g.dtype)
        d_w = jnp.matmul(oh.T, flat_g,
                         preferred_element_type=jnp.float32)
        return (d_w.astype(g.dtype),)

    lookup.defvjp(fwd, bwd)
    return apply_op("embedding", lookup, weight)


def one_hot(x, num_classes, name=None):
    return Tensor(jax.nn.one_hot(_val(x), num_classes))


def bilinear(x1, x2, weight, bias=None, name=None):
    def fn(a, b, w, *bi):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bi:
            out = out + bi[0]
        return out
    args = (x1, x2, weight) + ((bias,) if bias is not None else ())
    return apply_op("bilinear", fn, *args)


# -------------------------------------------------------------- normalization
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(tuple(normalized_shape))

    def fn(a, *wb):
        # stats in fp32, output (and affine params) in the input dtype:
        # keeps a bf16 residual stream bf16 under amp (see amp/auto_cast.py
        # BLACK_LIST note) without giving up fp32 mean/var numerics
        wb = tuple(w.astype(a.dtype) for w in wb)
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        mean = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(a.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]; i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op("layer_norm", fn, *args)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm (reference: paddle/phi/kernels/gpu/rms_norm_kernel.cu →
    here a single XLA fusion; Pallas variant in incubate)."""
    def fn(a, *w):
        h = a.astype(jnp.float32)
        var = jnp.mean(h * h, axis=-1, keepdims=True)
        out = (h * jax.lax.rsqrt(var + epsilon)).astype(a.dtype)
        if w:
            out = out * w[0].astype(a.dtype)
        return out
    args = (x,) + ((weight,) if weight is not None else ())
    return apply_op("rms_norm", fn, *args)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None):
    ch_axis = 1 if data_format.startswith("NC") else -1

    def stats_shape(a):
        s = [1] * a.ndim
        s[ch_axis] = a.shape[ch_axis]
        return s

    rm, rv = _val(running_mean), _val(running_var)
    if training and not use_global_stats:
        v = _val(x)
        axes = tuple(i for i in range(v.ndim) if i != (ch_axis % v.ndim))
        batch_mean = jnp.mean(v.astype(jnp.float32), axis=axes)
        batch_var = jnp.var(v.astype(jnp.float32), axis=axes)
        # update running stats in place (paddle semantics)
        running_mean._value = (momentum * rm + (1 - momentum) * batch_mean).astype(rm.dtype)
        running_var._value = (momentum * rv + (1 - momentum) * batch_var).astype(rv.dtype)
        mean_, var_ = batch_mean, batch_var
    else:
        mean_, var_ = rm, rv

    def fn(a, *wb):
        wb = tuple(w.astype(a.dtype) for w in wb)
        shape = stats_shape(a)
        out = (a - mean_.reshape(shape)) * jax.lax.rsqrt(var_.reshape(shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape); i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out.astype(a.dtype)

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op("batch_norm", fn, *args)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW", name=None):
    def fn(a, *wb):
        wb = tuple(w.astype(a.dtype) for w in wb)
        if not data_format.startswith("NC"):
            a_t = jnp.moveaxis(a, -1, 1)
        else:
            a_t = a
        n, c = a_t.shape[0], a_t.shape[1]
        rest = a_t.shape[2:]
        g = a_t.reshape(n, num_groups, c // num_groups, *rest).astype(jnp.float32)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a_t.shape).astype(a.dtype)
        shape = [1, c] + [1] * len(rest)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape); i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        if not data_format.startswith("NC"):
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op("group_norm", fn, *args)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    def fn(a, *wb):
        wb = tuple(w.astype(a.dtype) for w in wb)
        axes = tuple(range(2, a.ndim))
        mean = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = ((a.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + eps)).astype(a.dtype)
        c = a.shape[1]
        shape = [1, c] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape); i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op("instance_norm", fn, *args)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return apply_op(
        "normalize",
        lambda a: a / jnp.maximum(
            jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p), epsilon), x)


# ----------------------------------------------------------------- dropout
def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training and p > 0.0:
            return apply_op("dropout", lambda a: (a * (1.0 - p)).astype(a.dtype), x)
        return x if isinstance(x, Tensor) else Tensor(x)
    v = _val(x)
    shape = list(v.shape)
    if axis is not None:
        axes = axis if isinstance(axis, (list, tuple)) else [axis]
        shape = [s if i in axes else 1 for i, s in enumerate(shape)]
    keep = jax.random.bernoulli(_dropout_key(), 1.0 - p, tuple(shape))

    def fn(a):
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    return apply_op("dropout", fn, x)


def _dropout_key():
    """Dropout keys respect the TP-aware RNGStatesTracker when one is active
    (reference: fleet/meta_parallel/parallel_layers/random.py)."""
    from ..distributed.fleet import random as fleet_random
    tracker = fleet_random.get_rng_state_tracker()
    if tracker.active_state is not None:
        return tracker.next_key()
    return next_key()


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale_ = 1.0507009873554805
    alpha_p = -alpha * scale_
    keep = jax.random.bernoulli(next_key(), 1.0 - p, tuple(_val(x).shape))
    a = (1.0 / math.sqrt((1.0 - p) * (1.0 + p * alpha_p ** 2)))
    b = -a * alpha_p * p
    return apply_op("alpha_dropout",
                    lambda v: (a * jnp.where(keep, v, alpha_p) + b).astype(v.dtype), x)


# ------------------------------------------------------------------- losses
def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0,
                  name=None):
    lbl = _val(label)

    def fn(logits, *w):
        lg = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis) if use_softmax \
            else jnp.log(jnp.clip(logits.astype(jnp.float32), 1e-30, None))
        if soft_label:
            tgt = lbl.astype(jnp.float32)
            loss = -jnp.sum(tgt * lg, axis=axis)
        else:
            l = lbl
            if l.ndim == lg.ndim:
                l = jnp.squeeze(l, axis=axis)
            nclass = lg.shape[axis]
            if label_smoothing > 0.0:
                onehot = jax.nn.one_hot(l, nclass, axis=axis, dtype=jnp.float32)
                tgt = onehot * (1 - label_smoothing) + label_smoothing / nclass
                loss = -jnp.sum(tgt * lg, axis=axis)
            else:
                loss = -jnp.take_along_axis(
                    lg, jnp.expand_dims(l, axis).astype(jnp.int32), axis=axis
                ).squeeze(axis)
            mask = (l != ignore_index)
            loss = jnp.where(mask, loss, 0.0)
            if w:
                loss = loss * jnp.take(w[0], jnp.clip(l, 0, nclass - 1))
            if reduction == "mean":
                denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
                if w:
                    denom = jnp.maximum(jnp.sum(
                        jnp.where(mask, jnp.take(w[0], jnp.clip(l, 0, nclass - 1)), 0.0)), 1e-12)
                return jnp.sum(loss) / denom
            if reduction == "sum":
                return jnp.sum(loss)
            return loss
        if reduction == "mean":
            return jnp.mean(loss)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    args = (input,) + ((weight,) if weight is not None else ())
    return apply_op("cross_entropy", fn, *args)


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    loss = loss.unsqueeze(axis) if not soft_label else loss
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    lbl = _val(label)
    def fn(lg, *w):
        loss = -jnp.take_along_axis(lg, lbl[..., None].astype(jnp.int32), axis=-1).squeeze(-1)
        mask = lbl != ignore_index
        loss = jnp.where(mask, loss, 0.0)
        if w:
            loss = loss * jnp.take(w[0], jnp.clip(lbl, 0, lg.shape[-1] - 1))
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss
    args = (input,) + ((weight,) if weight is not None else ())
    return apply_op("nll_loss", fn, *args)


def mse_loss(input, label, reduction="mean", name=None):
    def fn(a, b):
        loss = (a - b) ** 2
        return _reduce_loss(loss, reduction)
    return apply_op("mse_loss", fn, input, label)


def l1_loss(input, label, reduction="mean", name=None):
    def fn(a, b):
        return _reduce_loss(jnp.abs(a - b), reduction)
    return apply_op("l1_loss", fn, input, label)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def fn(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d, delta * (d - 0.5 * delta))
        return _reduce_loss(loss, reduction)
    return apply_op("smooth_l1_loss", fn, input, label)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def fn(a, b, *w):
        a = jnp.clip(a, 1e-12, 1 - 1e-12)
        loss = -(b * jnp.log(a) + (1 - b) * jnp.log1p(-a))
        if w:
            loss = loss * w[0]
        return _reduce_loss(loss, reduction)
    args = (input, label) + ((weight,) if weight is not None else ())
    return apply_op("bce", fn, *args)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    def fn(a, b, *rest):
        mx = jnp.maximum(a, 0)
        loss = mx - a * b + jnp.log1p(jnp.exp(-jnp.abs(a)))
        i = 0
        if pos_weight is not None:
            pw = rest[i]; i += 1
            loss = loss * (b * (pw - 1) + 1)
        if weight is not None:
            loss = loss * rest[i]
        return _reduce_loss(loss, reduction)
    args = [logit, label]
    if pos_weight is not None:
        args.append(pos_weight)
    if weight is not None:
        args.append(weight)
    return apply_op("bce_logits", fn, *args)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def fn(a, b):
        tgt = jnp.exp(b) if log_target else b
        loss = tgt * ((b if log_target else jnp.log(jnp.clip(b, 1e-30, None))) - a)
        if reduction == "batchmean":
            return jnp.sum(loss) / a.shape[0]
        return _reduce_loss(loss, reduction)
    return apply_op("kl_div", fn, input, label)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def fn(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)
    return apply_op("cosine_similarity", fn, x1, x2)


def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


# ---------------------------------------------------------------- attention
def cached_scaled_dot_product_attention(query, key, value, k_cache, v_cache,
                                        offset, scale=None):
    """Decode-phase attention (reference: the masked-MHA cache branch of
    paddle/fluid/operators/fused/fused_multi_transformer_op.cu): write the
    new key/value chunk (B, S, Hkv, D) into the static ring-buffer caches
    (B, T, Hkv, D) at sequence position ``offset``, then attend ``query``
    (B, S, H, D; GQA allowed) causally against the written prefix.

    Returns ``(out, k_cache, v_cache)`` — out (B, S, H, D), caches updated.
    ``offset`` may be a python int or a traced scalar; shapes stay static so
    one compilation serves every decode step. ``scale``: the softmax
    scale, ``1 / sqrt(D)`` where None."""
    from ..kernels.decode_attention import cached_attention, update_kv_cache

    def fn(qv, knv, vnv, kcv, vcv, off):
        kcv, vcv = update_kv_cache(kcv, vcv, knv, vnv, off)
        out = cached_attention(qv, kcv, vcv,
                               jnp.asarray(off, jnp.int32) + qv.shape[1],
                               sm_scale=scale)
        return out, kcv, vcv

    return apply_op("cached_sdpa", fn, query, key, value, k_cache, v_cache,
                    offset)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """reference: python/paddle/nn/functional/flash_attention.py
    ``flash_attention`` — [B, S, H, D] layout, returns ``(out, softmax)``
    with softmax None unless requested (the fused kernel never
    materializes it; ``return_softmax=True`` raises like the reference
    does on backends without the debug path)."""
    if return_softmax:
        raise NotImplementedError(
            "return_softmax requires materializing the (S, S) matrix the "
            "flash kernel exists to avoid — use plain "
            "scaled_dot_product_attention for debugging")
    if dropout and training:   # inference dropout is a no-op, like the ref
        raise NotImplementedError("attention dropout is not folded into "
                                  "the TPU flash kernel")
    out = scaled_dot_product_attention(query, key, value, is_causal=causal,
                                       training=training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """reference: flash_attn_unpadded (the varlen/packed form over
    FlashAttnUnpaddedKernel). Packed [total_tokens, H, D] with cumulative
    sequence boundaries. TPU-native: the packed batch becomes ONE flash
    call with SEGMENT IDS — the kernel's block-skip masks cross-sequence
    attention, no unpadding/repacking kernels needed. Causal masking uses
    LOCAL per-sequence positions; the kernel path serves the dominant
    self-attention case (identical q/k boundaries), other layouts take
    the dense segment-masked path."""
    if return_softmax:
        raise NotImplementedError("return_softmax: see flash_attention")
    if dropout and training:
        raise NotImplementedError("attention dropout is not folded into "
                                  "the TPU flash kernel")
    from .. import flags
    from ..kernels.flash_attention import flash_attention_bshd

    cu_q = _val(cu_seqlens_q)
    cu_k = _val(cu_seqlens_k)
    try:   # concrete boundaries: is this a self-attention pack?
        same_pack = np.array_equal(np.asarray(cu_q), np.asarray(cu_k))
    except Exception:   # traced inside jit: assume the dominant layout
        same_pack = True
    snap = flags.snapshot(("use_pallas",))
    kernel_ok = (snap.use_pallas and flags.is_tpu_backend()
                 and (same_pack or not causal))

    def fn(qv, kv, vv, cq, ck):
        tq = qv.shape[0]
        tk = kv.shape[0]
        # token i belongs to the sequence whose boundary interval holds i
        seg_q = jnp.searchsorted(cq, jnp.arange(tq), side="right")[None, :]
        seg_k = jnp.searchsorted(ck, jnp.arange(tk), side="right")[None, :]
        sc = scale if scale is not None else 1.0 / math.sqrt(qv.shape[-1])
        if kernel_ok:
            # contiguous SELF-attention packing: global causal order ==
            # per-sequence local order, so global-causal + segment mask
            # is exact
            try:
                out = flash_attention_bshd(
                    qv[None], kv[None], vv[None], segment_ids=seg_q,
                    kv_segment_ids=seg_k, causal=causal, sm_scale=sc)
                return out[0]
            except NotImplementedError:
                pass   # packed total not block-divisible
        h, hkv = qv.shape[1], kv.shape[1]
        kx = jnp.repeat(kv, h // hkv, axis=1) if hkv != h else kv
        vx = jnp.repeat(vv, h // hkv, axis=1) if hkv != h else vv
        s = jnp.einsum("qhd,khd->hqk", qv.astype(jnp.float32),
                       kx.astype(jnp.float32)) * sc
        mask = (seg_q[0][:, None] == seg_k[0][None, :])
        if causal:
            # LOCAL positions: token index minus its sequence's start
            start_q = jnp.concatenate([jnp.zeros((1,), cq.dtype),
                                       cq])[seg_q[0]]
            start_k = jnp.concatenate([jnp.zeros((1,), ck.dtype),
                                       ck])[seg_k[0]]
            loc_q = jnp.arange(tq) - start_q
            loc_k = jnp.arange(tk) - start_k
            mask &= loc_q[:, None] >= loc_k[None, :]
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        any_vis = jnp.any(mask, axis=-1)[None, :, None]
        p = jnp.where(any_vis, p, 0.0)
        return jnp.einsum("hqk,khd->qhd", p,
                          vx.astype(jnp.float32)).astype(qv.dtype)

    out = apply_op("flash_attn_unpadded", fn, query, key, value, cu_q, cu_k)
    return out, None


def paged_scaled_dot_product_attention(query, key, value, state, scale=None,
                                       block=1, window=None):
    """Paged (block-table) variant of the decode attention (reference:
    block_multihead_attention's two phases). ``state`` is a per-layer
    :class:`~paddle_tpu.kernels.paged_attention.PagedDecodeState` or —
    for chunked prefill — a ``PagedChunkState``; the state TYPE routes
    the S > 1 phase statically at trace time.

    Prefill (S > 1, PagedDecodeState, empty cache): the prompt attends
    causally to ITSELF (no cache read needed), then its k/v write into
    the pool pages.
    Chunked prefill (S > 1, PagedChunkState, B = 1): the chunk writes at
    positions ``seq_lens .. seq_lens+S-1`` and attends to the
    already-written prefix PLUS itself causally, reading the pool
    through the block table a key block of pages at a time
    (``paged_chunk_attention`` on chip: a tile of query tokens against
    the blocks it can see; its copy-free XLA twin elsewhere) — no
    gathered per-sequence view is materialized. Pad positions past the
    block table are dropped — but the returned state's ``seq_lens``
    still advance by the full static S, so a PADDED final chunk
    overcounts by its pad tail: the driver owns the true lengths (see
    the PagedChunkState length contract).
    Decode (S == 1): the token writes at position ``seq_lens`` and
    attends against the pool through the Pallas block-table kernel (XLA
    gather fallback when pallas is off). Returns ``(out, new_state)``.

    Block step (``PagedBlockState``, block diffusion): every row's S
    tokens are one block at ``seq_lens .. seq_lens+S-1``; they write
    there and all attend ``seq_lens + S`` positions — the decode kernel
    with S times as many query rows a KV head. The returned ``seq_lens``
    advance by S only where ``state.commit`` is set.

    ``scale``: the softmax scale handed to the kernels as ``sm_scale``
    (q is never pre-scaled in its own dtype); None is ``1 / sqrt(D)`` of
    the head's own width. ``block``: a power of two B makes both prefill
    phases BLOCK-causal (a query sees keys up to the end of its own
    block of B, ``k <= q | (B - 1)``), read through the block table as
    the chunked phase is; the prompt then holds whole blocks.
    ``window``: a static length W makes every phase a WINDOW layer's (a
    query at ``i`` sees ``i - W < j <= i``): the kernels visit only the
    pages that hold those positions, so ``state`` may be over a pool
    whose rows give the pages before them back. A whole prompt longer
    than the window reads through the block table as a chunk does."""
    from .. import flags
    from ..kernels.decode_attention import cached_attention
    from ..kernels.paged_attention import (PagedBlockState, PagedChunkState,
                                           QuantizedPages,
                                           paged_attention,
                                           paged_attention_xla,
                                           paged_chunk_attention,
                                           paged_chunk_attention_xla,
                                           write_paged_block,
                                           write_paged_kv,
                                           write_paged_prompt,
                                           write_paged_prompt_at)

    use_pallas = (flags.snapshot(("use_pallas",)).use_pallas
                  and flags.is_tpu_backend())
    chunked = isinstance(state, PagedChunkState)
    block_step = isinstance(state, PagedBlockState)
    if window is not None and (block_step or block != 1):
        raise NotImplementedError(
            "a window is over the causal mask: no block step or "
            "block-causal prefill under one")
    # a call without a window is the call it always was
    windowed = {} if window is None else {"window": int(window)}

    # a quantized pool reaches here as a NamedTuple whose FIELDS were
    # Tensor-wrapped by functional_call's tree walk (the tuple itself is
    # a pytree node, not a leaf) — unwrap to raw arrays for the kernels
    def _raw_pages(p):
        if isinstance(p, QuantizedPages):
            return QuantizedPages(_val(p.q), _val(p.scale))
        return p

    def fn(qv, kv, vv, kp, vp, bt, sl, *commit):
        s = qv.shape[1]
        d = qv.shape[-1]
        if kp.shape[-1] != d:
            # a lane-padded pool (``padded_head_dim``): the pool's rows are
            # wider than the head. Zero lanes add nothing to q.k and carry
            # zeros through p.v, so q/k/v pad up to the pool's width, the
            # softmax scale stays the head's, and the output slices back
            qp, kvp, vvp = (jnp.pad(x, [(0, 0)] * 3 + [(0, kp.shape[-1] - d)])
                            for x in (qv, kv, vv))
        else:
            qp, kvp, vvp = qv, kv, vv
        sm_scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
        if block_step:
            kp2, vp2 = write_paged_block(kp, vp, kvp, vvp, bt, sl)
            # (B, S, H, D) -> (B, Hkv * S * rep, D): the S queries of a
            # KV head's rep query heads are that head's query rows
            b, _, h, dp = qp.shape
            hkv = kp.shape[0]
            rows = qp.reshape(b, s, hkv, h // hkv, dp).transpose(
                0, 2, 1, 3, 4).reshape(b, hkv * s * (h // hkv), dp)
            attend = paged_attention if use_pallas else paged_attention_xla
            out = attend(rows, kp2, vp2, bt, sl + s, sm_scale=sm_scale)
            out = out.reshape(b, hkv, s, h // hkv, dp).transpose(
                0, 2, 1, 3, 4).reshape(b, s, h, dp)[..., :d]
            sl2 = sl + s * commit[0].astype(sl.dtype)
        elif s > 1 and (chunked or block > 1
                        or (window is not None and s > window)):
            if qv.shape[0] != 1:
                raise NotImplementedError(
                    "chunked paged prefill is per-request (B = 1); got "
                    f"batch {qv.shape[0]}")
            kp2, vp2 = write_paged_prompt_at(kp, vp, kvp, vvp, bt, sl)
            # query rows sit at absolute positions sl .. sl+s-1; rows
            # past the real prompt tail (final-chunk padding) emit
            # garbage the caller discards, and their K is masked off
            # every earlier row by causality. The pool is read through
            # the block table a key block of pages at a time — no
            # gathered (B, T, Hkv, D) view is ever materialized.
            attend = (paged_chunk_attention if use_pallas
                      else paged_chunk_attention_xla)
            out = attend(qp, kp2, vp2, bt, sl, sm_scale=sm_scale,
                         block=block, **windowed)[..., :d]
            sl2 = sl + s
        elif s > 1:
            # whole-prompt prefill contract: the sequences must be
            # EMPTY (chunked prefill rides PagedChunkState instead).
            # Enforce it whenever the lengths are concrete (eager
            # prototyping); under jit the docstring contract applies.
            if not isinstance(sl, jax.core.Tracer) and int(jnp.max(sl)):
                raise ValueError(
                    "paged prefill (S > 1) requires empty sequences "
                    f"(seq_lens all 0); got max {int(jnp.max(sl))}. "
                    "Use a PagedChunkState (chunked prefill) to extend "
                    "non-empty sequences, or decode one token at a "
                    "time after the prompt.")
            kp2, vp2 = write_paged_prompt(kp, vp, kvp, vvp, bt)
            # the prompt is the whole valid cache: causal self-attention
            out = cached_attention(qv, kv, vv, s, sm_scale=sm_scale)
            sl2 = sl + s
        else:
            kp2, vp2 = write_paged_kv(kp, vp, kvp[:, 0], vvp[:, 0], bt, sl)
            attend = paged_attention if use_pallas else paged_attention_xla
            out = attend(qp[:, 0], kp2, vp2, bt, sl + 1,
                         sm_scale=sm_scale, **windowed)[:, None, :, :d]
            sl2 = sl + 1
        return out, kp2, vp2, sl2

    out, kp2, vp2, sl2 = apply_op(
        "paged_sdpa", fn, query, key, value,
        _raw_pages(state.k_pages), _raw_pages(state.v_pages),
        state.block_tables, state.seq_lens,
        *((state.commit,) if block_step else ()))
    return out, type(state)(kp2, vp2, state.block_tables, sl2, *state[4:])


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, window=None):
    """SDPA with [batch, seq, heads, dim] layout (paddle convention —
    reference: python/paddle/nn/functional/flash_attention.py).
    Dispatches to the Pallas flash-attention kernel on TPU when enabled,
    through the per-shape FLAGS_flash_dispatch_table: benched-slower
    shape buckets resolve to the XLA dense path; the kernels take their
    blocks from the call's shapes (``flash_tiling``). ``window``: a
    causal band, key ``j`` seen by query ``i`` iff ``i - window < j <=
    i`` (``is_causal`` implied); the kernels then visit the band's
    blocks alone."""
    from .. import flags
    # one snapshot covering the whole flash-dispatch decision (kernel
    # on/off, the min-seqlen gate, the per-shape table) — resolved once
    # per trace and threaded through resolve_dispatch, never re-read per
    # helper (tracecheck TRC001)
    snap = flags.snapshot(("use_pallas", "flash_attn_min_seqlen",
                           "flash_compact_stats", "flash_dispatch_table"))
    if (snap.use_pallas and attn_mask is None and dropout_p == 0.0
            and flags.is_tpu_backend()
            and query.shape[1] >= snap.flash_attn_min_seqlen):
        try:
            from ..kernels.flash_attention import (flash_attention_bshd,
                                                   resolve_dispatch)
            kind = resolve_dispatch(query.shape[1], snap)
        except ImportError:
            kind = "dense"
        if kind == "flash":
            try:
                win = {} if window is None else {"window": window}
                return apply_op(
                    "flash_attention",
                    lambda q, k, v: flash_attention_bshd(
                        q, k, v, causal=is_causal or window is not None,
                        snap=snap, **win),
                    query, key, value)
            except NotImplementedError:
                pass

    mask_val = _val(attn_mask) if attn_mask is not None else None

    def fn(q, k, v):
        # GQA: unexpanded kv accepted everywhere; the dense path expands
        # here (the flash kernel above never does — Hkv bandwidth)
        if k.shape[2] != q.shape[2]:
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # [B, S, H, D] -> [B, H, S, D]
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        scores = jnp.einsum("bhsd,bhtd->bhst", qt, kt) / math.sqrt(q.shape[-1])
        if is_causal or window is not None:
            # iota comparison instead of a materialized tril constant: XLA
            # fuses it into the where; the pred[S,S] table showed up as the
            # TOP op (copy-start, 3% device time) in PROFILE_r05
            s, t = scores.shape[-2], scores.shape[-1]
            rows = jax.lax.broadcasted_iota(jnp.int32, (s, t), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1)
            seen = rows >= cols
            if window is not None:
                seen &= rows - cols < window
            scores = jnp.where(seen, scores, -1e30)
        if mask_val is not None:
            if mask_val.dtype == jnp.bool_:
                scores = jnp.where(mask_val, scores, -1e30)
            else:
                scores = scores + mask_val
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
        if dropout_p > 0.0 and training:
            keep = jax.random.bernoulli(next_key(), 1.0 - dropout_p, probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
        out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
        return jnp.swapaxes(out, 1, 2)

    return apply_op("sdpa", fn, query, key, value)


# ---------------------------------------------------------------- conv/pool
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
    dilation = (dilation, dilation) if isinstance(dilation, int) else tuple(dilation)
    if isinstance(padding, str):
        pad = padding.upper()
    elif isinstance(padding, int):
        pad = [(padding, padding), (padding, padding)]
    else:
        p = list(padding)
        if len(p) == 2 and all(isinstance(pi, (tuple, list)) for pi in p):
            pad = [tuple(p[0]), tuple(p[1])]  # already (lo, hi) pairs
        elif len(p) == 2:
            pad = [(p[0], p[0]), (p[1], p[1])]
        else:
            pad = [tuple(p[:2]), tuple(p[2:])]
    dn = jax.lax.conv_dimension_numbers(
        _val(x).shape, _val(weight).shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC"))

    def fn(a, w, *b):
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups)
        if b:
            shape = [1, -1, 1, 1] if data_format == "NCHW" else [1, 1, 1, -1]
            out = out + b[0].reshape(shape)
        return out

    args = (x, weight) + ((bias,) if bias is not None else ())
    return apply_op("conv2d", fn, *args)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    x2 = apply_op("unsq", lambda a: a[..., None, :] if data_format == "NCL" else a[:, None], x)
    w2 = apply_op("unsq", lambda a: a[..., None, :], weight)
    out = conv2d(x2, w2, bias,
                 stride=(1, stride if isinstance(stride, int) else stride[0]),
                 padding=((0, 0), (padding, padding)) if isinstance(padding, int) else padding,
                 dilation=(1, dilation if isinstance(dilation, int) else dilation[0]),
                 groups=groups, data_format="NCHW" if data_format == "NCL" else "NHWC")
    return apply_op("sq", lambda a: a.squeeze(-2 if data_format == "NCL" else 1), out)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     dilation=1, groups=1, data_format="NCHW", output_size=None, name=None):
    """Gradient-of-conv formulation: lhs-dilated conv with the spatially
    flipped kernel; weight layout [in, out // groups, kh, kw] (the
    reference's conv2d_transpose convention).
    out = (L - 1) * stride - 2 * padding + dilation * (k - 1) + 1 + output_padding
    """
    stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
    dilation = (dilation, dilation) if isinstance(dilation, int) else tuple(dilation)
    padding_ = (padding, padding) if isinstance(padding, int) else tuple(padding)
    op_ = ((output_padding, output_padding) if isinstance(output_padding, int)
           else tuple(output_padding))
    if data_format not in ("NCHW", "NHWC"):
        raise ValueError(f"conv2d_transpose: bad data_format {data_format}")
    if data_format == "NHWC":
        # transpose around the NCHW core: weights are layout-independent
        # ([in, out/g, kh, kw]) and XLA folds the transposes into the conv
        x_nchw = apply_op("nhwc_to_nchw", lambda a: a.transpose(0, 3, 1, 2), x)
        out = conv2d_transpose(
            x_nchw, weight, bias, stride=stride, padding=padding,
            output_padding=output_padding, dilation=dilation, groups=groups,
            data_format="NCHW", output_size=output_size)
        return apply_op("nchw_to_nhwc", lambda a: a.transpose(0, 2, 3, 1), out)
    kh, kw = _val(weight).shape[2], _val(weight).shape[3]
    pads = tuple(
        (dilation[i] * (k - 1) - padding_[i],
         dilation[i] * (k - 1) - padding_[i] + op_[i])
        for i, k in enumerate((kh, kw)))
    dn = ("NCHW", "IOHW", "NCHW")

    def fn(a, w, *b):
        wf = jnp.flip(w, (2, 3))
        if groups > 1:
            cin = wf.shape[0]
            # regroup [in, out/g, kh, kw] -> [in/g, out, kh, kw] group-major
            wf = wf.reshape(groups, cin // groups, *wf.shape[1:]) \
                .transpose(1, 0, 2, 3, 4) \
                .reshape(cin // groups, -1, *wf.shape[2:])
        out = jax.lax.conv_general_dilated(
            a, wf, window_strides=(1, 1), padding=pads,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=dn, feature_group_count=groups)
        if b:
            out = out + b[0].reshape(1, -1, 1, 1)
        return out

    args = (x, weight) + ((bias,) if bias is not None else ())
    return apply_op("conv2d_transpose", fn, *args)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    # single source for pool padding/ceil semantics: functional_extra
    from .functional_extra import _max_pool_mask_nd, _pool_nd
    if return_mask:
        return _max_pool_mask_nd(x, 2, kernel_size,
                                 stride or kernel_size, padding,
                                 ceil_mode, "max_pool2d", data_format)
    fn, *_ = _pool_nd(_val(x), 2, kernel_size, stride or kernel_size,
                      padding, jax.lax.max, -jnp.inf, data_format, ceil_mode)
    return apply_op("max_pool2d", fn, x)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    from .functional_extra import _avg_pool_nd
    return _avg_pool_nd(x, 2, "avg_pool2d", kernel_size, stride, padding,
                        exclusive, ceil_mode, data_format, divisor_override)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    os = (output_size, output_size) if isinstance(output_size, int) else tuple(output_size)
    def fn(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            a_ = a.reshape(n, c, os[0], h // os[0], os[1], w // os[1])
            return jnp.mean(a_, axis=(3, 5))
        n, h, w, c = a.shape
        a_ = a.reshape(n, os[0], h // os[0], os[1], w // os[1], c)
        return jnp.mean(a_, axis=(2, 4))
    return apply_op("adaptive_avg_pool2d", fn, x)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW", name=None):
    v = _val(x)
    if data_format == "NCHW":
        spatial = v.shape[2:]
    else:
        spatial = v.shape[1:-1]
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else [scale_factor] * len(spatial)
        size = tuple(int(s * f) for s, f in zip(spatial, sf))
    size = tuple(int(_val(s)) for s in size)
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic",
              "trilinear": "linear", "linear": "linear", "area": "linear"}[mode]

    def fn(a):
        if data_format == "NCHW":
            tgt = a.shape[:2] + size
        else:
            tgt = (a.shape[0],) + size + (a.shape[-1],)
        return jax.image.resize(a, tgt, method=method)

    return apply_op("interpolate", fn, x)


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor
    def fn(a):
        n, c, h, w = a.shape
        a = a.reshape(n, c // (r * r), r, r, h, w)
        a = a.transpose(0, 1, 4, 2, 5, 3)
        return a.reshape(n, c // (r * r), h * r, w * r)
    return apply_op("pixel_shuffle", fn, x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    ks = (kernel_sizes, kernel_sizes) if isinstance(kernel_sizes, int) else tuple(kernel_sizes)
    st = (strides, strides) if isinstance(strides, int) else tuple(strides)
    pd = (paddings, paddings) if isinstance(paddings, int) else tuple(paddings)
    dl = (dilations, dilations) if isinstance(dilations, int) else tuple(dilations)
    def fn(a):
        n, c, h, w = a.shape
        a = jnp.pad(a, ((0, 0), (0, 0), (pd[0], pd[0]), (pd[1], pd[1])))
        oh = (a.shape[2] - (dl[0] * (ks[0] - 1) + 1)) // st[0] + 1
        ow = (a.shape[3] - (dl[1] * (ks[1] - 1) + 1)) // st[1] + 1
        cols = []
        for i in range(ks[0]):
            for j in range(ks[1]):
                patch = a[:, :, i * dl[0]: i * dl[0] + oh * st[0]: st[0],
                          j * dl[1]: j * dl[1] + ow * st[1]: st[1]]
                cols.append(patch)
        out = jnp.stack(cols, axis=2)  # [N, C, k*k, oh, ow]
        return out.reshape(n, c * ks[0] * ks[1], oh * ow)
    return apply_op("unfold", fn, x)


# ---------------------------------------------------------------- sequence
def pad_sequence(sequences, padding_value=0.0, batch_first=False):
    vals = [_val(s) for s in sequences]
    maxlen = max(v.shape[0] for v in vals)
    padded = [jnp.pad(v, [(0, maxlen - v.shape[0])] + [(0, 0)] * (v.ndim - 1),
                      constant_values=padding_value) for v in vals]
    out = jnp.stack(padded, axis=0 if batch_first else 1)
    return Tensor(out)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def fn(l):
        n = l.shape[-1]
        if prior_dist is not None:
            return (1 - epsilon) * l + epsilon * _val(prior_dist)
        return (1 - epsilon) * l + epsilon / n
    return apply_op("label_smooth", fn, label)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    def fn(a):
        nt, c, h, w = a.shape
        n = nt // seg_num
        a = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([a[:, 1:, :fold], jnp.zeros_like(a[:, -1:, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(a[:, :1, fold:2 * fold]), a[:, :-1, fold:2 * fold]], axis=1)
        rest = a[:, :, 2 * fold:]
        return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)
    return apply_op("temporal_shift", fn, x)


# extended surface: 3-D conv/pool family, grid sampling, CTC, loss zoo
def softmax_(x, axis=-1, dtype=None, name=None):
    """In-place softmax (reference F.softmax_)."""
    out = softmax(x, axis=axis, dtype=dtype)
    x._value = out._value
    return x


from .functional_extra import *  # noqa: F401,F403,E402
