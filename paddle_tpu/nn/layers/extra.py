"""Layer wrappers for the extended functional surface
(reference: python/paddle/nn/layer/{conv,pooling,norm,loss,distance}.py)."""

from __future__ import annotations

import math

import jax.numpy as jnp

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from ..layer import Layer
from ..param_attr import ParamAttr


class _ConvNd(Layer):
    _NDIM = 2

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, padding_mode="zeros",
                 weight_attr=None, bias_attr=None, data_format=None,
                 transpose=False, output_padding=0):
        super().__init__()
        nd = self._NDIM
        ks = (kernel_size,) * nd if isinstance(kernel_size, int) \
            else tuple(kernel_size)
        self._in, self._out = in_channels, out_channels
        self._stride, self._padding = stride, padding
        self._dilation, self._groups = dilation, groups
        self._output_padding = output_padding
        if transpose:
            wshape = (in_channels, out_channels // groups) + ks
        else:
            wshape = (out_channels, in_channels // groups) + ks
        fan_in = in_channels // groups * int(math.prod(ks))
        std = 1.0 / math.sqrt(fan_in)
        self.weight = self.create_parameter(
            wshape, attr=ParamAttr._to_attr(weight_attr),
            default_initializer=None if weight_attr else I.Uniform(-std, std))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (out_channels,), attr=ParamAttr._to_attr(bias_attr),
                is_bias=True,
                default_initializer=None if bias_attr else
                I.Uniform(-std, std))

    def extra_repr(self):
        return f"{self._in}, {self._out}, stride={self._stride}"


class Conv1D(_ConvNd):
    _NDIM = 1

    def forward(self, x):
        return F.conv1d(x, self.weight, self.bias, self._stride,
                        self._padding, self._dilation, self._groups)


class Conv3D(_ConvNd):
    _NDIM = 3

    def forward(self, x):
        return F.conv3d(x, self.weight, self.bias, self._stride,
                        self._padding, self._dilation, self._groups)


class Conv1DTranspose(_ConvNd):
    _NDIM = 1

    def __init__(self, *args, **kwargs):
        super().__init__(*args, transpose=True, **kwargs)

    def forward(self, x, output_size=None):
        return F.conv1d_transpose(x, self.weight, self.bias, self._stride,
                                  self._padding, self._output_padding,
                                  self._groups, self._dilation)


class Conv3DTranspose(_ConvNd):
    _NDIM = 3

    def __init__(self, *args, **kwargs):
        super().__init__(*args, transpose=True, **kwargs)

    def forward(self, x, output_size=None):
        return F.conv3d_transpose(x, self.weight, self.bias, self._stride,
                                  self._padding, self._output_padding,
                                  self._groups, self._dilation)


class _Pool(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, **kw):
        super().__init__()
        self._k, self._s, self._p = kernel_size, stride, padding
        # ceil_mode / exclusive / data_format ride through to the functional
        kw.pop("name", None)
        self._kw = kw

    def extra_repr(self):
        return f"kernel_size={self._k}, stride={self._s}, padding={self._p}"


class MaxPool1D(_Pool):
    def forward(self, x):
        return F.max_pool1d(x, self._k, self._s, self._p, **self._kw)


class AvgPool1D(_Pool):
    def forward(self, x):
        return F.avg_pool1d(x, self._k, self._s, self._p, **self._kw)


class MaxPool3D(_Pool):
    def forward(self, x):
        return F.max_pool3d(x, self._k, self._s, self._p, **self._kw)


class AvgPool3D(_Pool):
    def forward(self, x):
        return F.avg_pool3d(x, self._k, self._s, self._p, **self._kw)


class AdaptiveAvgPool1D(Layer):
    def __init__(self, output_size, name=None):
        super().__init__()
        self._o = output_size

    def forward(self, x):
        return F.adaptive_avg_pool1d(x, self._o)


class AdaptiveAvgPool3D(AdaptiveAvgPool1D):
    def forward(self, x):
        return F.adaptive_avg_pool3d(x, self._o)


class AdaptiveMaxPool1D(AdaptiveAvgPool1D):
    def forward(self, x):
        return F.adaptive_max_pool1d(x, self._o)


class AdaptiveMaxPool2D(AdaptiveAvgPool1D):
    def forward(self, x):
        return F.adaptive_max_pool2d(x, self._o)


class AdaptiveMaxPool3D(AdaptiveAvgPool1D):
    def forward(self, x):
        return F.adaptive_max_pool3d(x, self._o)


class _InstanceNorm(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCL",
                 name=None):
        super().__init__()
        self._eps = epsilon
        if weight_attr is False:
            self.scale = None
        else:
            self.scale = self.create_parameter(
                (num_features,), attr=ParamAttr._to_attr(weight_attr),
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (num_features,), attr=ParamAttr._to_attr(bias_attr),
                is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.scale, bias=self.bias,
                               eps=self._eps)


class InstanceNorm1D(_InstanceNorm):
    pass


class InstanceNorm2D(_InstanceNorm):
    pass


class InstanceNorm3D(_InstanceNorm):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self._args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self._args)


class ChannelShuffle(Layer):
    def __init__(self, groups, data_format="NCHW", name=None):
        super().__init__()
        self._groups, self._fmt = groups, data_format

    def forward(self, x):
        return F.channel_shuffle(x, self._groups, self._fmt)


class ZeroPad2D(Layer):
    def __init__(self, padding, data_format="NCHW", name=None):
        super().__init__()
        self._padding, self._fmt = padding, data_format

    def forward(self, x):
        return F.zeropad2d(x, self._padding, self._fmt)


class Fold(Layer):
    def __init__(self, output_sizes, kernel_sizes, strides=1, paddings=0,
                 dilations=1, name=None):
        super().__init__()
        self._args = (output_sizes, kernel_sizes, strides, paddings,
                      dilations)

    def forward(self, x):
        return F.fold(x, *self._args)


class Unfold(Layer):
    def __init__(self, kernel_sizes, strides=1, paddings=0, dilations=1,
                 name=None):
        super().__init__()
        self._args = (kernel_sizes, strides, paddings, dilations)

    def forward(self, x):
        return F.unfold(x, *self._args)


class PairwiseDistance(Layer):
    def __init__(self, p=2.0, epsilon=1e-6, keepdim=False, name=None):
        super().__init__()
        self._args = (p, epsilon, keepdim)

    def forward(self, x, y):
        return F.pairwise_distance(x, y, *self._args)


class Bilinear(Layer):
    """(reference: python/paddle/nn/layer/common.py::Bilinear)."""

    def __init__(self, in1_features, in2_features, out_features,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        std = 1.0 / math.sqrt(in1_features)
        self.weight = self.create_parameter(
            (out_features, in1_features, in2_features),
            attr=ParamAttr._to_attr(weight_attr),
            default_initializer=None if weight_attr else I.Uniform(-std, std))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (out_features,), attr=ParamAttr._to_attr(bias_attr),
                is_bias=True)

    def forward(self, x1, x2):
        return F.bilinear(x1, x2, self.weight, self.bias)


# ------------------------------------------------------------ loss layers
class CTCLoss(Layer):
    def __init__(self, blank=0, reduction="mean"):
        super().__init__()
        self._blank, self._reduction = blank, reduction

    def forward(self, log_probs, labels, input_lengths, label_lengths,
                norm_by_times=False):
        return F.ctc_loss(log_probs, labels, input_lengths, label_lengths,
                          self._blank, self._reduction, norm_by_times)


class MarginRankingLoss(Layer):
    def __init__(self, margin=0.0, reduction="mean", name=None):
        super().__init__()
        self._margin, self._reduction = margin, reduction

    def forward(self, input, other, label):
        return F.margin_ranking_loss(input, other, label, self._margin,
                                     self._reduction)


class HingeEmbeddingLoss(Layer):
    def __init__(self, margin=1.0, reduction="mean", name=None):
        super().__init__()
        self._margin, self._reduction = margin, reduction

    def forward(self, input, label):
        return F.hinge_embedding_loss(input, label, self._margin,
                                      self._reduction)


class SoftMarginLoss(Layer):
    def __init__(self, reduction="mean", name=None):
        super().__init__()
        self._reduction = reduction

    def forward(self, input, label):
        return F.soft_margin_loss(input, label, self._reduction)


class MultiLabelSoftMarginLoss(Layer):
    def __init__(self, weight=None, reduction="mean", name=None):
        super().__init__()
        self._weight, self._reduction = weight, reduction

    def forward(self, input, label):
        return F.multi_label_soft_margin_loss(input, label, self._weight,
                                              self._reduction)


class CosineEmbeddingLoss(Layer):
    def __init__(self, margin=0.0, reduction="mean", name=None):
        super().__init__()
        self._margin, self._reduction = margin, reduction

    def forward(self, input1, input2, label):
        return F.cosine_embedding_loss(input1, input2, label, self._margin,
                                       self._reduction)


class TripletMarginLoss(Layer):
    def __init__(self, margin=1.0, p=2.0, epsilon=1e-6, swap=False,
                 reduction="mean", name=None):
        super().__init__()
        self._args = (margin, p, epsilon, swap, reduction)

    def forward(self, input, positive, negative):
        return F.triplet_margin_loss(input, positive, negative, *self._args)


class PoissonNLLLoss(Layer):
    def __init__(self, log_input=True, full=False, epsilon=1e-8,
                 reduction="mean", name=None):
        super().__init__()
        self._args = (log_input, full, epsilon, reduction)

    def forward(self, input, label):
        return F.poisson_nll_loss(input, label, *self._args)


class GaussianNLLLoss(Layer):
    def __init__(self, full=False, epsilon=1e-6, reduction="mean", name=None):
        super().__init__()
        self._args = (full, epsilon, reduction)

    def forward(self, input, label, variance):
        return F.gaussian_nll_loss(input, label, variance, *self._args)


# ------------------------------------------- coverage-manifest layer batch
class AlphaDropout(Layer):
    """reference: nn/layer/common.py AlphaDropout (SELU-preserving)."""

    def __init__(self, p=0.5, name=None):
        super().__init__()
        self.p = p

    def forward(self, x):
        return F.alpha_dropout(x, p=self.p, training=self.training)


class Dropout3D(Layer):
    def __init__(self, p=0.5, data_format="NCDHW", name=None):
        super().__init__()
        self.p, self.data_format = p, data_format

    def forward(self, x):
        return F.dropout3d(x, p=self.p, training=self.training,
                           data_format=self.data_format)


class HuberLoss(Layer):
    def __init__(self, reduction="mean", delta=1.0, name=None):
        super().__init__()
        self._args = (delta, reduction)

    def forward(self, input, label):
        return F.huber_loss(input, label, *self._args)


class MultiMarginLoss(Layer):
    def __init__(self, p=1, margin=1.0, weight=None, reduction="mean",
                 name=None):
        super().__init__()
        self._p, self._margin, self._weight = p, margin, weight
        self._reduction = reduction

    def forward(self, input, label):
        return F.multi_margin_loss(input, label, p=self._p,
                                   margin=self._margin, weight=self._weight,
                                   reduction=self._reduction)


class Maxout(Layer):
    def __init__(self, groups, axis=1, name=None):
        super().__init__()
        self._groups, self._axis = groups, axis

    def forward(self, x):
        return F.maxout(x, self._groups, self._axis)


class RReLU(Layer):
    def __init__(self, lower=1.0 / 8.0, upper=1.0 / 3.0, name=None):
        super().__init__()
        self._lower, self._upper = lower, upper

    def forward(self, x):
        return F.rrelu(x, self._lower, self._upper, training=self.training)


class ThresholdedReLU(Layer):
    def __init__(self, threshold=1.0, value=0.0, name=None):
        super().__init__()
        self._threshold, self._value = threshold, value

    def forward(self, x):
        return F.thresholded_relu(x, self._threshold, self._value)


class PixelUnshuffle(Layer):
    def __init__(self, downscale_factor, data_format="NCHW", name=None):
        super().__init__()
        self._r, self._fmt = downscale_factor, data_format

    def forward(self, x):
        return F.pixel_unshuffle(x, self._r, self._fmt)


class _PadND(Layer):
    _fmt = "NCHW"

    def __init__(self, padding, mode="constant", value=0.0,
                 data_format=None, name=None):
        super().__init__()
        self.padding, self.mode, self.value = padding, mode, value
        self.data_format = data_format or self._fmt

    def forward(self, x):
        return F.pad(x, self.padding, mode=self.mode, value=self.value,
                     data_format=self.data_format)


class Pad1D(_PadND):
    _fmt = "NCL"


class Pad3D(_PadND):
    _fmt = "NCDHW"


class MaxUnPool1D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None, name=None):
        super().__init__()
        self._args = (kernel_size, stride, padding, data_format)
        self._output_size = output_size

    def forward(self, x, indices):
        return F.max_unpool1d(x, indices, self._args[0], self._args[1],
                              self._args[2], self._args[3],
                              self._output_size)


class MaxUnPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
        super().__init__()
        self._args = (kernel_size, stride, padding, data_format)
        self._output_size = output_size

    def forward(self, x, indices):
        return F.max_unpool2d(x, indices, self._args[0], self._args[1],
                              self._args[2], self._args[3],
                              self._output_size)


class MaxUnPool3D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None, name=None):
        super().__init__()
        self._args = (kernel_size, stride, padding, data_format)
        self._output_size = output_size

    def forward(self, x, indices):
        return F.max_unpool3d(x, indices, self._args[0], self._args[1],
                              self._args[2], self._args[3],
                              self._output_size)


class UpsamplingNearest2D(Layer):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._kw = dict(size=size, scale_factor=scale_factor,
                        mode="nearest", data_format=data_format)

    def forward(self, x):
        return F.interpolate(x, **self._kw)


class UpsamplingBilinear2D(Layer):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._kw = dict(size=size, scale_factor=scale_factor,
                        mode="bilinear", align_corners=True,
                        data_format=data_format)

    def forward(self, x):
        return F.interpolate(x, **self._kw)


class SpectralNorm(Layer):
    """reference: nn/layer/norm.py SpectralNorm — normalizes an input
    WEIGHT tensor by its largest singular value via power iteration.
    The u/v estimates are buffers updated eagerly per forward (inside a
    jitted program the update is functional: same math, no persistence —
    the reference trains eagerly here too)."""

    def __init__(self, weight_shape, dim=0, power_iters=1, epsilon=1e-12,
                 name=None):
        super().__init__()
        import numpy as _np
        self._dim, self._iters, self._eps = dim, power_iters, epsilon
        h = weight_shape[dim]
        w = int(_np.prod(weight_shape)) // h
        from ...core.tensor import Tensor as _T
        rng = _np.random.default_rng(0)
        self.register_buffer("weight_u", _T(
            rng.standard_normal(h).astype("float32"), stop_gradient=True))
        self.register_buffer("weight_v", _T(
            rng.standard_normal(w).astype("float32"), stop_gradient=True))

    def forward(self, weight):
        import jax as _jax
        import jax.numpy as jnp
        from ... import ops as _ops
        from ...core.tensor import Tensor as _T, _val as _v
        w = _v(weight)
        perm = [self._dim] + [i for i in range(w.ndim) if i != self._dim]
        wm = jnp.transpose(w, perm).reshape(w.shape[self._dim], -1)
        u, v = _v(self.weight_u), _v(self.weight_v)
        for _ in range(self._iters):
            v = wm.T @ u
            v = v / (jnp.linalg.norm(v) + self._eps)
            u = wm @ v
            u = u / (jnp.linalg.norm(u) + self._eps)
        if not isinstance(u, _jax.core.Tracer):
            self.weight_u._value = u
            self.weight_v._value = v
        # sigma via TAPE-RECORDED ops on the input weight so grads flow
        w_mat = _ops.reshape(_ops.transpose(weight, perm),
                             [w.shape[self._dim], -1])
        u_t = _T(u, stop_gradient=True)
        v_t = _T(v, stop_gradient=True)
        sigma = _ops.matmul(_ops.matmul(_ops.unsqueeze(u_t, 0), w_mat),
                            _ops.unsqueeze(v_t, -1)).reshape([])
        return weight / sigma


class RNNTLoss(Layer):
    def __init__(self, blank=0, fastemit_lambda=0.0, reduction="mean",
                 name=None):
        super().__init__()
        self._blank, self._reduction = blank, reduction

    def forward(self, logits, labels, logit_lengths, label_lengths):
        return F.rnnt_loss(logits, labels, logit_lengths, label_lengths,
                           blank=self._blank, reduction=self._reduction)


class ZeroPad1D(Layer):
    def __init__(self, padding, data_format="NCL", name=None):
        super().__init__()
        self._padding = padding if isinstance(padding, (list, tuple)) \
            else (padding, padding)
        self._fmt = data_format

    def forward(self, x):
        pad = list(self._padding)
        axis = -1 if self._fmt == "NCL" else 1
        return F.pad(x, [0, 0] * (2 if self._fmt == "NCL" else 1)
                     + pad if axis == -1 else pad, mode="constant")


class ZeroPad3D(Layer):
    def __init__(self, padding, data_format="NCDHW", name=None):
        super().__init__()
        p = padding
        self._padding = (p,) * 6 if isinstance(p, int) else tuple(p)
        self._fmt = data_format

    def forward(self, x):
        return F.pad(x, list(self._padding), mode="constant",
                     data_format=self._fmt)


class Unflatten(Layer):
    """reference: paddle.nn.Unflatten."""

    def __init__(self, axis, shape, name=None):
        super().__init__()
        self._axis, self._shape = axis, tuple(shape)

    def forward(self, x):
        from ...ops.manipulation import unflatten as _unf
        return _unf(x, self._axis, self._shape)


class Softmax2D(Layer):
    """reference: paddle.nn.Softmax2D — softmax over the channel dim of
    (N, C, H, W)."""

    def forward(self, x):
        return F.softmax(x, axis=-3)


class Silu(Layer):
    def forward(self, x):
        return F.silu(x)


class FeatureAlphaDropout(Layer):
    def __init__(self, p=0.5, name=None):
        super().__init__()
        self._p = p

    def forward(self, x):
        return F.feature_alpha_dropout(x, self._p, training=self.training)


class TripletMarginWithDistanceLoss(Layer):
    def __init__(self, distance_function=None, margin=1.0, swap=False,
                 reduction="mean", name=None):
        super().__init__()
        self._args = (distance_function, margin, swap, reduction)

    def forward(self, input, positive, negative):
        d, m, s, r = self._args
        return F.triplet_margin_with_distance_loss(
            input, positive, negative, distance_function=d, margin=m,
            swap=s, reduction=r)


class HSigmoidLoss(Layer):
    """reference: paddle.nn.HSigmoidLoss — holds the tree weights."""

    def __init__(self, feature_size, num_classes, weight_attr=None,
                 bias_attr=None, is_custom=False, is_sparse=False,
                 name=None):
        super().__init__()
        self.num_classes = num_classes
        self.weight = self.create_parameter(
            (num_classes - 1, feature_size),
            attr=ParamAttr._to_attr(weight_attr),
            default_initializer=I.XavierNormal())
        if bias_attr is not False:
            self.bias = self.create_parameter(
                (num_classes - 1, 1), is_bias=True)
        else:
            self.bias = None

    def forward(self, input, label):
        return F.hsigmoid_loss(input, label, self.num_classes,
                               self.weight, self.bias)


class AdaptiveLogSoftmaxWithLoss(Layer):
    """reference: paddle.nn.AdaptiveLogSoftmaxWithLoss."""

    def __init__(self, in_features, n_classes, cutoffs, div_value=4.0,
                 head_bias=False, name=None):
        super().__init__()
        self.cutoffs = list(cutoffs)
        self.n_clusters = len(self.cutoffs)
        shortlist = self.cutoffs[0]
        self.head_weight = self.create_parameter(
            (shortlist + self.n_clusters, in_features),
            default_initializer=I.XavierNormal())
        self.head_bias = (self.create_parameter(
            (shortlist + self.n_clusters,), is_bias=True)
            if head_bias else None)
        self.tail_weights = []
        bounds = self.cutoffs + [n_classes]
        for i in range(self.n_clusters):
            hsz = max(1, int(in_features // (div_value ** (i + 1))))
            osz = bounds[i + 1] - bounds[i]
            proj = self.create_parameter((hsz, in_features),
                                         default_initializer=I.XavierNormal())
            w = self.create_parameter((osz, hsz),
                                      default_initializer=I.XavierNormal())
            setattr(self, f"tail_proj_{i}", proj)
            setattr(self, f"tail_w_{i}", w)
            self.tail_weights += [proj, w]

    def forward(self, input, label):
        out, loss = F.adaptive_log_softmax_with_loss(
            input, label, self.head_weight, self.tail_weights,
            self.cutoffs, head_bias=self.head_bias)
        return out, loss


class FractionalMaxPool2D(Layer):
    """reference: paddle.nn.FractionalMaxPool2D — pseudo-random
    fractional pooling (Graham 2014); the region sequence is derived
    from output_size with the deterministic 'pseudo' scheme."""

    def __init__(self, output_size, kernel_size=None, random_u=None,
                 return_mask=False, name=None):
        super().__init__()
        self._out = output_size
        self._return_mask = return_mask

    def forward(self, x):
        return _fractional_pool(x, self._out, nd=2,
                                return_mask=self._return_mask)


class FractionalMaxPool3D(Layer):
    def __init__(self, output_size, kernel_size=None, random_u=None,
                 return_mask=False, name=None):
        super().__init__()
        self._out = output_size
        self._return_mask = return_mask

    def forward(self, x):
        return _fractional_pool(x, self._out, nd=3,
                                return_mask=self._return_mask)


def _fractional_pool(x, output_size, nd, return_mask=False):
    from ...core.tensor import apply_op as _ap
    import jax.numpy as _jnp
    spatial = x.shape[-nd:]
    outs = ((output_size,) * nd if isinstance(output_size, int)
            else tuple(output_size))

    def fn(a):
        out = a
        for d in range(nd):
            axis = a.ndim - nd + d
            n_in, n_out = spatial[d], outs[d]
            # deterministic fractional boundaries: floor(i * n_in/n_out)
            edges = _jnp.floor(
                _jnp.arange(n_out + 1) * (n_in / n_out)).astype(int)
            pieces = [
                _jnp.max(_jnp.take(out, _jnp.arange(int(edges[i]),
                                                    max(int(edges[i]) + 1,
                                                        int(edges[i + 1]))),
                                   axis=axis), axis=axis, keepdims=True)
                for i in range(n_out)]
            out = _jnp.concatenate(pieces, axis=axis)
        return out
    res = _ap("fractional_max_pool", fn, x)
    if return_mask:
        raise NotImplementedError("fractional pool return_mask")
    return res
