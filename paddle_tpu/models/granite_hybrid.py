"""Granite 4.0-H (``granitemoehybrid``): Mamba-2 layers beside attention
layers in one decoder.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro
(``config.json``); the published implementation is ``granitemoehybrid``
in ``transformers``, whose Mamba-2 layer is Bamba's. The equations, with
the config's names:

    x0     = embedding_multiplier * E[ids]
    x      = x + residual_multiplier * mixer_l(RMSNorm(x))
    x      = x + residual_multiplier * mlp(RMSNorm(x))
    logits = (RMSNorm(x) E^T) / logits_scaling          (tied embedding)

``mlp(u) = W_out (silu(g) * v)`` with ``[g | v] = W_in u`` at
``shared_intermediate_size`` (``num_local_experts`` is 0: no routed
part). ``mixer_l`` is attention where ``layer_types[l] == "attention"``:
GQA with NO positional encoding (``position_embedding_type: "nope"``) and
the softmax scale ``attention_multiplier``; Mamba-2 elsewhere:

    [z | xBC | dt] = W_in u                 widths d_inner | d_inner + 2 G N | H
    xBC  = silu(conv1d_causal_depthwise(xBC) + b_conv)       kernel d_conv
    [x | B | C] = xBC                       widths d_inner | G N | G N
    dt   = softplus(dt + dt_bias),  A = -exp(A_log)          per head
    h_t  = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,  y_t = h_t C_t + D x_t
    out  = W_out (RMSNorm(y * silu(z)) * w_norm)

The recurrence itself — the chunked scan prefill runs, the aliased
kernel one decode step runs, the packed layout the state is kept in —
is ``kernels/ssm_update.py``; what a sequence's state IS, and how it
rides a program, is ``kernels/recurrent_state.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import ops
from ..core.tensor import _val, apply_op
from ..generation import GenerationMixin
from ..kernels import ssm_update as ssm
from ..kernels.recurrent_state import (RecurrentSpec, RecurrentState,
                                       is_recurrent_state)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Embedding, Linear, RMSNorm
from ..nn.param_attr import ParamAttr

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 8192
    shared_intermediate_size: int = 8192
    layer_types: Optional[Tuple[str, ...]] = None
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    logits_scaling: float = 8.0
    residual_multiplier: float = 0.22
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    # keys of the published config that select what this file implements
    position_embedding_type: str = "nope"
    attention_bias: bool = False
    hidden_act: str = "silu"
    normalization_function: str = "rmsnorm"
    num_local_experts: int = 0
    num_experts_per_tok: int = 0

    def __post_init__(self):
        if self.layer_types is None:
            reps = -(-self.num_hidden_layers // len(_PERIOD))
            self.layer_types = (_PERIOD * reps)[:self.num_hidden_layers]
        self.layer_types = tuple(self.layer_types)
        for key, want in (("position_embedding_type", "nope"),
                          ("attention_bias", False), ("hidden_act", "silu"),
                          ("normalization_function", "rmsnorm"),
                          ("num_local_experts", 0),
                          ("mamba_proj_bias", False),
                          ("tie_word_embeddings", True)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"GraniteHybridConfig.{key}={getattr(self, key)!r}: "
                    f"only {want!r} is implemented")
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in ("mamba", "attention") for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each 'mamba' or 'attention': {self.layer_types}")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError(
                "mamba_n_heads * mamba_d_head must equal mamba_expand * "
                "hidden_size")

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @staticmethod
    def tiny(**over) -> "GraniteHybridConfig":
        """Every mechanism at toy widths: 4 layers (mamba, attention,
        mamba, mamba), two heads to a packed state row."""
        kw = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                  num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=96, shared_intermediate_size=96,
                  layer_types=("mamba", "attention", "mamba", "mamba"),
                  attention_multiplier=0.0625, mamba_n_heads=4,
                  mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8,
                  max_position_embeddings=512)
        kw.update(over)
        return GraniteHybridConfig(**kw)

    def num_params(self) -> int:
        h, c = self.hidden_size, self
        mlp = 3 * h * c.shared_intermediate_size + 2 * h   # + both norms
        d = h // c.num_attention_heads
        attn = 2 * h * d * (c.num_attention_heads + c.num_key_value_heads)
        mamba = (h * (c.d_inner + c.conv_channels + c.mamba_n_heads)
                 + c.conv_channels * (c.mamba_d_conv + 1)
                 + 3 * c.mamba_n_heads + c.d_inner + c.d_inner * h)
        n_attn = sum(t == "attention" for t in c.layer_types)
        return (n_attn * (attn + mlp)
                + (c.num_hidden_layers - n_attn) * (mamba + mlp)
                + c.vocab_size * h + h)


class _Law(I.Initializer):
    """A parameter drawn by ``fn(key, shape) -> float32 array``."""

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, shape, dtype):
        from ..core.dtype import to_jax_dtype
        from ..framework.random import next_key
        return self.fn(next_key(), tuple(shape)).astype(to_jax_dtype(dtype))


def _a_log_law(key, shape):
    """``A ~ U[1, 16]``, ``A_log = log A`` (``mamba_ssm``'s
    ``Mamba2.__init__``, which ``transformers`` keeps)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias_law(key, shape):
    """``dt ~ logU[1e-3, 1e-1]``, ``dt_bias`` the inverse of the
    softplus at ``dt`` (same source)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


# ------------------------------------------------------------- the mixer
def _causal_conv(window, weight, bias, s: int):
    """Depthwise causal convolution: ``window`` (b, K - 1 + s, C) holds
    the K - 1 inputs before the first position, then the s positions;
    ``weight`` (K, C), tap K - 1 on the current input. float32."""
    k = weight.shape[0]
    w = weight.astype(jnp.float32)
    out = sum(window[:, j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    return out + bias.astype(jnp.float32)


def _mixer_core(config: GraniteHybridConfig, state_kind: str):
    """The Mamba-2 mixer between its two projections, on raw arrays:
    ``(zxbcdt, conv_w, conv_b, dt_bias, A_log, D, norm_w[, ssm, conv,
    slot, n_valid, live]) -> (gated y[, ssm, conv])``.

    ``state_kind``: ``"none"`` (a full forward from a zero state),
    ``"rows"`` (rows ``[:b]`` of the state, row for row with the batch)
    or ``"slot"`` (the one row ``slot`` of a b=1 call)."""
    c = config
    n_heads, p, n, g = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                        c.mamba_n_groups)
    d_inner, k = c.d_inner, c.mamba_d_conv
    pack = ssm.heads_per_row(n_heads, p, g)

    def fn(zxbcdt, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
           ssm_all=None, conv_all=None, slot=None, n_valid=None, live=None):
        b, s, _ = zxbcdt.shape
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + c.conv_channels]
        dt = zxbcdt[..., d_inner + c.conv_channels:]
        if state_kind == "none":
            conv0 = jnp.zeros((b, k - 1, c.conv_channels), xbc.dtype)
            h0 = jnp.zeros((b, n_heads, p, n), jnp.float32)
        elif state_kind == "slot":
            conv0 = lax.dynamic_slice_in_dim(conv_all, slot, 1, 0)
            h0 = ssm.unpack_state(
                lax.dynamic_slice_in_dim(ssm_all, slot, 1, 0), pack)
        else:
            conv0 = conv_all[:b]
            h0 = None if s == 1 else ssm.unpack_state(ssm_all[:b], pack)
        window = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)
        xbc_c = jax.nn.silu(_causal_conv(window, conv_w, conv_b, s))
        xbc_c = xbc_c.astype(xbc.dtype)
        x = xbc_c[..., :d_inner].reshape(b, s, n_heads, p)
        B = xbc_c[..., d_inner:d_inner + g * n].reshape(b, s, g, n)
        C = xbc_c[..., d_inner + g * n:].reshape(b, s, g, n)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        A = -jnp.exp(a_log.astype(jnp.float32))
        # the rows of the convolution's window a call leaves behind: the
        # last K - 1 REAL inputs (a padded chunk's pad is not an input)
        if n_valid is not None:
            real = jnp.arange(s, dtype=jnp.int32) < n_valid
            dt = jnp.where(real[None, :, None], dt, 0.0)
            conv_new = lax.dynamic_slice_in_dim(window, n_valid, k - 1, 1)
        else:
            conv_new = window[:, s:]
        if live is not None:
            alive = live.astype(bool)
            dt = jnp.where(alive[:, None, None], dt, 0.0)
            conv_new = jnp.where(alive[:, None, None], conv_new, conv0)

        if state_kind == "rows" and s == 1:
            ssm_all, y = ssm.ssm_decode_update(
                ssm_all, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], d_skip)
            y = y[:, None]
        else:
            y, h = ssm.ssd_chunk_scan(x, dt, A, B, C, d_skip, h0,
                                      c.mamba_chunk_size)
            if state_kind != "none":
                at = slot if state_kind == "slot" else 0
                ssm_all = lax.dynamic_update_slice_in_dim(
                    ssm_all, ssm.pack_state(h, pack), at, 0)
        y = y.reshape(b, s, d_inner) * jax.nn.silu(z.astype(jnp.float32))
        y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + c.rms_norm_eps)
        y = (y * norm_w.astype(jnp.float32)).astype(zxbcdt.dtype)
        if state_kind == "none":
            return y
        at = slot if state_kind == "slot" else 0
        conv_all = lax.dynamic_update_slice_in_dim(
            conv_all, conv_new.astype(conv_all.dtype), at, 0)
        return y, ssm_all, conv_all

    return fn


class GraniteMambaMixer(Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        c = self.config = config
        init = ParamAttr(initializer=I.Normal(0.0, c.initializer_range))
        self.in_proj = Linear(
            c.hidden_size, c.d_inner + c.conv_channels + c.mamba_n_heads,
            weight_attr=init, bias_attr=False)
        self.out_proj = Linear(c.d_inner, c.hidden_size, weight_attr=init,
                               bias_attr=False)
        self.norm = RMSNorm(c.d_inner, epsilon=c.rms_norm_eps)

        # the depthwise convolution as nn.Conv1d draws it at a fan-in
        # of d_conv; A_log and dt_bias by Mamba-2's own law
        bound = 1.0 / math.sqrt(c.mamba_d_conv)
        self.conv_weight = self.create_parameter(
            (c.mamba_d_conv, c.conv_channels),
            default_initializer=I.Uniform(-bound, bound))
        self.conv_bias = self.create_parameter(
            (c.conv_channels,), is_bias=True,
            default_initializer=(I.Uniform(-bound, bound)
                                 if c.mamba_conv_bias else I.Constant(0.0)))
        self.dt_bias = self.create_parameter(
            (c.mamba_n_heads,), is_bias=True,
            default_initializer=_Law(_dt_bias_law))
        self.A_log = self.create_parameter(
            (c.mamba_n_heads,), default_initializer=_Law(_a_log_law))
        self.D = self.create_parameter(
            (c.mamba_n_heads,), default_initializer=I.Constant(1.0))

    def forward(self, u, state: Optional[RecurrentState] = None):
        weights = (self.conv_weight, self.conv_bias, self.dt_bias,
                   self.A_log, self.D, self.norm.weight)
        zxbcdt = self.in_proj(u)
        if state is None:
            y = apply_op("mamba2_mixer", _mixer_core(self.config, "none"),
                         zxbcdt, *weights)
            return self.out_proj(y)
        kind = "rows" if state.slot is None else "slot"
        y, ssm_all, conv_all = apply_op(
            "mamba2_mixer", _mixer_core(self.config, kind), zxbcdt,
            *weights, state.ssm, state.conv, state.slot, state.n_valid,
            state.live)
        # the call's row selection was this call's: what goes back is
        # the arrays
        return self.out_proj(y), RecurrentState(ssm_all, conv_all)


class GraniteAttention(Layer):
    """GQA with no positional encoding and the config's softmax scale."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        self.scale = float(config.attention_multiplier)
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = Linear(h, h, weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.v_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.o_proj = Linear(h, h, weight_attr=init, bias_attr=False)

    def forward(self, x, cache=None):
        from ..kernels.paged_attention import is_paged_state
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if cache is None:
            out = apply_op(
                "granite_sdpa",
                lambda q_, k_, v_: _dense_causal(q_, k_, v_, self.scale),
                q, k, v)
            return self.o_proj(out.reshape([b, s, -1]))
        entry, offset = cache
        if is_paged_state(entry):
            out, entry = F.paged_scaled_dot_product_attention(
                q, k, v, entry, scale=self.scale)
        else:
            kc, vc = entry
            out, kc, vc = F.cached_scaled_dot_product_attention(
                q, k, v, kc, vc, offset, scale=self.scale)
            entry = (kc, vc)
        return self.o_proj(out.reshape([b, s, -1])), entry


def _dense_causal(q, k, v, scale):
    from ..kernels.decode_attention import cached_attention_dense
    return cached_attention_dense(q, k, v, q.shape[1], sm_scale=scale)


class GraniteSharedMLP(Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        inter = config.shared_intermediate_size
        self.input_linear = Linear(config.hidden_size, 2 * inter,
                                   weight_attr=init, bias_attr=False)
        self.output_linear = Linear(inter, config.hidden_size,
                                    weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.output_linear(F.swiglu(self.input_linear(x)))


class GraniteHybridLayer(Layer):
    def __init__(self, config: GraniteHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.residual = float(config.residual_multiplier)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        if kind == "attention":
            self.self_attn = GraniteAttention(config)
        else:
            self.mamba = GraniteMambaMixer(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.shared_mlp = GraniteSharedMLP(config)

    def forward(self, x, cache=None):
        """``cache``: None, or ``(entry, offset)`` with the layer's own
        kind of entry. Returns ``x`` (and the entry, given one)."""
        u = self.input_layernorm(x)
        entry = None
        if self.kind == "attention":
            mixed = self.self_attn(u, cache)
        else:
            mixed = self.mamba(u, None if cache is None else cache[0])
        if cache is not None:
            mixed, entry = mixed
        x = x + mixed * self.residual
        x = x + self.shared_mlp(self.post_attention_layernorm(x)) \
            * self.residual
        return x if cache is None else (x, entry)


class GraniteHybridModel(Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([GraniteHybridLayer(config, kind)
                                 for kind in config.layer_types])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None, offset=None):
        x = self.embed_tokens(input_ids) * float(
            self.config.embedding_multiplier)
        if caches is None:
            for layer in self.layers:
                x = layer(x)
            return self.norm(x)
        new = []
        for layer, entry in zip(self.layers, caches):
            if (layer.kind == "mamba") != is_recurrent_state(entry):
                raise TypeError(
                    f"layer of kind {layer.kind!r} was handed a cache "
                    f"entry of type {type(entry).__name__}")
            x, entry = layer(x, cache=(entry, offset))
            new.append(entry)
        return self.norm(x), new


class GraniteHybridForCausalLM(GenerationMixin, Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.model = GraniteHybridModel(config)

    def logits(self, hidden):
        out = ops.matmul(hidden, self.model.embed_tokens.weight,
                         transpose_y=True)
        return out * (1.0 / float(self.config.logits_scaling))

    def forward(self, input_ids, labels=None):
        hidden = self.model(input_ids)
        if labels is None:
            return self.logits(hidden)
        logits = self.logits(hidden)
        return F.cross_entropy(
            logits.reshape([-1, self.config.vocab_size]).astype("float32"),
            labels.reshape([-1]), reduction="mean")

    # ---- decode path (GenerationMixin hooks) -----------------------------
    def cache_spec(self):
        """Per layer: ``(kv_heads, head_dim)`` for an attention layer, a
        :class:`RecurrentSpec` for a Mamba-2 layer."""
        c = self.config
        page = (c.num_key_value_heads,
                c.hidden_size // c.num_attention_heads)
        rec = RecurrentSpec(
            ssm.packed_shape(c.mamba_n_heads, c.mamba_d_head,
                             c.mamba_d_state, c.mamba_n_groups),
            (c.mamba_d_conv - 1, c.conv_channels))
        return [page if t == "attention" else rec for t in c.layer_types]

    def init_cache(self, batch: int, max_len: int, dtype=None):
        """A dense cache for ``forward_with_cache`` outside the engine:
        ring-buffer ``(k, v)`` for the attention layers, a zero
        :class:`RecurrentState` of ``batch`` rows for the others."""
        if dtype is None:
            dtype = _val(next(iter(self.parameters()))).dtype
        out = []
        for e in self.cache_spec():
            if isinstance(e, RecurrentSpec):
                out.append(RecurrentState(
                    jnp.zeros((batch,) + e.ssm_shape, jnp.float32),
                    jnp.zeros((batch,) + e.conv_shape, dtype)))
            else:
                out.append((jnp.zeros((batch, max_len) + e, dtype),
                            jnp.zeros((batch, max_len) + e, dtype)))
        return out

    def forward_with_cache(self, input_ids, caches, offset):
        hidden, new_caches = self.model(input_ids, caches=caches,
                                        offset=offset)
        return self.logits(hidden), new_caches

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "GraniteHybridForCausalLM is served through ServingEngine "
            "(a recurrent layer's state is not the ring buffer "
            "GenerationMixin.generate builds from cache_spec); outside "
            "the engine, drive forward_with_cache with init_cache()")

    generate_paged = generate_speculative = generate
