"""Mellum 2 (``mellum``, JetBrains): a sparse-expert decoder whose
attention layers are of two kinds, WINDOW and FULL, each with its own
rotary embedding, trained through ``hapi.TrainStep``.

By the published ``config.json`` (no q/k norm, no biases, untied head, no
embedding scale; every layer sparse, no shared expert):

    x_0 = E[ids]
    layer l:  a = RMSNorm(x);  q = a W_q, k = a W_k, v = a W_v
              a ``sliding_attention`` layer: RoPE (theta 500000, plain),
              key j seen by query i iff i - window < j <= i
              a ``full_attention`` layer: RoPE with YaRN (factor,
              original context, beta_fast / beta_slow from
              ``rope_parameters``), cos and sin times its
              ``attention_factor``; causal
              o = softmax(q k^T / sqrt(d)) v  (GQA);  x' = x + o W_o
              b = RMSNorm(x');  p = softmax_fp32(b W_r) over ALL experts;
              the top k renormalised;  x = x' + sum_j w_j SwiGLU_{e_j}(b)
    loss = CE(RMSNorm(x_L) W_head, labels)
           + router_aux_loss_coef * sum_l E * sum_e f_e P_e   (Switch)

The expert layer is :class:`DroplessMoE` told which experts it holds
(``first_expert``, ``num_experts`` of the router's ``router_experts``),
trained through its differentiable path (``forward_train``): an
assignment to an expert not held contributes nothing, and the balancing
loss is over the router's full width. Window layers run the flash
kernels over the band alone (``scaled_dot_product_attention(window=)``).
Each layer is recomputed in the backward, as a job of this size needs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import _val, apply_op
from ..distributed.fleet.utils.recompute import recompute
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..incubate.nn import functional as IF
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Embedding, Linear, RMSNorm
from ..nn.param_attr import ParamAttr

WINDOW, FULL = "sliding_attention", "full_attention"


def _default_rope():
    return {FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                   "original_max_position_embeddings": 8192,
                   "beta_fast": 32, "beta_slow": 1,
                   "attention_factor": 1.2772588722239782},
            WINDOW: {"rope_type": "default", "rope_theta": 500000}}


@dataclasses.dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    # the experts this instance holds (``first_expert`` onwards) and the
    # router's width, which is the published count whatever is held
    num_experts: int = 64
    router_experts: Optional[int] = None
    first_expert: int = 0
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    layer_types: Optional[List[str]] = None
    rope_parameters: Optional[Dict[str, dict]] = None
    max_position_embeddings: int = 131072
    router_aux_loss_coef: float = 0.001
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.num_experts
        if self.layer_types is None:
            self.layer_types = [FULL if (i + 1) % 4 == 0 else WINDOW
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if self.rope_parameters is None:
            self.rope_parameters = _default_rope()
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {WINDOW!r} or {FULL!r}; got {self.layer_types}")
        if self.first_expert < 0 or (self.first_expert + self.num_experts
                                     > self.router_experts):
            raise ValueError(
                f"experts held [{self.first_expert}, "
                f"{self.first_expert + self.num_experts}) are not inside "
                f"the router's {self.router_experts}")

    @staticmethod
    def tiny(**kw) -> "MellumConfig":
        base = dict(vocab_size=128, hidden_size=64, moe_intermediate_size=32,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, num_experts=8,
                    num_experts_per_tok=2, sliding_window=8,
                    max_position_embeddings=256,
                    rope_parameters={
                        FULL: {"rope_type": "yarn", "rope_theta": 10000,
                               "factor": 4,
                               "original_max_position_embeddings": 16,
                               "beta_fast": 32, "beta_slow": 1},
                        WINDOW: {"rope_type": "default",
                                 "rope_theta": 10000}})
        base.update(kw)
        return MellumConfig(**base)


# ------------------------------------------------------------------ rope
def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float = 32, beta_slow: float = 1,
                  truncate: bool = True):
    """YaRN's inverse frequencies (Peng et al., arXiv:2309.00071): the
    dimensions that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn fewer than
    ``beta_slow`` times are divided by ``factor``, and a linear ramp over
    the dimension index blends the two between. As
    ``transformers.modeling_rope_utils._compute_yarn_parameters``."""
    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolate = 1.0 - ramp
    return ((1.0 / (factor * pos)) * (1.0 - extrapolate)
            + (1.0 / pos) * extrapolate).astype(np.float32)


def rope_parameters(rope: dict, dim: int):
    """``(inv_freq (dim/2,), attention_factor)`` of one layer kind's
    ``rope_parameters`` entry: ``default`` or ``yarn``."""
    theta = float(rope["rope_theta"])
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32)
                                / dim)).astype(np.float32), 1.0
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}")
    factor = float(rope["factor"])
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    inv = yarn_inv_freq(dim, theta, factor,
                        int(rope["original_max_position_embeddings"]),
                        rope.get("beta_fast") or 32,
                        rope.get("beta_slow") or 1,
                        rope.get("truncate", True))
    return inv, float(scale)


def apply_rope(x, inv_freq, scale):
    """(B, S, H, D) rotated by position, the two halves of a head against
    each other; cos and sin times ``scale`` (YaRN's attention factor).
    In float32, returned in ``x``'s dtype."""
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


def attention_pairs(seq: int, window: Optional[int]) -> int:
    """Query-key pairs one sequence's causal attention must compute: all
    of the triangle, or under a window ``W(W+1)/2 + (s - W) W``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


# ---------------------------------------------------------------- layers
class MellumAttention(Layer):
    """``window``: this layer's window, None for a full layer."""

    def __init__(self, config: MellumConfig, kind: str):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.window = config.sliding_window if kind == WINDOW else None
        inv, scale = rope_parameters(config.rope_parameters[kind],
                                     config.head_dim)
        self._inv_freq, self._rope_scale = inv, scale
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        q, kv = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        self.q_proj = Linear(h, q, weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.v_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.o_proj = Linear(q, h, weight_attr=init, bias_attr=False)

    def forward(self, x):
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        inv, scale = self._inv_freq, self._rope_scale
        q, k = apply_op("rope", lambda a, c: (apply_rope(a, inv, scale),
                                              apply_rope(c, inv, scale)),
                        q, k)
        if self.window is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        else:
            with jax.named_scope("attn.window"):
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, window=self.window)
        return self.o_proj(out.reshape([b, s, -1]))


class MellumDecoderLayer(Layer):
    def __init__(self, config: MellumConfig, index: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = RMSNorm(h, epsilon=eps)
        self.self_attn = MellumAttention(config, config.layer_types[index])
        self.post_attention_layernorm = RMSNorm(h, epsilon=eps)
        self.mlp = DroplessMoE(
            h, config.moe_intermediate_size, config.router_experts,
            config.num_experts_per_tok, norm_topk_prob=config.norm_topk_prob,
            first=config.first_expert, count=config.num_experts,
            initializer_range=config.initializer_range)

    def forward(self, x):
        """``(x, counts (chunks, held) int32, balancing loss)``."""
        x = x + self.self_attn(self.input_layernorm(x))
        y, counts, balance = self.mlp.forward_train(
            self.post_attention_layernorm(x))
        return x + y, counts, balance


class MellumModel(Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([MellumDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        """``(hidden, [counts per layer], [balance per layer])``."""
        x = self.embed_tokens(input_ids)
        counts, balances = [], []
        for layer in self.layers:
            x, c, bal = recompute(layer, x)
            counts.append(c)
            balances.append(bal)
        return self.norm(x), counts, balances


class MellumForCausalLM(Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.model = MellumModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)),
            bias_attr=False)

    def logits(self, hidden):
        return apply_op(
            "lm_head",
            lambda h, w: jnp.dot(h, w, preferred_element_type=jnp.float32),
            hidden, self.lm_head.weight)

    def forward(self, input_ids, labels=None, return_counters=False):
        """float32 logits; with ``labels`` the training loss (the mean
        cross-entropy over the tokens plus the balancing loss), and with
        ``return_counters`` also this call's :meth:`train_counters`."""
        hidden, counts, balances = self.model(input_ids)
        if labels is None:
            return self.logits(hidden)
        ce = IF.fused_linear_cross_entropy(hidden, self.lm_head.weight,
                                           labels)
        coef = self.config.router_aux_loss_coef
        loss = apply_op("balanced_loss",
                        lambda c, *b: c + coef * sum(b), ce, *balances)
        if not return_counters:
            return loss
        cs = [_val(c) for c in counts]
        return loss, {
            "moe_assignments": sum(jnp.sum(c) for c in cs),
            "moe_experts_touched": sum(jnp.sum(c > 0) for c in cs
                                       ).astype(jnp.int32),
            "moe_expert_hist": sum(jnp.sum(c, axis=0) for c in cs)}

    # ---- what hapi.TrainStep keeps beside the step ----------------------
    def train_counters(self) -> dict:
        """The device-side counters a train step adds up (zeros): the
        assignments to held experts over all layers, the (layer, chunk,
        expert) grouped-matmul visits that had a row, and each held
        expert's assignments."""
        return {"moe_assignments": jnp.zeros((), jnp.int32),
                "moe_experts_touched": jnp.zeros((), jnp.int32),
                "moe_expert_hist": jnp.zeros((self.config.num_experts,),
                                             jnp.int32)}

    def attention_pairs(self, batch: int, seq: int) -> dict:
        """Query-key pairs a step of ``batch`` sequences of ``seq`` must
        compute, by layer kind, over all layers (host-side, from the
        shapes)."""
        c = self.config
        out = {"window": 0, "full": 0}
        for kind in c.layer_types:
            if kind == WINDOW:
                out["window"] += batch * attention_pairs(seq,
                                                         c.sliding_window)
            else:
                out["full"] += batch * attention_pairs(seq, None)
        return out
