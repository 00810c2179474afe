"""AFMoE (``afmoe``, arcee-ai Trinity): a sparse-expert decoder whose
attention layers are of two kinds, WINDOW and GLOBAL, in one model.

By the published ``config.json`` and ``modeling_afmoe.py``:

    h_0 = E[ids] * sqrt(hidden)                       (``mup_enabled``)
    layer l:  a = RMS_in(h)
              q = RMS_q(a W_q), k = RMS_k(a W_k) per head, v = a W_v,
              g = a W_g
              a ``sliding_attention`` layer rotates q and k (RoPE) and
              sees ``i - window < j <= i``; a ``full_attention`` layer
              has NO positional encoding and sees ``j <= i``
              o = softmax(q k^T / sqrt(d)) v * sigmoid(g)
              h = h + RMS_post_attn(o W_o)
              f = SwiGLU(RMS_pre_mlp(h))  for l < num_dense_layers, else
                  the expert layer: sigmoid scores over all experts, the
                  top k CHOSEN by score + ``expert_bias``, WEIGHTED by
                  the bare scores of the chosen, normalised
                  (``route_norm``) and scaled (``route_scale``), plus one
                  shared expert every token passes through
              h = h + RMS_post_mlp(f)
    logits = RMS_final(h) W_head                      (float32)

The expert layer is
:class:`~paddle_tpu.incubate.distributed.models.moe.DroplessMoE`, told
which experts it holds. ``cache_spec()`` names each layer's kind: a
window layer's entry is a :class:`WindowKV`, which makes
``ServingEngine``'s cache manager keep its pages in a second pool whose
rows hold a window and no more. ``forward`` is the whole sequence under
the dense masks; ``forward_with_cache`` serves the engine's paged
states. No group-limited routing (``n_group`` = ``topk_group`` = 1 in
every published config).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from .. import ops
from ..core.tensor import _val, apply_op
from ..generation import GenerationMixin
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..incubate.nn import functional as FF
from ..kernels.paged_attention import WindowKV
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Embedding, Linear, RMSNorm
from ..nn.param_attr import ParamAttr

WINDOW, GLOBAL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: Optional[List[str]] = None
    mup_enabled: bool = True
    initializer_range: float = 0.02
    # the experts this instance holds (all of them unless told)
    first_expert: int = 0
    experts_held: Optional[int] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts - self.first_expert
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = [GLOBAL if (i + 1) % n == 0 else WINDOW
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {WINDOW, GLOBAL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {WINDOW!r} or {GLOBAL!r}; got {self.layer_types}")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                "group-limited routing (n_group / topk_group > 1): no "
                "published afmoe config has it")
        if self.num_shared_experts not in (0, 1):
            raise NotImplementedError("more than one shared expert")

    @staticmethod
    def tiny(**kw) -> "AfmoeConfig":
        base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=5,
                    num_dense_layers=1, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=256,
                    sliding_window=16,
                    layer_types=[WINDOW, WINDOW, WINDOW, GLOBAL, WINDOW])
        base.update(kw)
        return AfmoeConfig(**base)


def _dense_attention(q, k, v, window):
    """(B, S, H, D) causal attention, float32 scores, and under a window
    ``i - j < window``: the whole-sequence path (no cache)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, s, hkv, h // hkv, d).astype(jnp.float32)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(jnp.float32))
    scores = scores * (1.0 / d ** 0.5)
    pos = jnp.arange(s, dtype=jnp.int32)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[:, None] - pos[None, :] < window
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


class AfmoeAttention(Layer):
    """``window``: this layer's window length, None for a global layer
    (which also takes no rotary embedding)."""

    def __init__(self, config: AfmoeConfig, window: Optional[int]):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.rope_theta = config.rope_theta
        self.window = window
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        q, kv = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        self.q_proj = Linear(h, q, weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.v_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.o_proj = Linear(q, h, weight_attr=init, bias_attr=False)
        # the output gate: sigmoid(a W_g) on the attention's output
        self.gate_proj = Linear(h, q, weight_attr=init, bias_attr=False)
        self.q_norm = RMSNorm(self.head_dim, epsilon=config.rms_norm_eps)
        self.k_norm = RMSNorm(self.head_dim, epsilon=config.rms_norm_eps)

    def forward(self, x, cache=None):
        from ..kernels.paged_attention import paged_position_ids
        b, s, _ = x.shape
        q = self.q_norm(self.q_proj(x).reshape(
            [b, s, self.num_heads, self.head_dim]))
        k = self.k_norm(self.k_proj(x).reshape(
            [b, s, self.num_kv_heads, self.head_dim]))
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        gate = F.sigmoid(self.gate_proj(x))
        if self.window is not None:         # a global layer has no RoPE
            if cache is None:
                position_ids = ops.arange(s, dtype="int32").unsqueeze(0)
            else:
                position_ids = paged_position_ids(s, cache[1], cache[0],
                                                  "int32")
            q, k, _ = FF.fused_rotary_position_embedding(
                q, k, None, position_ids=position_ids,
                rotary_emb_base=self.rope_theta)
        if cache is None:
            out = apply_op(
                "afmoe_sdpa",
                lambda q_, k_, v_: _dense_attention(q_, k_, v_, self.window),
                q, k, v)
            return self.o_proj(out.reshape([b, s, -1]) * gate)
        out, state = F.paged_scaled_dot_product_attention(
            q, k, v, cache[0], window=self.window)
        return self.o_proj(out.reshape([b, s, -1]) * gate), state


class AfmoeMLP(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        h, f = config.hidden_size, config.intermediate_size
        self.gate_proj = Linear(h, f, weight_attr=init, bias_attr=False)
        self.up_proj = Linear(h, f, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(f, h, weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class AfmoeDecoderLayer(Layer):
    def __init__(self, config: AfmoeConfig, index: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        window = (config.sliding_window
                  if config.layer_types[index] == WINDOW else None)
        self.sparse = index >= config.num_dense_layers
        self.input_layernorm = RMSNorm(h, epsilon=eps)
        self.self_attn = AfmoeAttention(config, window)
        self.post_attention_layernorm = RMSNorm(h, epsilon=eps)
        self.pre_mlp_layernorm = RMSNorm(h, epsilon=eps)
        if self.sparse:
            self.mlp = DroplessMoE(
                h, config.moe_intermediate_size, config.num_experts,
                config.num_experts_per_tok,
                norm_topk_prob=config.route_norm, first=config.first_expert,
                count=config.experts_held,
                initializer_range=config.initializer_range,
                score_func=config.score_func,
                route_scale=config.route_scale, select_bias=True,
                shared_intermediate_size=(config.moe_intermediate_size
                                          * config.num_shared_experts))
        else:
            self.mlp = AfmoeMLP(config)
        self.post_mlp_layernorm = RMSNorm(h, epsilon=eps)

    def forward(self, x, cache=None):
        attn = self.self_attn(self.input_layernorm(x), cache)
        state = None
        if cache is not None:
            attn, state = attn
        x = x + self.post_attention_layernorm(attn)
        m = self.pre_mlp_layernorm(x)
        counts = None
        if self.sparse:
            y, counts = self.mlp(m, return_counts=True)
        else:
            y = self.mlp(m)
        x = x + self.post_mlp_layernorm(y)
        return x if cache is None else (x, state, counts)


class AfmoeModel(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([AfmoeDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None, offset=None):
        x = self.embed_tokens(input_ids)
        if self.config.mup_enabled:
            x = x * math.sqrt(self.config.hidden_size)
        if caches is None:
            for layer in self.layers:
                x = layer(x)
            return self.norm(x)
        new, counts = [], []
        for layer, entry in zip(self.layers, caches):
            x, entry, c = layer(x, cache=(entry, offset))
            new.append(entry)
            if c is not None:
                counts.append(c)
        return self.norm(x), new, counts


class AfmoeForCausalLM(GenerationMixin, Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.model = AfmoeModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)),
            bias_attr=False)

    def logits(self, hidden):
        """float32 logits from the operands as they are stored: greedy
        decoding compares logits a few hundredths apart, and a bf16
        logit near 4 is a multiple of 1/32."""
        return apply_op(
            "lm_head",
            lambda h, w: jnp.dot(h, w, preferred_element_type=jnp.float32),
            hidden, self.lm_head.weight)

    def forward(self, input_ids):
        """Logits of whole sequences under the dense masks."""
        return self.logits(self.model(input_ids))

    # ---- the serving engine's hooks --------------------------------------
    def cache_spec(self):
        """Per layer: a :class:`WindowKV` for a window layer, the plain
        ``(kv_heads, head_dim)`` for a global one."""
        c = self.config
        return [WindowKV(c.num_key_value_heads, c.head_dim, c.sliding_window)
                if t == WINDOW else (c.num_key_value_heads, c.head_dim)
                for t in c.layer_types]

    def expert_counts_width(self) -> int:
        """What makes this a model with expert layers to
        ``ServingEngine`` (see ``SDARMoEForCausalLM``): (experts_held +
        1,) int32, the assignments each held expert got summed over the
        SPARSE layers, and last the (layer, expert) pairs touched."""
        return self.config.experts_held + 1

    # ``ServingEngine``'s prefill programs read ONE position's logits:
    # they hand it over (``logits_at``) and the head runs on that row
    logits_at_position = True

    def forward_with_cache(self, input_ids, caches, offset,
                           expert_counts: bool = False, logits_at=None):
        """``caches``: per layer a ``PagedDecodeState`` (a whole prompt
        into empty sequences, or one token a row) or a
        ``PagedChunkState`` (a chunk at the cursor). Returns ``(logits,
        caches)``, and with ``expert_counts`` the counts of
        :meth:`expert_counts_width` as a third value. ``logits_at``: a
        position (an int or a traced scalar); the logits are then of that
        position alone, ``(B, 1, V)``: a chunk of 1,024 through a head
        of 200,192 is 0.8 GB of float32 logits to read one row of."""
        from ..kernels.paged_attention import is_paged_state
        if not all(is_paged_state(e) for e in caches):
            raise NotImplementedError(
                "AfmoeForCausalLM keeps its cache in pages (ServingEngine); "
                "the ring buffer GenerationMixin.generate builds has no "
                "window")
        hidden, new_caches, per_layer = self.model(
            input_ids, caches=caches, offset=offset)
        if logits_at is not None:
            at = _val(logits_at)        # a traced scalar arrives wrapped
            hidden = apply_op(
                "position",
                lambda h: jax.lax.dynamic_slice_in_dim(h, at, 1, 1), hidden)
        logits = self.logits(hidden)
        if not expert_counts:
            return logits, new_caches
        counts = apply_op(
            "expert_counts",
            lambda *cs: jnp.concatenate(
                [sum(cs), sum(jnp.sum(c > 0) for c in cs)[None]
                 .astype(cs[0].dtype)]),
            *per_layer)
        return logits, new_caches, counts

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "AfmoeForCausalLM is served through ServingEngine (submit / "
            "run): its window layers keep their pages in a pool "
            "GenerationMixin's ring buffer does not have")

    generate_paged = generate_speculative = generate
