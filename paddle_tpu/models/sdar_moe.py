"""SDAR-MoE (``sdar_moe``, JetLM SDAR-30B-A3B-Chat): a Qwen3-MoE decoder
that generates by DIFFUSION OVER BLOCKS.

The decoder: identical layers of GQA attention with per-head RMS norms
on q and k before RoPE, and a sparse expert layer — a router
``softmax(x W_r)`` over ``num_experts``, the top ``num_experts_per_tok``
renormalised, each expert SwiGLU at ``moe_intermediate_size``; no shared
expert; RMSNorm, no bias, an untied head. Built from the pieces of
``models/llama.py``; the expert layer is the dropless
:class:`~paddle_tpu.incubate.distributed.models.moe.DroplessMoE` (told
which experts it holds: ``first_expert`` / ``experts_held``).

Generation: positions are cut into blocks of ``block_length`` (B, a
power of two). A token attends every token of its own and of earlier
blocks — the BLOCK-causal mask ``k <= q | (B - 1)``. A new block starts
as B mask tokens; each denoising forward predicts every masked position
and reveals the most confident; a last forward over the finished block
stores its K and V. :meth:`SDARMoEForCausalLM.block_spec` tells the
serving engine so; ``ServingEngine`` runs the schedule
(``generation/serving.py``, "block step"). ``forward`` is the whole
sequence under the block-causal mask, dense; ``forward_with_cache``
serves the engine's three paged states.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import ops
from ..core.tensor import apply_op
from ..generation import GenerationMixin
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..incubate.nn import functional as FF
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Embedding, Linear, RMSNorm
from ..nn.param_attr import ParamAttr


@dataclasses.dataclass
class SDARMoEConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    initializer_range: float = 0.02
    # generation by blocks (the family's generate.py; not in config.json)
    block_length: int = 4
    mask_token_id: int = 151669
    # the experts this instance holds (all of them unless told)
    first_expert: int = 0
    experts_held: Optional[int] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts - self.first_expert
        b = self.block_length
        if b < 1 or b & (b - 1):
            raise ValueError(f"block_length must be a power of two, got {b}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} is outside the "
                f"vocabulary of {self.vocab_size}")

    @staticmethod
    def tiny(**kw) -> "SDARMoEConfig":
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=256,
                    mask_token_id=127)
        base.update(kw)
        return SDARMoEConfig(**base)


def block_causal_mask(s: int, block: int):
    """(s, s) bool: query ``q`` sees key ``k`` iff ``k <= q | (block-1)``."""
    pos = jnp.arange(s, dtype=jnp.int32)
    return pos[None, :] <= (pos[:, None] | (block - 1))


def _dense_block_attention(q, k, v, block):
    """(B, S, H, D) attention under the block-causal mask, float32
    scores: the whole-sequence path (no cache)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, s, hkv, h // hkv, d).astype(jnp.float32)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(jnp.float32))
    scores = scores * (1.0 / d ** 0.5)
    scores = jnp.where(block_causal_mask(s, block), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


class SDARAttention(Layer):
    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.rope_theta = config.rope_theta
        self.block = config.block_length
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        q, kv = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        self.q_proj = Linear(h, q, weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.v_proj = Linear(h, kv, weight_attr=init, bias_attr=False)
        self.o_proj = Linear(q, h, weight_attr=init, bias_attr=False)
        # per head, over head_dim, before RoPE (Qwen3)
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
        if cache is None:
            position_ids = ops.arange(s, dtype="int32").unsqueeze(0)
        else:
            position_ids = paged_position_ids(s, cache[1], cache[0], "int32")
        q, k, _ = FF.fused_rotary_position_embedding(
            q, k, None, position_ids=position_ids,
            rotary_emb_base=self.rope_theta)
        if cache is None:
            out = apply_op(
                "sdar_sdpa",
                lambda q_, k_, v_: _dense_block_attention(q_, k_, v_,
                                                          self.block),
                q, k, v)
            return self.o_proj(out.reshape([b, s, -1]))
        out, state = F.paged_scaled_dot_product_attention(
            q, k, v, cache[0], block=self.block)
        return self.o_proj(out.reshape([b, s, -1])), state


class SDARDecoderLayer(Layer):
    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = SDARAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = DroplessMoE(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_tok,
            norm_topk_prob=config.norm_topk_prob, first=config.first_expert,
            count=config.experts_held,
            initializer_range=config.initializer_range)

    def forward(self, x, cache=None):
        attn = self.self_attn(self.input_layernorm(x), cache)
        state = None
        if cache is not None:
            attn, state = attn
        x = x + attn
        y, counts = self.mlp(self.post_attention_layernorm(x),
                             return_counts=True)
        x = x + y
        return x if cache is None else (x, state, counts)


class SDARMoEModel(Layer):
    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([SDARDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None, offset=None):
        x = self.embed_tokens(input_ids)
        if caches is None:
            for layer in self.layers:
                x = layer(x)
            return self.norm(x)
        new, counts = [], []
        for layer, entry in zip(self.layers, caches):
            x, entry, c = layer(x, cache=(entry, offset))
            new.append(entry)
            counts.append(c)
        return self.norm(x), new, counts


class SDARMoEForCausalLM(GenerationMixin, Layer):
    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        self.config = config
        self.model = SDARMoEModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)),
            bias_attr=False)

    def logits(self, hidden):
        """float32 logits from the operands as they are stored: the
        reveal compares confidences ACROSS positions, and a bf16 logit
        near 4 is a multiple of 1/32, which alone flips one reveal in
        ten against the float32 reference."""
        return apply_op(
            "lm_head",
            lambda h, w: jnp.dot(h, w, preferred_element_type=jnp.float32),
            hidden, self.lm_head.weight)

    def forward(self, input_ids):
        """Logits of whole sequences under the block-causal mask (the
        sequence may hold mask tokens)."""
        return self.logits(self.model(input_ids))

    # ---- the serving engine's hooks --------------------------------------
    def cache_spec(self):
        c = self.config
        return [(c.num_key_value_heads, c.head_dim)
                for _ in range(c.num_hidden_layers)]

    def block_spec(self) -> dict:
        """What makes this a block-diffusion model to ``ServingEngine``:
        the block length and the mask token's id."""
        c = self.config
        return dict(block_length=c.block_length,
                    mask_token_id=c.mask_token_id)

    def expert_counts_width(self) -> int:
        """What makes this a model with expert layers to
        ``ServingEngine``: ``forward_with_cache(..., expert_counts=True)``
        returns a third value of this width, (experts_held + 1,) int32:
        the assignments each held expert got in the call, summed over
        the layers, and last the experts TOUCHED: (layer, expert) pairs
        that got at least one assignment, which is how many experts'
        weights the grouped matmuls read."""
        return self.config.experts_held + 1

    def forward_with_cache(self, input_ids, caches, offset,
                           expert_counts: bool = False):
        """``caches``: per layer a ``PagedDecodeState`` (a whole prompt
        into empty sequences), ``PagedChunkState`` (a chunk at the
        cursor) or ``PagedBlockState`` (every row's block at its
        cursor); the two prefill states run block-causal. Returns
        ``(logits, caches)``, and with ``expert_counts`` the counts of
        :meth:`expert_counts_width` as a third value."""
        from ..kernels.paged_attention import is_paged_state
        if not all(is_paged_state(e) for e in caches):
            raise NotImplementedError(
                "SDARMoEForCausalLM keeps its cache in pages "
                "(ServingEngine); the ring buffer GenerationMixin.generate "
                "builds has no block-causal path")
        hidden, new_caches, per_layer = self.model(
            input_ids, caches=caches, offset=offset)
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
            "SDARMoEForCausalLM generates by diffusion over blocks, which "
            "ServingEngine schedules (submit / run); GenerationMixin's "
            "token-at-a-time loops do not apply")

    generate_paged = generate_speculative = generate
