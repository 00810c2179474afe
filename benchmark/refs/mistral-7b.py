"""Plain reference for ``mistral-7b``: the Mistral 7B decoder (Jiang et
al., arXiv:2310.06825; ``mistralai/Mistral-7B-v0.1`` model card and
``config.json``) in float32 ``jax.numpy``, written from the paper:
pre-RMSNorm blocks, grouped-query attention (32 query heads over 8
key/value heads of 128), rotary positions (theta 10000, the two halves
of a head rotated against each other), SLIDING-WINDOW causal attention
(a position sees the 4,096 before it), SwiGLU MLP, an output head that
is not tied. No kernel, no cache, no batching; nothing of ``paddle_tpu``
is imported. Only the NAMES of the weights are the program's
(``LlamaForCausalLM``), because the reference is given the program's own
weights.

The program runs the Llama layout, which has no window. At the cell's
sequence length of 4,096 the window covers every earlier position, so
the two compute the same function; the reference keeps the window, so a
longer sequence would show the difference.

``LOSS_ATOL`` — the program computes in bf16 (8 bits of mantissa) with
float32 accumulation and a float32 loss, the reference in float32
throughout. The loss sits near 11.2 with seeded random weights and is a
mean over 16,384 tokens, so unbiased per-logit rounding averages out: on
the four chips the two differed by 2.3e-5 to 2.4e-4 in eight runs (my
chip runs, PR 23). 2e-3 is about ten times the widest; a step in 8-bit floats
(32 times coarser than bf16), or one that dropped its float32
accumulation, moves the loss by more.
"""

import jax
import jax.numpy as jnp

LOSS_ATOL = 2e-3


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(t, theta):
    """t (s, heads, d): rotate the two halves of each head."""
    s, _, d = t.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]   # (s, d/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    t1, t2 = t[..., :d // 2], t[..., d // 2:]
    return t * cos + jnp.concatenate([-t2, t1], -1) * sin


def logits(weights: dict, ids, model: dict):
    """(s, vocab) float32 logits of ONE sequence ``ids`` (s,)."""
    f32 = lambda name: weights[name].astype(jnp.float32)  # noqa: E731
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    eps = model["rms_norm_eps"]
    theta = model["rope_theta"]
    window = model.get("sliding_window") or ids.shape[0]
    s = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = f32("llama.embed_tokens.weight")[ids]
        d = x.shape[-1] // heads
        pos = jnp.arange(s)
        seen = (pos[None] <= pos[:, None]) & (pos[:, None] - pos[None] < window)
        for i in range(model["num_hidden_layers"]):
            p = f"llama.layers.{i}."
            a = _rms_norm(x, f32(p + "input_layernorm.weight"), eps)
            q = (a @ f32(p + "self_attn.q_proj.weight")).reshape(s, heads, d)
            k = (a @ f32(p + "self_attn.k_proj.weight")).reshape(s, kv_heads, d)
            v = (a @ f32(p + "self_attn.v_proj.weight")).reshape(s, kv_heads, d)
            q, k = _rope(q, theta), _rope(k, theta)
            rep = heads // kv_heads     # query head h reads kv head h // rep
            q = q.reshape(s, kv_heads, rep, d)

            def group(g):
                """The ``rep`` query heads of kv head ``g``; one group at
                a time, so that no more than rep x s x s scores are alive."""
                scores = jnp.einsum("qrd,kd->rqk", q[:, g], k[:, g])
                scores = jnp.where(seen[None], scores / jnp.sqrt(float(d)),
                                   -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                return jnp.einsum("rqk,kd->qrd", probs, v[:, g])

            o = jax.lax.map(group, jnp.arange(kv_heads))  # (kv, s, rep, d)
            o = o.transpose(1, 0, 2, 3).reshape(s, heads * d)
            x = x + o @ f32(p + "self_attn.o_proj.weight")
            m = _rms_norm(x, f32(p + "post_attention_layernorm.weight"), eps)
            gate = m @ f32(p + "mlp.gate_proj.weight")
            up = m @ f32(p + "mlp.up_proj.weight")
            x = x + (jax.nn.silu(gate) * up) @ f32(p + "mlp.down_proj.weight")
        x = _rms_norm(x, f32("llama.norm.weight"), eps)
        return x @ f32("lm_head.weight")


def loss(weights: dict, ids, model: dict):
    """Mean next-token cross-entropy over every position of every row
    of ``ids`` (b, s + 1), one row at a time."""
    def row(r):
        lg = logits(weights, r[:-1], model)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, r[1:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)
    return jnp.mean(jax.lax.map(row, ids))
