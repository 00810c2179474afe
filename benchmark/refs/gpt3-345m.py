"""Plain reference for ``gpt3-345m``: the GPT-2 / Megatron-LM decoder
(Radford et al. 2019; Shoeybi et al., arXiv:1909.08053, section 5) in
float32 ``jax.numpy``, written from the papers: pre-LayerNorm blocks,
fused qkv projection, causal softmax attention, 4h MLP with the tanh
GELU, learned positions, the token embedding tied as the output head.
No kernel, no cache, no batching; nothing of ``paddle_tpu`` is imported.
Only the NAMES of the weights are the program's, because the reference
is given the program's own weights.

Tolerances, with their reasons:

``LOSS_ATOL`` — training. The program computes in bf16 (8 bits of
mantissa) with float32 accumulation and a float32 loss; the reference in
float32 throughout. With seeded random weights the loss sits near
ln(50304) = 10.83, and it is a mean over 16,384 tokens, so the per-logit
rounding (relative 2**-8, unbiased) averages out: on the chip the two
differed by 3.1e-5, 6.2e-5 and 3.1e-5 in three runs (my chip runs,
PR 23). 1e-3 is some twenty times that; a step computed in 8-bit floats
(3 bits of mantissa, 32 times coarser than bf16) or without its float32
accumulation moves the loss by more.

``TIE_ATOL``/``TIE_RTOL`` — serving. Greedy tokens must equal the
reference's argmax, except where the reference's own logit of the
engine's token is within the bf16 tolerance of its top logit (a near-tie
that bf16 rounding may flip). 3e-2 absolute plus 3e-2 relative is the
tolerance ``tests/test_paged_attention.py`` documents for kernels that
agree to 2e-5 in float32 and ``chip_smoke.py`` uses for the same purpose.
"""

import jax
import jax.numpy as jnp

LOSS_ATOL = 1e-3
TIE_ATOL = 3e-2
TIE_RTOL = 3e-2


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def logits(weights: dict, ids, model: dict):
    """(s, vocab) float32 logits of ONE sequence ``ids`` (s,)."""
    f32 = lambda name: weights[name].astype(jnp.float32)  # noqa: E731
    heads = model["num_attention_heads"]
    eps = model.get("layer_norm_epsilon", 1e-5)
    s = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        wte = f32("gpt.wte.weight")
        x = wte[ids] + f32("gpt.wpe.weight")[:s]
        h = x.shape[-1]
        d = h // heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(model["num_hidden_layers"]):
            p = f"gpt.h.{i}."
            a = _layer_norm(x, f32(p + "ln_1.weight"), f32(p + "ln_1.bias"),
                            eps)
            qkv = a @ f32(p + "attn.qkv_proj.weight") \
                + f32(p + "attn.qkv_proj.bias")
            qkv = qkv.reshape(s, 3, heads, d)
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h)
            x = x + o @ f32(p + "attn.out_proj.weight") \
                + f32(p + "attn.out_proj.bias")
            m = _layer_norm(x, f32(p + "ln_2.weight"), f32(p + "ln_2.bias"),
                            eps)
            m = _gelu_tanh(m @ f32(p + "mlp.fc_in.weight")
                           + f32(p + "mlp.fc_in.bias"))
            x = x + m @ f32(p + "mlp.fc_out.weight") \
                + f32(p + "mlp.fc_out.bias")
        x = _layer_norm(x, f32("gpt.ln_f.weight"), f32("gpt.ln_f.bias"), eps)
        return x @ wte.T


def loss(weights: dict, ids, model: dict):
    """Mean next-token cross-entropy over every position of every row
    of ``ids`` (b, s + 1); one row at a time, so that nothing larger
    than one sequence's logits is ever alive."""
    def row(r):
        lg = logits(weights, r[:-1], model)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, r[1:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)
    return jnp.mean(jax.lax.map(row, ids))
