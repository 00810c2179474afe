"""Plain reference for ``granite-4.0-h-micro``: IBM Granite 4.0-H Micro
(``model_type: granitemoehybrid``), written from its ``config.json``
(https://huggingface.co/ibm-granite/granite-4.0-h-micro) and the
published implementation (``transformers``' ``granitemoehybrid``, whose
Mamba-2 layer is Bamba's; Dao & Gu, arXiv:2405.21060) in float32
``jax.numpy``. No kernel, no cache, no batching, no chunked form: the
state-space recurrence is a ``lax.scan`` over positions. Nothing of
``paddle_tpu`` is imported; only the NAMES of the weights are the
program's, because the reference is given the program's own weights.

    x0     = embedding_multiplier * E[ids]
    x      = x + residual_multiplier * mixer_l(RMSNorm(x))
    x      = x + residual_multiplier * W_out (silu(g) * v),  [g | v] = W_in RMSNorm(x)
    logits = RMSNorm(x) E^T / logits_scaling

``mixer_l`` is causal attention (GQA, NO positional encoding, scores
times ``attention_multiplier``) where ``layer_types[l] == "attention"``
and Mamba-2 elsewhere:

    [z | xBC | dt] = W_in u;  xBC = silu(conv1d_causal_depthwise(xBC) + b)
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;  y_t = h_t C_t + D x_t
    out = W_out (RMSNorm(y * silu(z)) * w_norm)

Departures from the published implementation, each by what it changes:

- Everything is float32 (the published model computes in bfloat16 and
  keeps the recurrence's state in float32). The state's float32 is
  under ``assumed`` in the configuration file.
- The recurrence is sequential where the published code uses the
  chunked (SSD) form at ``mamba_chunk_size``; the two are the same sum
  in another order.
- The parts of ``W_in``'s output are taken in the order ``z | xBC | dt``
  and of ``xBC`` in the order ``x | B | C``, as the published code splits
  them; the depthwise convolution's weight is stored ``(d_conv,
  channels)``, tap ``d_conv - 1`` on the current position (the published
  ``(channels, 1, d_conv)`` transposed). Also under ``assumed``.
- The convolution's output is rounded to the weights' dtype before it
  is split into ``x | B | C``, as the published bfloat16 model's is:
  with float32 weights this is no rounding.

``TIE_ATOL``/``TIE_RTOL`` — serving. Greedy tokens must equal the
reference's argmax, except where the reference's own logit of the
engine's token is within the tolerance of its top logit. The tolerance
is on THIS model's logit scale: the division by ``logits_scaling`` = 8
puts the standard deviation of the logits near 0.11 and the top of
100,352 near 0.5, so the 3e-2 + 3e-2 * top that ``gpt3-345m`` uses (its
logits are eight times larger) would accept a token three tenths of a
standard deviation down, i.e. nearly any. 2e-2 absolute (no relative
part: the top logit hardly varies) lies between two readings at the
published widths on the chip (my chip runs, PR 28; 40 tokens a seed:
prompts of 64, 96, 128, 160 and 416, 8 tokens each):

- the largest gap the ENGINE gave: 0.0093, 0.0034, 0.0067 on three
  seeds (two flips of 40 each), and 0.0073 / 0.0048 on the cell's first
  two runs. This reference with both operands of every weight matmul
  rounded to bfloat16 moves a logit by at most 0.012, so two logits can
  part by 0.024 at the very worst; 2e-2 is 2.2 times the largest seen;
- this reference computed in 8-bit floats (``float8_e4m3fn``: matmul
  operands and the recurrent state), the nearest precision below the
  configuration's: largest gaps 0.65, 0.60, 0.63 on the same seeds, 8
  to 12 of 40 tokens flipped, 7 or more of them beyond 2e-2: it fails.
  The recurrent state ALONE in 8-bit floats: 0.094, 0.032, 0.046: fails.

What the limit cannot see, and what sees it instead: a bfloat16
recurrent state moves a logit by 0.004, a third of what bfloat16
matmuls already do, so no limit on the chip's tokens can tell it from
the configuration's own rounding; tier-1 holds the state float32
(``tests/test_granite_hybrid.py``, at 1e-4 in float32).
"""

import jax
import jax.numpy as jnp

TIE_ATOL = 2e-2
TIE_RTOL = 0.0


def _rounded(x, dtype):
    """float32 ``x`` with the precision of ``dtype``. An explicit
    ``reduce_precision``: XLA may drop a convert-and-back pair
    (``xla_allow_excess_precision``)."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _attention(u, mm, p, model, s):
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["hidden_size"] // heads
    q = mm(u, p + "q_proj.weight").reshape(s, kv_heads, heads // kv_heads, d)
    k = mm(u, p + "k_proj.weight").reshape(s, kv_heads, d)
    v = mm(u, p + "v_proj.weight").reshape(s, kv_heads, d)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) * model["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("grqk,kgd->qgrd", probs, v).reshape(s, heads * d)
    return mm(out, p + "o_proj.weight")


def _mamba(u, w, mm, p, model, s, round_to, state_dtype):
    n_heads, d_head = model["mamba_n_heads"], model["mamba_d_head"]
    n, g, k = (model["mamba_d_state"], model["mamba_n_groups"],
               model["mamba_d_conv"])
    d_inner = n_heads * d_head
    channels = d_inner + 2 * g * n
    zxbcdt = mm(u, p + "in_proj.weight")
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + channels]
    dt = zxbcdt[:, d_inner + channels:]
    conv_w = w(p + "conv_weight")                           # (k, channels)
    padded = jnp.concatenate([jnp.zeros((k - 1, channels)), xbc])
    conv = sum(padded[j:j + s] * conv_w[j] for j in range(k))
    xbc = round_to(jax.nn.silu(conv + w(p + "conv_bias")))
    x = xbc[:, :d_inner].reshape(s, n_heads, d_head)
    B = jnp.repeat(xbc[:, d_inner:d_inner + g * n].reshape(s, g, n),
                   n_heads // g, axis=1)                    # (s, H, n)
    C = jnp.repeat(xbc[:, d_inner + g * n:].reshape(s, g, n),
                   n_heads // g, axis=1)
    dt = jax.nn.softplus(dt + w(p + "dt_bias"))             # (s, H)
    A = -jnp.exp(w(p + "A_log"))

    def step(h, args):
        x_t, b_t, c_t, dt_t = args
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        h = _rounded(h, state_dtype)
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((n_heads, d_head, n)), (x, B, C, dt))
    y = (y + w(p + "D")[:, None] * x).reshape(s, d_inner)
    y = _rms_norm(y * jax.nn.silu(z), w(p + "norm.weight"),
                  model["rms_norm_eps"])
    return mm(y, p + "out_proj.weight")


def logits(weights: dict, ids, model: dict, *, matmul_dtype=None,
           state_dtype=jnp.float32):
    """(s, vocab) float32 logits of ONE sequence ``ids`` (s,).

    The two keywords exist for ONE purpose, the readings ``TIE_ATOL`` is
    set between (PERF.md): ``matmul_dtype`` rounds both operands of
    every weight matmul to a lower precision, ``state_dtype`` the
    recurrence's carried state after every step. The harness passes
    neither."""
    dtype = weights["model.embed_tokens.weight"].dtype

    def w(name):
        return weights[name].astype(jnp.float32)

    def low(x):
        return x if matmul_dtype is None else _rounded(x, matmul_dtype)

    def mm(x, name):
        return low(x) @ low(w(name))

    def round_to(x):
        return _rounded(x, dtype)

    eps, res = model["rms_norm_eps"], model["residual_multiplier"]
    s = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        emb = w("model.embed_tokens.weight")
        x = emb[ids] * model["embedding_multiplier"]
        for i, kind in enumerate(model["layer_types"]):
            p = f"model.layers.{i}."
            u = _rms_norm(x, w(p + "input_layernorm.weight"), eps)
            if kind == "attention":
                x = x + res * _attention(u, mm, p + "self_attn.", model, s)
            else:
                x = x + res * _mamba(u, w, mm, p + "mamba.", model, s,
                                     round_to, state_dtype)
            u = _rms_norm(x, w(p + "post_attention_layernorm.weight"), eps)
            gv = mm(u, p + "shared_mlp.input_linear.weight")
            inter = gv.shape[-1] // 2
            x = x + res * mm(jax.nn.silu(gv[:, :inter]) * gv[:, inter:],
                             p + "shared_mlp.output_linear.weight")
        x = _rms_norm(x, w("model.norm.weight"), eps)
        return low(x) @ low(emb).T / model["logits_scaling"]
