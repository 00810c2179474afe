"""Plain reference for ``trinity-mini``: arcee-ai Trinity-Mini
(``model_type: afmoe``), written from its ``config.json``
(https://huggingface.co/arcee-ai/Trinity-Mini) and, for the six points
its keys do not give, from the model repository's own
``modeling_afmoe.py`` (each is under ``assumed`` in the configuration's
file), in float32 ``jax.numpy`` under
``default_matmul_precision("highest")``. No kernel, no cache, no paging,
no sorting of tokens; nothing of ``paddle_tpu`` is imported. Only the
NAMES of the weights are the program's (``AfmoeForCausalLM``), because
the reference is given the program's own weights.

    h      = E[ids] * sqrt(hidden)                      (mup_enabled)
    a      = RMS_in(h)
    q, k   = RMS_q(a W_q), RMS_k(a W_k)  per head over 128;  v = a W_v
    g      = a W_g                                       (4096 wide)
    a ``sliding_attention`` layer rotates q and k (theta 10000, the two
    halves of a head against each other) and sees  i - 2048 < j <= i;
    a ``full_attention`` layer has NO positional encoding, sees j <= i
    o      = softmax(q k^T / sqrt(128)) v * sigmoid(g)
    h      = h + RMS_post_attn(o W_o)
    m      = RMS_pre_mlp(h)
    f      = W_down (silu(W_gate m) * W_up m)            l < num_dense_layers
           = shared(m) + sum_{e in sel} w_e expert_e(m)  else, with
             s = sigmoid(m W_r) over all 128, sel = top8(s + b),
             w = s[sel] / (sum s[sel] + 1e-20) * 2.826
    h      = h + RMS_post_mlp(f)
    logits = RMS_final(h) W_head

The masks are dense ``(s, s)`` arrays. The expert layer is a plain loop
over the experts with a top-8 mask, one expert's weights in float32 at
a time (every token through every expert held: the reference affords
what the program must not), so that it fits beside the engine; the head
runs over eighths of the vocabulary for the same reason, and ``rows``
lets the harness ask for the logits of the positions it compares only
(3,848 x 200,192 float32 logits are 3 GB). The experts held are
``first_expert .. first_expert + experts_held - 1`` (all 128 in this
configuration); one not held adds nothing.

**The limits of the comparison** (``benchmark/lib/afmoe.py`` ``compare``:
the engine's greedy tokens beside this reference's teacher-forced argmax
on prompts of 512, 1,536, 2,560 and 3,840, 8 tokens each, 32 a run).
With seeded normal(0, 0.02) weights a logit has a standard deviation
near 0.9 and the top of 200,192 sits near 4. The program computes in
bf16 with float32 accumulation, a float32 router score and float32
logits, the reference in float32 throughout. Each limit lies between
two readings on the chip at the cell's sizes and the cell's own sample,
made by ``benchmark/tests/control_mixed.py`` and the cell's own runs
through ``compare`` itself (my chip runs, PR 34; thirteen seeds for the
engine, six for the controls; PERF.md sections 4 and 6).

- ``MEAN_GAP_ATOL`` 7e-2: the mean over ALL the checked tokens of the
  reference's own logit gap, top less the engine's token (0 where the
  token is the reference's argmax). What the ENGINE gave: 0.0036-0.0247
  over thirteen seeds (1-7 of 32 tokens differ). What this reference gives
  with both operands of every weight matmul rounded to
  ``float8_e4m3fn``, the nearest precision below the configuration's
  (the CONTROL, ``logits(matmul_dtype=)``): 0.172-0.219 over six seeds
  (16-20 tokens differ), not correct on every one. The limit is 2.8
  times the engine's largest and 2.5 times under the control's
  smallest: this is the limit that tells a precision from the one below
  it. (This reference with bf16 operands, which is what the engine's
  rounding should look like, reads 0.0018-0.0305 on the same seeds: the
  engine is inside its own precision's range.)
- ``TIE_ATOL`` 1.0, ``TIE_RTOL`` 0 (no relative part: the top logit
  hardly moves): a token that is not the reference's argmax passes where
  the reference's logit of it lies within the limit of the top; past it
  the token is wrong, alone. This one is for a fault in some rows (a
  wrong mask past the window, a wrong cursor, a page given back too
  early), and it lies between the engine's largest gap, 0.06-0.36 over
  thirteen seeds (the bf16 reference's own: up to 0.355), and the second
  control's: this reference with the window left off every layer
  (``logits(window=None)``) reads largest gaps of 2.0-3.4 with six or
  more tokens past 1.0 on every seed, and a mean of 0.47-0.67, on the
  prompts past the window only (0, 0, 7-8 and 7-8 of their 8 tokens
  differ): not correct by both limits on every seed, so the comparison
  sees the window. The engine's own tail is long for a reason no
  precision cures: where the eighth and ninth expert's scores nearly
  tie, bf16 hidden states choose the other one, which replaces an
  eighth of the routed sum and moves a logit by a tenth of its standard
  deviation or more; a limit a token near the typical rounding (6e-2,
  this file's first; then 4e-1, which a sound run came within a tenth
  of) would refuse sound runs. The float8 control's largest gap is
  0.69-1.24: it fails by the mean, and by this limit only on some seeds.
"""

import math

import jax
import jax.numpy as jnp

TIE_ATOL = 1.0
TIE_RTOL = 0.0
MEAN_GAP_ATOL = 7e-2

WINDOW_LAYER = "sliding_attention"
HEAD_BLOCKS = 8


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


def seen_mask(s: int, window=None):
    """(s, s) bool: query i sees key j iff ``j <= i`` and, under a
    window, ``i - j < window``."""
    pos = jnp.arange(s)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[:, None] - pos[None, :] < window
    return seen


def _rounded(x, dtype):
    """float32 ``x`` with the precision of ``dtype`` (None: as it is)."""
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _swiglu(m, gate_up, down, low):
    """``down (silu(gate m) * up m)`` with gate | up side by side."""
    width = down.shape[0]
    gu = low(m) @ low(gate_up.astype(jnp.float32))
    return low(jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
        @ low(down.astype(jnp.float32))


def _experts(m, weights, p, model, low):
    """The expert layer on (s, hidden) ``m``: the shared expert, and
    every expert held over every token, masked by the top-k of the
    full-width router."""
    top_k = model["num_experts_per_tok"]
    first = model.get("first_expert", 0)
    held = model.get("experts_held") or model["num_experts"] - first
    router = weights[p + "mlp.router"].astype(jnp.float32)
    bias = weights[p + "mlp.expert_bias"].astype(jnp.float32)
    scores = jax.nn.sigmoid(low(m) @ low(router))               # (s, E)
    _, top_e = jax.lax.top_k(scores + bias, top_k)     # chosen WITH the bias
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)  # weighted without
    if model.get("route_norm", True):
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    top_s = top_s * model["route_scale"]
    gate_up, down = weights[p + "mlp.gate_up"], weights[p + "mlp.down"]

    def one(carry, e):
        weight = jnp.sum(jnp.where(top_e == first + e, top_s, 0.0), axis=-1)
        return carry + weight[:, None] * _swiglu(m, gate_up[e], down[e],
                                                 low), None

    out = _swiglu(m, weights[p + "mlp.shared_gate_up"],
                  weights[p + "mlp.shared_down"], low)
    out, _ = jax.lax.scan(one, out, jnp.arange(held))
    return out


def _head(x, w, low):
    """``x @ w`` over blocks of the vocabulary: the float32 copy of the
    whole head would be 1.6 GB."""
    vocab = w.shape[1]
    if vocab % HEAD_BLOCKS:
        return low(x) @ low(w.astype(jnp.float32))
    width = vocab // HEAD_BLOCKS

    def block(i):
        cols = jax.lax.dynamic_slice_in_dim(w, i * width, width, 1)
        return low(x) @ low(cols.astype(jnp.float32))

    out = jax.lax.map(block, jnp.arange(HEAD_BLOCKS))       # (blocks, r, width)
    return out.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def logits(weights: dict, ids, model: dict, *, rows=None,
           matmul_dtype=None, window="published"):
    """(s, vocab) float32 logits of ONE sequence ``ids`` (s,); with
    ``rows = (start, n)`` only of the positions ``start .. start + n -
    1``, (n, vocab) (``start`` may be traced).

    The other two keywords exist for ONE purpose, the controls
    ``TIE_ATOL`` is held against (``benchmark/tests/control_mixed.py``):
    ``matmul_dtype`` rounds both operands of every weight matmul
    (projections, router, experts, head) to a lower precision;
    ``window=None`` leaves the window off every layer. The harness
    passes neither."""
    f32 = lambda name: weights[name].astype(jnp.float32)  # noqa: E731
    low = lambda x: _rounded(x, matmul_dtype)             # noqa: E731
    mm = lambda x, name: low(x) @ low(f32(name))          # noqa: E731
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    d = model["head_dim"]
    eps = model["rms_norm_eps"]
    theta = model["rope_theta"]
    if window == "published":
        window = model["sliding_window"]
    s = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = weights["model.embed_tokens.weight"][ids].astype(jnp.float32)
        if model.get("mup_enabled", False):
            x = x * math.sqrt(model["hidden_size"])
        for i, kind in enumerate(model["layer_types"]):
            p = f"model.layers.{i}."
            windowed = kind == WINDOW_LAYER
            seen = seen_mask(s, window if windowed else None)
            a = _rms_norm(x, f32(p + "input_layernorm.weight"), eps)
            q = mm(a, p + "self_attn.q_proj.weight").reshape(s, heads, d)
            k = mm(a, p + "self_attn.k_proj.weight").reshape(s, kv_heads, d)
            v = mm(a, p + "self_attn.v_proj.weight").reshape(s, kv_heads, d)
            gate = jax.nn.sigmoid(mm(a, p + "self_attn.gate_proj.weight"))
            q = _rms_norm(q, f32(p + "self_attn.q_norm.weight"), eps)
            k = _rms_norm(k, f32(p + "self_attn.k_norm.weight"), eps)
            if windowed:        # a full-attention layer takes no rotation
                q, k = _rope(q, theta), _rope(k, theta)
            rep = heads // kv_heads     # query head h reads kv head h // rep
            q = q.reshape(s, kv_heads, rep, d)

            def group(g):
                scores = jnp.einsum("qrd,kd->rqk", q[:, g], k[:, g])
                scores = jnp.where(seen[None], scores / jnp.sqrt(float(d)),
                                   -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                return jnp.einsum("rqk,kd->qrd", probs, v[:, g])

            o = jax.lax.map(group, jnp.arange(kv_heads))  # (kv, s, rep, d)
            o = o.transpose(1, 0, 2, 3).reshape(s, heads * d) * gate
            x = x + _rms_norm(mm(o, p + "self_attn.o_proj.weight"),
                              f32(p + "post_attention_layernorm.weight"),
                              eps)
            m = _rms_norm(x, f32(p + "pre_mlp_layernorm.weight"), eps)
            if i < model["num_dense_layers"]:
                f = mm(jax.nn.silu(mm(m, p + "mlp.gate_proj.weight"))
                       * mm(m, p + "mlp.up_proj.weight"),
                       p + "mlp.down_proj.weight")
            else:
                f = _experts(m, weights, p, model, low)
            x = x + _rms_norm(f, f32(p + "post_mlp_layernorm.weight"), eps)
        x = _rms_norm(x, f32("model.norm.weight"), eps)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
        return _head(x, weights["lm_head.weight"], low)
