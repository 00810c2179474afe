"""Plain reference for ``mellum2-12b-a2.5b-instruct``: JetBrains Mellum 2
12B-A2.5B (``model_type: mellum``), written from its ``config.json``
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct) in float32
``jax.numpy`` under ``default_matmul_precision("highest")``. No kernel,
no sorting of tokens, no chunked rows; nothing of ``paddle_tpu`` is
imported. Only the NAMES of the weights are the program's
(``MellumForCausalLM``), because the reference is given the program's own
weights; its gradients are ``jax.grad`` of :func:`loss`.

    x      = E[ids]                               (untied head, no scale)
    a      = RMSNorm(x);  q = a W_q, k = a W_k, v = a W_v   (no q/k norm)
    a ``sliding_attention`` layer rotates q and k by RoPE (theta 500000)
    and sees  i - 1024 < j <= i;  a ``full_attention`` layer rotates by
    YaRN (factor 16 over an original 8,192, beta_fast 32, beta_slow 1;
    cos and sin times 1.2772588722239782) and sees  j <= i
    o      = softmax(q k^T / sqrt(128)) v         (8 query heads a KV head)
    x'     = x + o W_o
    b      = RMSNorm(x');  p = softmax(b W_r) over all 64;  top 8, the
             weights renormalised
    x      = x' + sum_{held e in top 8} w_e W_down_e(silu(W_gate_e b) * W_up_e b)
    loss   = mean CE(RMSNorm(x_L) W_head) + 0.001 * sum_l 64 * sum_e f_e P_e

with f_e the share of the step's tokens (all rows) that chose e among
their top 8 and P_e the mean of p_e, both over all 64 (Switch; the
router is held whole). The experts held are ``first_expert ..
first_expert + num_experts - 1`` of the router's ``router_experts``; an
expert not held adds nothing (the chip's share of a four-way expert
group). The vocabulary is the configuration's slice.

**Departures, each for memory alone** (4 x 8,192 tokens beside the
program's weights): the rows go one at a time (``lax.map``), each layer
is recomputed in the backward (``jax.checkpoint``), attention is taken
in blocks of ``Q_BLOCK`` queries (a window layer's block against the
``Q_BLOCK + window`` keys that can reach it, a full layer's against all
keys under the causal mask), every held expert runs over every token of
a row and is masked by the top 8 (one expert at a time), and the head's
cross-entropy goes in blocks of ``HEAD_BLOCK`` tokens. None changes a
value beyond float32 rounding. The balancing loss sums its statistics
over the rows before it is formed, so it is the step's.

**The limits of the comparison** (``benchmark/lib/mellum.py``
``judge_first_step``: the TIMED step's first call, its loss, its gradient
as its optimizer state holds it and its update of the float32 master
weights). Each lies between two readings, the program's and a control's
(``benchmark/tests/control_train_moe.py``: this reference with both
operands of every weight matmul rounded to ``float8_e4m3fn`` under a
per-tensor power-of-two amax scale, the gradient straight through, the
nearest precision below the configuration's bf16; and this reference
with every layer full attention). Both controls must fail; one limit
failing them is enough. The readings are in PERF.md section 6 (PR 38).

- ``LOSS_ATOL``: the absolute difference of the first loss. The
  program read 3.8e-6 to 5.1e-5 over seven seeds (a loss near
  ln 24,576 averaged over 32,768 tokens hides a precision); the limit
  sits about ten times from it and from the window-off control (4.3e-3
  to 1.4e-2 over three seeds).
- ``GRAD_RTOL``: per parameter group (attention, router, experts,
  embedding, head), ``|g_program - g_ref| / |g_ref|`` in the Frobenius
  norm over the group. The program's largest over seven seeds:
  attention 0.0154, router 0.0486, experts 0.0331, embedding 0.0205,
  head 0.0107; the scaled float8 control's, on the CPU at 2 layers,
  4 held experts and 1,024 tokens: 0.12, 0.20, 0.24, 0.14, 0.085, and
  on the chip at the cell's size 0.079, 0.148, 0.114, 0.074, 0.087. Each
  limit lies between the program's and the chip's control reading with
  room on both sides (the program read the same again through the timed
  step: attention 0.0150, router 0.0449, experts 0.0327, embedding
  0.0193, head 0.0110 at most over seven more seeds). The window-off
  control reads 1.0-2.0 in every group.
- ``UPDATE_RTOL``: per group, the step's change of the master weights
  against AdamW's first step of the step's own gradient. The program
  reads 8.4e-6 to 3.3e-5 on the chip (float32 rounding of a change of
  1e-4; the norm scales, near one, the most); a state left unchanged
  reads 1;
  the limit leaves the more room above the reading, as fresh seeds read
  higher.
"""

import math

import jax
import jax.numpy as jnp

LOSS_ATOL = 5e-4
GRAD_RTOL = {"attention": 0.04, "router": 0.1, "experts": 0.09,
             "embedding": 0.05, "head": 0.03}
UPDATE_RTOL = 0.02
# queries a block of attention, tokens a block of the head's loss
Q_BLOCK = 256
HEAD_BLOCK = 1024
WINDOW, FULL = "sliding_attention", "full_attention"


def group_of(name: str) -> str:
    """The parameter group a weight's gradient is judged in."""
    if name == "model.embed_tokens.weight":
        return "embedding"
    if name in ("lm_head.weight", "model.norm.weight"):
        return "head"
    if ".mlp.router" in name or "post_attention_layernorm" in name:
        return "router"
    if ".mlp." in name:
        return "experts"
    return "attention"


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _inv_freq(rope: dict, d: int):
    """(inverse frequencies, cos/sin scale) of one layer kind: plain RoPE,
    or YaRN by its paper (arXiv:2309.00071): a dimension that turns more
    than beta_fast times over the original context keeps its frequency,
    fewer than beta_slow times is divided by the factor, a linear ramp
    between (correction range floored and ceiled, as transformers)."""
    theta = float(rope["rope_theta"])
    base = theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / base, 1.0
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim_of(rope.get("beta_fast") or 32)), 0)
    hi = min(math.ceil(dim_of(rope.get("beta_slow") or 1)), d - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    keep = 1.0 - ramp                # 1: extrapolate (the original freq)
    inv = (1.0 / base) * keep + (1.0 / (factor * base)) * (1.0 - keep)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


def _rope(t, inv, scale):
    """t (s, heads, d): the two halves of each head rotated against each
    other; cos and sin times ``scale``."""
    s, _, d = t.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None] * scale
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None] * scale
    t1, t2 = t[..., :d // 2], t[..., d // 2:]
    return t * cos + jnp.concatenate([-t2, t1], -1) * sin


def _attention(q, k, v, window):
    """(s, heads, d) queries over (s, kv_heads, d) keys, causal, under a
    window when given; in blocks of ``Q_BLOCK`` queries."""
    s, heads, d = q.shape
    kv_heads = k.shape[1]
    rep = heads // kv_heads
    blk = min(Q_BLOCK, s)
    span = blk if window is None else blk + window
    if window is not None:      # keys before position 0 are padding
        k = jnp.concatenate([jnp.zeros((window,) + k.shape[1:], k.dtype), k])
        v = jnp.concatenate([jnp.zeros((window,) + v.shape[1:], v.dtype), v])

    def block(i):
        q0 = i * blk
        qb = jax.lax.dynamic_slice_in_dim(q, q0, blk).reshape(
            blk, kv_heads, rep, d)
        qpos = q0 + jnp.arange(blk)
        if window is None:
            kb, vb, kpos = k, v, jnp.arange(s)
        else:
            kb = jax.lax.dynamic_slice_in_dim(k, q0, span)
            vb = jax.lax.dynamic_slice_in_dim(v, q0, span)
            kpos = q0 - window + jnp.arange(span)
        seen = kpos[None] <= qpos[:, None]
        if window is not None:
            seen &= (kpos[None] >= 0) & (qpos[:, None] - kpos[None] < window)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, kb) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", probs, vb).reshape(blk, heads * d)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(s // blk))
    return out.reshape(s, heads * d)


def _rounded(t, dtype):
    """``t`` rounded to ``dtype`` under one power-of-two scale for the
    whole tensor that takes its largest magnitude to the top of the
    dtype's range (amax scaling, as float8 training does), and scaled
    back: the precision is the dtype's, not its range. The gradient
    passes straight through (the backward's products take the rounded
    operands, and the cotangents stay float32)."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(t)))
    scale = jnp.exp2(jnp.floor(jnp.log2(
        float(jnp.finfo(dtype).max) / jnp.maximum(amax, 1e-30))))
    r = (t * scale).astype(dtype).astype(jnp.float32) / scale
    return t + jax.lax.stop_gradient(r - t)


def _mm(a, w, matmul_dtype):
    """A weight matmul; ``matmul_dtype``: both operands rounded to it
    first (the controls)."""
    if matmul_dtype is not None:
        a, w = _rounded(a, matmul_dtype), _rounded(w, matmul_dtype)
    return a @ w


def _layer(w, x, i, model, matmul_dtype, all_full):
    """One layer over one row: (x, chose (E,), prob sums (E,))."""
    p = f"model.layers.{i}."
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    kind = FULL if all_full else model["layer_types"][i]
    window = model["sliding_window"] if kind == WINDOW else None
    s = x.shape[0]
    a = _rms_norm(x, w[p + "input_layernorm.weight"], eps)
    q = _mm(a, w[p + "self_attn.q_proj.weight"], matmul_dtype).reshape(
        s, heads, d)
    k = _mm(a, w[p + "self_attn.k_proj.weight"], matmul_dtype).reshape(
        s, kv_heads, d)
    v = _mm(a, w[p + "self_attn.v_proj.weight"], matmul_dtype).reshape(
        s, kv_heads, d)
    inv, scale = _inv_freq(model["rope_parameters"][model["layer_types"][i]],
                           d)
    q, k = _rope(q, inv, scale), _rope(k, inv, scale)
    x = x + _mm(_attention(q, k, v, window), w[p + "self_attn.o_proj.weight"],
                matmul_dtype)

    b = _rms_norm(x, w[p + "post_attention_layernorm.weight"], eps)
    probs = jax.nn.softmax(_mm(b, w[p + "mlp.router"], matmul_dtype), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_router = probs.shape[-1]
    chose = jnp.sum(jax.nn.one_hot(top_e, n_router), axis=1)       # (s, E)
    weight = jnp.sum(jax.nn.one_hot(top_e, n_router) * top_p[..., None],
                     axis=1)                                          # (s, E)
    first = model.get("first_expert", 0)
    gate_up, down = w[p + "mlp.gate_up"], w[p + "mlp.down"]
    width = down.shape[1]

    def expert(acc, e):
        gu = _mm(b, gate_up[e], matmul_dtype)
        h = jax.nn.silu(gu[:, :width]) * gu[:, width:]
        return acc + weight[:, first + e, None] * _mm(h, down[e],
                                                      matmul_dtype), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                        jnp.arange(gate_up.shape[0]))
    return x + y, jnp.sum(chose, axis=0), jnp.sum(probs, axis=0)


def _row(w, r, model, matmul_dtype, all_full):
    """One row ``r`` (s + 1,): (its summed token losses, per layer the
    summed top-8 choices and router probabilities, (L, E) each)."""
    eps = model["rms_norm_eps"]
    x = w["model.embed_tokens.weight"][r[:-1]]
    chose, probs = [], []
    for i in range(model["num_hidden_layers"]):
        x, c, pr = jax.checkpoint(
            lambda w, x, i=i: _layer(w, x, i, model, matmul_dtype, all_full)
        )(w, x)
        chose.append(c)
        probs.append(pr)
    x = _rms_norm(x, w["model.norm.weight"], eps)
    s = x.shape[0]
    blk = min(HEAD_BLOCK, s)

    def head(args):
        xb, yb = args
        lg = _mm(xb, w["lm_head.weight"], matmul_dtype)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], -1)[:, 0])

    ce = jax.lax.map(jax.checkpoint(head),
                     (x.reshape(s // blk, blk, -1),
                      r[1:].reshape(s // blk, blk)))
    return jnp.sum(ce), jnp.stack(chose), jnp.stack(probs)


def loss(weights: dict, ids, model: dict, matmul_dtype=None,
         all_full: bool = False):
    """The training loss of the batch ``ids`` (b, s + 1): the mean
    next-token cross-entropy over every position of every row, plus the
    balancing loss. ``matmul_dtype`` / ``all_full``: the two controls.
    The weights are taken in float32 whatever their dtype, so that
    ``jax.grad`` of this gives float32 gradients of float32 weights."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in weights.items()}
        ce, chose, probs = jax.lax.map(
            jax.checkpoint(lambda r: _row(w, r, model, matmul_dtype,
                                          all_full)), ids)
        tokens = ids.shape[0] * (ids.shape[1] - 1)
        f = jax.lax.stop_gradient(jnp.sum(chose, axis=0)) / tokens   # (L, E)
        pm = jnp.sum(probs, axis=0) / tokens
        balance = jnp.sum(f.shape[-1] * jnp.sum(f * pm, axis=-1))
        return (jnp.sum(ce) / tokens
                + model.get("router_aux_loss_coef", 0.001) * balance)
