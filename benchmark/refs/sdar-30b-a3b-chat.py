"""Plain reference for ``sdar-30b-a3b-chat``: the SDAR-MoE decoder
(``JetLM/SDAR-30B-A3B-Chat`` ``config.json``, ``model_type: sdar_moe``,
which continues Qwen3-MoE) in float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, and the published reveal rule of
its block-diffusion generation. No kernel, no cache, no batching, no
sorting of tokens; nothing of ``paddle_tpu`` is imported. Only the NAMES
of the weights are the program's (``SDARMoEForCausalLM``), because the
reference is given the program's own weights.

The layer: pre-RMSNorm; grouped-query attention, 32 query heads over 4
key/value heads of 128, each head's q and k RMS-normed over the head
before the rotary embedding (theta 1e6, the two halves of a head rotated
against each other); the BLOCK-causal mask, a query sees every key up to
the end of its own block of B positions (``k <= q | (B - 1)``); then the
expert layer, ``p = softmax(x W_r)`` over all 128 experts, the top 8
renormalised, ``y = sum_e p_e / sum_top8 p * W_down_e (silu(W_gate_e x)
* W_up_e x)``, computed here as a plain loop over the experts with a
top-8 mask (every token through every expert held: the reference affords
what the program must not). The experts held are ``first_expert ..
first_expert + experts_held - 1`` of the 128 (all of them in this
configuration); one not held adds nothing. Untied head.

``logits`` is the full forward of one sequence that may hold mask tokens.
``reveal`` is the published choice (``low_confidence_static``): of the
masked positions of a block, the one whose largest softmax probability is
largest, and its argmax token.

**The limits of the comparison** (``benchmark/lib/blocks.py``
``compare``; every forward of the checked requests, 64 a run, the
reference fed the engine's own prefix and block). With seeded
normal(0, 0.02) weights a logit has a standard deviation near 0.9 and
the top of 151,936 sits near 4; the four positions of a block differ in
log-confidence by tenths. The program computes in bf16 with float32
accumulation, a float32 router and float32 logits, the reference in
float32 throughout. Each limit lies between two readings on the chip at
the cell's sizes and the cell's own sample, both made by
``benchmark/tests/control_blocks.py`` through ``compare`` itself (my
chip runs, PR 32; seeds 1234567891, 2718281828, 3141592653, 4000000007,
987654321; PERF.md sections 4 and 6): what the ENGINE gave, and what
this reference gives with both operands of every weight matmul rounded
to ``float8_e4m3fn``, the nearest precision below the configuration's
(the CONTROL, ``logits(matmul_dtype=)``), which has to come out not
correct: it does on every seed, by the first limit with room to spare
and by the other two narrowly on the weakest seed (0.156 for 0.15,
0.104 for 0.1).

- ``CONF_MEDIAN_ATOL`` 3e-2: the median over the forwards of each
  one's largest |log-confidence the step computed - the reference's|
  over its masked positions. Engine 0.0083-0.0124 over the five seeds;
  control 0.062-0.106. The limit is 2.4 times the engine's largest and
  under half the control's smallest, and a median of 64 hardly moves
  between seeds: this is the limit that tells a precision from the one
  below it.
- ``CONF_ATOL`` 1.5e-1: the same distance in ANY forward. The engine's
  own rounding has a long tail, largest 0.030-0.061 a seed over eight
  seeds (median 0.010); the control's largest 0.156-0.280, 1-17 of 64
  forwards beyond the limit. Two and a half times the engine's largest:
  this one is for a fault
  in some rows (a wrong mask past a chunk, a wrong cursor), which moves
  a log-confidence by far more, and a limit a forward near the engine's
  own tail would refuse a sound run now and then.
- ``TIE_ATOL`` 1e-1, ``TIE_RTOL`` 0 (no relative part: the top logit
  hardly moves): a reveal passes where it is the reference's own, or
  where the reference gives the engine's (position, token) a
  log-confidence within ``TIE_ATOL + TIE_RTOL * |top logit|`` of its
  own choice's AND the engine's token a logit within the same of the
  top logit at that position. The engine differs on 6-15 of 64 reveals,
  largest gap 0.010-0.051 a seed (seven seeds; exactly what this
  reference gives with bf16 operands); the control on 26-37, largest
  0.104-0.327. Twice the engine's largest. A limit at the middle of
  these two, 5e-2, refused a sound run (0.0509 on seed 1234567891): the
  gap of a flipped reveal is bounded by how far apart the positions'
  confidences lie, tenths, for any precision, so this statistic alone
  separates the two by a factor of 2 and the first limit does the work.

An expert matmul accumulated in bf16, a router softmax in bf16 (which
changes which experts a token gets) and a causal mask in place of the
block-causal one move log-confidences by more than the limits
(tests/test_sdar_moe.py holds the last at toy size).
"""

import jax
import jax.numpy as jnp

CONF_MEDIAN_ATOL = 3e-2
CONF_ATOL = 1.5e-1
TIE_ATOL = 1e-1
TIE_RTOL = 0.0


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


def block_mask(s: int, block: int):
    """(s, s) bool: query q sees key k iff k <= q | (block - 1)."""
    pos = jnp.arange(s)
    return pos[None, :] <= (pos[:, None] | (block - 1))


def _rounded(x, dtype):
    """float32 ``x`` with the precision of ``dtype`` (None: as it is)."""
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _experts(m, weights, p, model, low):
    """The expert layer on (s, hidden) ``m``: every expert held over
    every token, masked by the top-k of the full-width router. ``low``
    rounds a matmul's operand (``logits``' ``matmul_dtype``)."""
    top_k = model["num_experts_per_tok"]
    first = model.get("first_expert", 0)
    held = model.get("experts_held") or model["num_experts"] - first
    router = weights[p + "mlp.router"].astype(jnp.float32)
    probs = jax.nn.softmax(low(m) @ low(router), axis=-1)        # (s, E)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if model.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gate_up, down = weights[p + "mlp.gate_up"], weights[p + "mlp.down"]
    width = down.shape[1]

    def one(carry, e):
        # one expert's weights to float32 at a time: 6 layers of float32
        # experts at once would not fit beside the program's own
        weight = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
        gu = low(m) @ low(gate_up[e].astype(jnp.float32))
        y = low(jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
            @ low(down[e].astype(jnp.float32))
        return carry + weight[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(held))
    return out


def logits(weights: dict, ids, model: dict, mask=None, *,
           matmul_dtype=None):
    """(s, vocab) float32 logits of ONE sequence ``ids`` (s,), which may
    hold mask tokens, under the block-causal mask (``mask``: another
    (s, s) bool mask in its place, for the tests).

    ``matmul_dtype`` exists for ONE purpose, the control ``TIE_ATOL`` is
    held against (``benchmark/tests/control_blocks.py``): it rounds both
    operands of every weight matmul (projections, router, experts,
    head) to a lower precision. The harness does not pass it."""
    f32 = lambda name: weights[name].astype(jnp.float32)  # noqa: E731
    low = lambda x: _rounded(x, matmul_dtype)             # noqa: E731
    mm = lambda x, name: low(x) @ low(f32(name))          # noqa: E731
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    d = model["head_dim"]
    eps = model["rms_norm_eps"]
    theta = model["rope_theta"]
    s = ids.shape[0]
    seen = block_mask(s, model["block_length"]) if mask is None else mask
    with jax.default_matmul_precision("highest"):
        x = weights["model.embed_tokens.weight"][ids].astype(jnp.float32)
        for i in range(model["num_hidden_layers"]):
            p = f"model.layers.{i}."
            a = _rms_norm(x, f32(p + "input_layernorm.weight"), eps)
            q = mm(a, p + "self_attn.q_proj.weight").reshape(s, heads, d)
            k = mm(a, p + "self_attn.k_proj.weight").reshape(s, kv_heads, d)
            v = mm(a, p + "self_attn.v_proj.weight").reshape(s, kv_heads, d)
            q = _rms_norm(q, f32(p + "self_attn.q_norm.weight"), eps)
            k = _rms_norm(k, f32(p + "self_attn.k_norm.weight"), eps)
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
            o = o.transpose(1, 0, 2, 3).reshape(s, heads * d)
            x = x + mm(o, p + "self_attn.o_proj.weight")
            m = _rms_norm(x, f32(p + "post_attention_layernorm.weight"), eps)
            x = x + _experts(m, weights, p, model, low)
        x = _rms_norm(x, f32("model.norm.weight"), eps)
        return mm(x, "lm_head.weight")


def reveal(logits_block, masked):
    """The published choice for one denoising forward that reveals one
    position: ``logits_block`` (B, vocab) of a block, ``masked`` (B,)
    bool. Returns ``(position, token, log_conf, top)``: of the masked
    positions the one with the largest max-probability (the first on a
    tie), its argmax token; and, for the comparison, per position the
    log of that max-probability and the top logit, (B,) each."""
    logits_block = logits_block.astype(jnp.float32)
    top = jnp.max(logits_block, axis=-1)
    log_conf = top - jax.nn.logsumexp(logits_block, axis=-1)
    position = jnp.argmax(jnp.where(masked, log_conf, -jnp.inf))
    token = jnp.argmax(logits_block, axis=-1)[position]
    return position, token, log_conf, top
