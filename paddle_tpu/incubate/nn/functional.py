"""Fused-op surface (reference: python/paddle/incubate/nn/functional/).

The reference exposes hand-fused CUDA kernels here; the TPU build maps each
to either a Pallas kernel (paddle_tpu/kernels/) or a composition XLA fuses on
its own. Names match the reference so user code ports directly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ... import flags
from ...core.tensor import Tensor, apply_op, _val
from ...nn import functional as F


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, **kwargs):
    """reference: paddle/phi/kernels/fusion/gpu rms_norm fused op. On TPU the
    residual-add + rms_norm composition is one XLA fusion; a Pallas variant
    exists for the long-row case (paddle_tpu/kernels/rms_norm.py)."""
    if flags.snapshot(("use_pallas",)).use_pallas and flags.is_tpu_backend():
        try:
            from ...kernels.rms_norm import rms_norm_pallas
            h = x
            if bias is not None:
                h = h + bias
            if residual is not None:
                h = h + residual
            out = apply_op("fused_rms_norm",
                           lambda a, w: rms_norm_pallas(a, w, epsilon),
                           h, norm_weight)
            if norm_bias is not None:
                out = out + norm_bias
            return (out, h) if residual is not None else out
        except Exception:
            pass
    h = x
    if bias is not None:
        h = h + bias
    if residual is not None:
        h = h + residual
    out = F.rms_norm(h, norm_weight, epsilon)
    if norm_bias is not None:
        out = out + norm_bias
    return (out, h) if residual is not None else out


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5, begin_norm_axis=-1,
                     bias=None, residual=None, **kwargs):
    h = x
    if bias is not None:
        h = h + bias
    if residual is not None:
        h = h + residual
    out = F.layer_norm(h, h.shape[begin_norm_axis:] if begin_norm_axis >= 0
                       else h.shape[-1], norm_weight, norm_bias, epsilon)
    return (out, h) if residual is not None else out


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0):
    """reference: fused_rotary_position_embedding CUDA op. Layout [B, S, H, D]."""

    def rope_one(t, sin_, cos_):
        if t is None:
            return None
        d = t.shape[-1]
        if use_neox_rotary_style:
            t1, t2 = jnp.split(t, 2, axis=-1)
            rot = jnp.concatenate([-t2, t1], axis=-1)
            return t * cos_ + rot * sin_
        t_even = t[..., 0::2]
        t_odd = t[..., 1::2]
        out_even = t_even * cos_[..., 0::2] - t_odd * sin_[..., 0::2]
        out_odd = t_odd * cos_[..., 0::2] + t_even * sin_[..., 0::2]
        return jnp.stack([out_even, out_odd], axis=-1).reshape(t.shape)

    qv, kv, vv = _val(q), _val(k) if k is not None else None, _val(v) if v is not None else None
    seq_axis = 0 if time_major else 1
    s = qv.shape[seq_axis]
    d = qv.shape[-1]
    if (sin is None or cos is None) and position_ids is not None:
        # Compute sin/cos straight from the positions (no table + gather):
        # decode-time positions exceed the current chunk length, so a
        # chunk-sized table would be out of range — and the direct compute
        # is the better TPU program anyway (VPU math beats HBM gathers).
        pid = _val(position_ids).astype(jnp.float32)       # [B, S]
        inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = pid[..., None] * inv                        # [B, S, D/2]
        emb = jnp.concatenate([freqs, freqs], axis=-1)      # [B, S, D]
        sin_b = jnp.sin(emb)[:, :, None, :]
        cos_b = jnp.cos(emb)[:, :, None, :]
    elif sin is None or cos is None:
        pos = jnp.arange(s, dtype=jnp.float32)
        inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = jnp.outer(pos, inv)
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        sin_v, cos_v = jnp.sin(emb), jnp.cos(emb)
        sin_b = sin_v[None, :, None, :] if not time_major else sin_v[:, None, None, :]
        cos_b = cos_v[None, :, None, :] if not time_major else cos_v[:, None, None, :]
    else:
        sin_v, cos_v = _val(sin), _val(cos)
        sin_v = sin_v.reshape(s, d) if sin_v.ndim > 2 else sin_v
        cos_v = cos_v.reshape(s, d) if cos_v.ndim > 2 else cos_v
        sin_b = cos_b = None  # set below
    if sin_b is None and position_ids is not None:
        pid = _val(position_ids)
        sin_v = jnp.take(sin_v, pid, axis=0)  # [B, S, D]
        cos_v = jnp.take(cos_v, pid, axis=0)
        sin_b = sin_v[:, :, None, :]
        cos_b = cos_v[:, :, None, :]
    elif sin_b is None:
        if time_major:
            sin_b = sin_v[:, None, None, :]
            cos_b = cos_v[:, None, None, :]
        else:
            sin_b = sin_v[None, :, None, :]
            cos_b = cos_v[None, :, None, :]

    outs = []
    for t in (q, k, v):
        if t is None:
            outs.append(None)
            continue
        outs.append(apply_op("fused_rope",
                             lambda a: rope_one(a, sin_b.astype(a.dtype),
                                                cos_b.astype(a.dtype)), t))
    return tuple(outs)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train"):
    return F.dropout(x, p=p, training=training, mode=mode) + y


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None, ln_scale=None,
                                           ln_bias=None, dropout_rate=0.5,
                                           ln_epsilon=1e-5, training=True):
    """reference: paddle/phi/kernels/fusion/gpu/fused_bias_dropout_residual_
    layer_norm — one XLA fusion here."""
    h = x if bias is None else x + bias
    h = F.dropout(h, p=dropout_rate, training=training)
    h = h + residual
    return F.layer_norm(h, h.shape[-1], ln_scale, ln_bias, ln_epsilon)


def fused_linear(x, weight, bias=None, transpose_weight=False):
    if transpose_weight:
        from ... import ops
        weight = ops.t(weight)
    return F.linear(x, weight, bias)


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation="gelu"):
    from ... import ops
    out = ops.matmul(x, y, transpose_x=trans_x, transpose_y=trans_y)
    if bias is not None:
        out = out + bias
    if activation == "gelu":
        return F.gelu(out)
    if activation == "relu":
        return F.relu(out)
    return out


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False):
    from ... import ops
    out = ops.matmul(x, y, transpose_x=transpose_x, transpose_y=transpose_y)
    return out if bias is None else out + bias


def swiglu(x, y=None):
    return F.swiglu(x, y)


def fused_multi_head_attention(x, qkv_weight, linear_weight, pre_layer_norm=False,
                               pre_ln_scale=None, pre_ln_bias=None, ln_scale=None,
                               ln_bias=None, pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None, attn_mask=None,
                               dropout_rate=0.5, attn_dropout_rate=0.5,
                               ln_epsilon=1e-5, training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, num_heads=None,
                               transpose_qkv_wb=False):
    """reference: paddle/fluid/operators/fused/fused_attention_op.cu.
    Composed from XLA/Pallas pieces; numerics match the reference layout
    (qkv_weight [3, H, D_head, D_model])."""
    from ... import ops

    residual = x
    h = x
    if pre_layer_norm:
        h = F.layer_norm(h, h.shape[-1], pre_ln_scale, pre_ln_bias, pre_ln_epsilon)
    qw = _val(qkv_weight)
    b, s, d = _val(h).shape
    n_heads = qw.shape[1]
    head_dim = qw.shape[2]

    def qkv_fn(a, w, *bias_):
        qkv = jnp.einsum("bsd,thed->bsthe", a, w)  # t in {q,k,v}
        if bias_:
            qkv = qkv + _val(qkv_bias).reshape(1, 1, 3, n_heads, head_dim)
        return qkv

    args = (h, qkv_weight) + ((qkv_bias,) if qkv_bias is not None else ())
    qkv = apply_op("fused_qkv", qkv_fn, *args)
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    if cache_kv is not None:
        k = ops.concat([cache_kv[0], k], axis=1)
        v = ops.concat([cache_kv[1], v], axis=1)
    out = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0, training=training)
    out = out.reshape([b, s, n_heads * head_dim])
    out = F.linear(out, linear_weight, linear_bias)
    out = F.dropout(out, p=dropout_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1], ln_scale, ln_bias, ln_epsilon)
    return out


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu", ln1_epsilon=1e-5,
                      ln2_epsilon=1e-5, pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1):
    """reference: paddle/fluid/operators/fused/fused_feedforward_op.cu."""
    residual = x
    h = x
    if pre_layer_norm:
        h = F.layer_norm(h, h.shape[-1], ln1_scale, ln1_bias, ln1_epsilon)
    h = F.linear(h, linear1_weight, linear1_bias)
    h = getattr(F, activation)(h)
    h = F.dropout(h, p=dropout1_rate, training=training, mode=mode)
    h = F.linear(h, linear2_weight, linear2_bias)
    h = F.dropout(h, p=dropout2_rate, training=training, mode=mode)
    out = residual + h
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1], ln2_scale, ln2_bias, ln2_epsilon)
    return out


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                            linear_weights, linear_biases, ffn_ln_scales,
                            ffn_ln_biases, ffn1_weights, ffn1_biases,
                            ffn2_weights, ffn2_biases, pre_layer_norm=True,
                            epsilon=1e-5, cache_kvs=None, pre_caches=None,
                            rotary_embs=None, time_step=None, attn_mask=None,
                            dropout_rate=0.0, rotary_emb_dims=0,
                            activation="gelu", training=False,
                            mode="upscale_in_train", trans_qkvw=True,
                            ring_id=-1, name=None):
    """Whole-stack fused transformer with KV caches — reference:
    paddle/fluid/operators/fused/fused_multi_transformer_op.cu (SURVEY.md
    §3.5). One call runs all L layers: pre-LN -> qkv -> (rope) -> cache
    attention -> out-proj -> residual -> ffn-LN -> ffn1 -> act -> ffn2 ->
    residual. On TPU the per-layer "fusion" is XLA's job; what this function
    contributes is the reference-shaped weight-list API and the decode cache
    semantics (static ring-buffer caches + traced ``time_step``).

    Weight shapes follow the reference: ``qkv_weights[i]`` is
    (3, num_head, head_dim, embed_dim) when ``trans_qkvw`` else
    (embed_dim, 3, num_head, head_dim); ``cache_kvs[i]`` is
    (2, B, num_head, max_seq, head_dim). ``time_step`` (int scalar, decode
    phase only) is the number of tokens already cached; when ``cache_kvs``
    is given the call returns ``(out, cache_kvs)``.

    Serving fast path: the DECODE phase (s == 1 with caches) dispatches
    through the decode program cache (generation/program_cache.py) as ONE
    cached compiled step with the caches donated — reference in-place
    cache semantics, no per-token retrace and no per-call eager op
    dispatch. ``FLAGS_fused_block_decode=0`` restores the eager chain.
    """
    use_cache = cache_kvs is not None
    xv = _val(x)
    b, s, h = xv.shape

    w = dict(
        ln_scales=[_val(t) for t in ln_scales],
        ln_biases=[_val(t) for t in ln_biases] if ln_biases else [],
        qkv_weights=[_val(t) for t in qkv_weights],
        qkv_biases=[_val(t) for t in qkv_biases] if qkv_biases else [],
        linear_weights=[_val(t) for t in linear_weights],
        linear_biases=[_val(t) for t in linear_biases]
        if linear_biases else [],
        ffn_ln_scales=[_val(t) for t in ffn_ln_scales],
        ffn_ln_biases=[_val(t) for t in ffn_ln_biases]
        if ffn_ln_biases else [],
        ffn1_weights=[_val(t) for t in ffn1_weights],
        ffn1_biases=[_val(t) for t in ffn1_biases] if ffn1_biases else [],
        ffn2_weights=[_val(t) for t in ffn2_weights],
        ffn2_biases=[_val(t) for t in ffn2_biases] if ffn2_biases else [],
    )
    caches = [_val(c) for c in cache_kvs] if use_cache else []
    mask = _val(attn_mask) if attn_mask is not None else None
    rot = (_val(rotary_embs)
           if rotary_embs is not None and rotary_emb_dims > 0 else None)
    ts = (jnp.asarray(_val(time_step), jnp.int32).reshape(())
          if time_step is not None else jnp.int32(0))
    static = dict(pre_layer_norm=pre_layer_norm, epsilon=epsilon,
                  activation=activation, trans_qkvw=trans_qkvw,
                  use_cache=use_cache)

    snap = flags.snapshot(flags.PROGRAM_FLAGS)
    if use_cache and s == 1 and snap.fused_block_decode:
        from ...generation.program_cache import (DecodeKey,
                                                 decode_program_cache)
        # O(1)-per-call key: layer count + exemplar shapes + bias/extra
        # presence. Per-layer shape heterogeneity the key misses is
        # refused by the cached executable's own aval check (a
        # TypeError, never a wrong answer) — hashing every weight leaf
        # per TOKEN is exactly the per-call host overhead this fast path
        # exists to remove.
        sig = (f"L{len(w['qkv_weights'])}:{xv.shape}:{xv.dtype}:"
               f"{caches[0].shape}:{caches[0].dtype}:"
               f"{w['qkv_weights'][0].shape}:{w['ffn1_weights'][0].shape}:"
               f"{[bool(w[k]) for k in sorted(w)]}:"
               f"{mask.shape if mask is not None else None}:"
               f"{rot.shape if rot is not None else None}:"
               f"{sorted(static.items())}")
        key = DecodeKey(kind="fmt_decode", model_sig=sig, batch_bucket=b,
                        page_budget=(caches[0].shape[3],),
                        dtype=str(xv.dtype), flags=snap.as_tuple())

        def builder(note_trace):
            def run(xv, w, caches, ts, mask, rot):
                note_trace()
                return _fmt_forward(xv, w, caches, ts, mask, rot, **static)
            # donate the caches: the decode step then updates them in
            # place (the reference CUDA op's semantics) instead of
            # copying every layer's (2, B, H, T, D) buffer per token
            return jax.jit(run, donate_argnums=(2,))

        fn = decode_program_cache().get(key, builder)
        hid, cache_out = fn(xv, w, caches, ts, mask, rot)
    else:
        hid, cache_out = _fmt_forward(xv, w, caches, ts, mask, rot,
                                      **static)
    out = Tensor(hid.astype(xv.dtype), stop_gradient=True)
    if use_cache:
        return out, [Tensor(c, stop_gradient=True) for c in cache_out]
    return out


def _arg_sig(trees, static) -> str:
    """Structural signature of a pytree of arrays + a static config dict
    (shape/dtype only — values are traced) for decode program keys."""
    import hashlib
    parts = [repr(sorted(static.items()))]
    for leaf in jax.tree_util.tree_leaves(trees):
        parts.append(f"{getattr(leaf, 'shape', ())}:"
                     f"{getattr(leaf, 'dtype', type(leaf).__name__)}")
    return hashlib.md5("|".join(parts).encode()).hexdigest()


def _fmt_forward(xv, w, caches, time_step, attn_mask, rotary_embs, *,
                 pre_layer_norm, epsilon, activation, trans_qkvw,
                 use_cache):
    """fused_multi_transformer's whole-stack forward as a pure function
    of raw arrays — traced once by the decode program cache on the
    serving path, executed eagerly for prefill / no-cache calls."""
    from ...kernels.decode_attention import cached_attention, update_kv_cache

    b, s, h = xv.shape
    cache_out = []
    hid = xv
    for i in range(len(w["qkv_weights"])):
        qkvw = w["qkv_weights"][i]
        if trans_qkvw:          # (3, H, D, E) -> project E -> (3, H, D)
            three, nh, hd, _ = qkvw.shape
        else:
            _, three, nh, hd = qkvw.shape
            qkvw = jnp.transpose(qkvw, (1, 2, 3, 0))
        residual = hid
        ln_in = hid
        if pre_layer_norm:
            ln_in = _ln(hid, w["ln_scales"][i],
                        w["ln_biases"][i] if w["ln_biases"] else None,
                        epsilon)
        qkv = jnp.einsum("bse,nhde->bsnhd", ln_in, qkvw)
        if w["qkv_biases"]:
            qkv = qkv + w["qkv_biases"][i][None, None]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]   # (B,S,H,D)
        if rotary_embs is not None:
            cos_r, sin_r = rotary_embs[0], rotary_embs[1]    # (B, 1, S, D)
            q = _apply_rot(q, cos_r, sin_r)
            k = _apply_rot(k, cos_r, sin_r)
        if use_cache:
            ck = caches[i]                                    # (2,B,H,T,D)
            k_cache = jnp.transpose(ck[0], (0, 2, 1, 3))      # (B,T,H,D)
            v_cache = jnp.transpose(ck[1], (0, 2, 1, 3))
            off = time_step
            k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v, off)
            attn = cached_attention(q, k_cache, v_cache, off + s)
            new_ck = jnp.stack([jnp.transpose(k_cache, (0, 2, 1, 3)),
                                jnp.transpose(v_cache, (0, 2, 1, 3))])
            cache_out.append(new_ck)
        else:
            attn = _causal_sdpa(q, k, v, attn_mask)
        attn = attn.reshape(b, s, nh * hd)
        out = attn @ w["linear_weights"][i]
        if w["linear_biases"]:
            out = out + w["linear_biases"][i]
        hid = residual + out
        if not pre_layer_norm:
            hid = _ln(hid, w["ln_scales"][i],
                      w["ln_biases"][i] if w["ln_biases"] else None,
                      epsilon)

        residual = hid
        ffn_in = hid
        if pre_layer_norm:
            ffn_in = _ln(hid, w["ffn_ln_scales"][i],
                         w["ffn_ln_biases"][i] if w["ffn_ln_biases"]
                         else None, epsilon)
        f1 = ffn_in @ w["ffn1_weights"][i]
        if w["ffn1_biases"]:
            f1 = f1 + w["ffn1_biases"][i]
        f1 = jax.nn.gelu(f1, approximate=True) if activation == "gelu" \
            else jax.nn.relu(f1)
        f2 = f1 @ w["ffn2_weights"][i]
        if w["ffn2_biases"]:
            f2 = f2 + w["ffn2_biases"][i]
        hid = residual + f2
        if not pre_layer_norm:
            hid = _ln(hid, w["ffn_ln_scales"][i],
                      w["ffn_ln_biases"][i] if w["ffn_ln_biases"]
                      else None, epsilon)
    return hid, cache_out


def _ln(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return out.astype(x.dtype)


def _apply_rot(t, cos_r, sin_r):
    # neox-style rotate-half; cos/sin (B, 1, S, D) -> (B, S, 1, D)
    cos_b = jnp.transpose(cos_r, (0, 2, 1, 3)).astype(t.dtype)
    sin_b = jnp.transpose(sin_r, (0, 2, 1, 3)).astype(t.dtype)
    t1, t2 = jnp.split(t, 2, axis=-1)
    rot = jnp.concatenate([-t2, t1], axis=-1)
    return t * cos_b + rot * sin_b


def _causal_sdpa(q, k, v, mask):
    import math as _math
    scale = 1.0 / _math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt)
    if mask is not None:
        s = jnp.where(mask.astype(bool), s, -1e30) if mask.dtype != s.dtype \
            else s + mask
    else:
        sq, sk = s.shape[-2], s.shape[-1]
        tri = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) >= \
            jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(tri, s, -1e30)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vt)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def fused_linear_cross_entropy(hidden, weight, labels, transpose_y=False,
                               ignore_index=-100, chunk_tokens=1024):
    """LM-head matmul + softmax cross-entropy without materializing the full
    (tokens, vocab) f32 logits — the single largest activation in causal-LM
    training (2 x 3GB for GPT-345M at batch 8 x 2048 on one v5e chip).

    TPU-native design: ``lax.map`` over token chunks; each chunk's logits
    come out of the MXU already f32 (preferred_element_type), the per-chunk
    loss reduces immediately, and ``jax.checkpoint`` drops the chunk logits
    so the backward recomputes them chunk-by-chunk. Peak vocab-activation
    memory falls from O(tokens) to O(chunk_tokens). Reference analogue:
    c_softmax_with_cross_entropy_op.cu fuses the same chain for the TP path
    (paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu).

    ``weight``: (H, V), or (V, H) with ``transpose_y=True`` (tied
    embeddings). ``labels`` < 0 or == ignore_index are masked out; returns
    the mean loss over unmasked tokens.
    """
    from ...core.tensor import apply_op

    def fn(hv, wv, lv):
        h_dim = hv.shape[-1]
        h2 = hv.reshape(-1, h_dim)
        l2 = lv.reshape(-1).astype(jnp.int32)
        l2 = jnp.where(l2 == ignore_index, -1, l2)
        n = h2.shape[0]
        k = max(1, -(-n // chunk_tokens))
        pad = k * chunk_tokens - n if n > chunk_tokens else 0
        if n <= chunk_tokens:
            k = 1
        if pad:
            h2 = jnp.concatenate([h2, jnp.zeros((pad, h_dim), h2.dtype)])
            l2 = jnp.concatenate([l2, jnp.full((pad,), -1, l2.dtype)])
        hs = h2.reshape(k, -1, h_dim)
        ls = l2.reshape(k, -1)
        contract = ((1,), (1,)) if transpose_y else ((1,), (0,))

        def chunk_fn(args):
            h_c, l_c = args
            logits = jax.lax.dot_general(
                h_c, wv, (contract, ((), ())),
                preferred_element_type=jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            safe = jnp.clip(l_c, 0, logits.shape[-1] - 1)
            gold = jnp.take_along_axis(logits, safe[:, None], -1)[..., 0]
            return jnp.where(l_c >= 0, lse - gold, 0.0)

        per = jax.lax.map(jax.checkpoint(chunk_fn), (hs, ls))
        count = jnp.maximum(jnp.sum(ls >= 0), 1)
        return jnp.sum(per) / count.astype(jnp.float32)

    return apply_op("fused_linear_cross_entropy", fn, hidden, weight, labels)


def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias,
                 act_type="gelu"):
    """reference: incubate.nn.functional.fused_ec_moe — expert-choice
    style batched-expert FFN: gate (B, S, E) soft-combines E expert
    FFNs run as batched matmuls (MXU-friendly einsum formulation)."""
    import jax
    from ...core.tensor import apply_op

    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}[act_type]

    def fn(xv, gv, w0, b0, w1, b1):
        h = jnp.einsum("bsd,edh->bseh", xv, w0) + b0
        h = act(h)
        out = jnp.einsum("bseh,ehd->bsed", h, w1) + b1
        probs = jax.nn.softmax(gv, axis=-1)
        return jnp.einsum("bsed,bse->bsd", out, probs)
    return apply_op("fused_ec_moe", fn, x, gate, bmm0_weight, bmm0_bias,
                    bmm1_weight, bmm1_bias)


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               sequence_lengths=None, rotary_tensor=None,
                               out_scale=-1, seq_len=1, rotary_emb_dims=0,
                               **kwargs):
    """reference: incubate.nn.functional.masked_multihead_attention — the
    one-token decode attention against a running cache. Maps onto the
    decode path of kernels/decode_attention (static cache, GQA-ready).
    Dispatches through the decode program cache: repeated decode calls at
    a fixed shape run ONE cached compiled program with the cache donated
    (in-place update), instead of re-dispatching the op chain eagerly
    per token (``FLAGS_fused_block_decode=0`` restores eager)."""
    from ...core.tensor import Tensor, _val
    xv = _val(x)
    b = xv.shape[0]
    if cache_kv is None:
        raise ValueError("masked_multihead_attention needs cache_kv")
    ck = _val(cache_kv)                    # (2, B, T, H, D)
    t = ck.shape[2]
    cur = _val(sequence_lengths) if sequence_lengths is not None else t - 1
    cur = jnp.asarray(cur, jnp.int32)

    snap = flags.snapshot(flags.PROGRAM_FLAGS)
    if snap.fused_block_decode:
        from ...generation.program_cache import (DecodeKey,
                                                 decode_program_cache)
        key = DecodeKey(kind="mmha", model_sig=_arg_sig((xv, ck, cur), {}),
                        batch_bucket=b, page_budget=(t,),
                        dtype=str(ck.dtype), flags=snap.as_tuple())

        def builder(note_trace):
            def run(xv, ck, cur):
                note_trace()
                return _mmha_forward(xv, ck, cur)
            return jax.jit(run, donate_argnums=(1,))

        out, new_cache = decode_program_cache().get(key, builder)(
            xv, ck, cur)
    else:
        out, new_cache = _mmha_forward(xv, ck, cur)
    return (Tensor(out), Tensor(new_cache))


def _mmha_forward(xv, ck, cur):
    from ...kernels.decode_attention import cached_attention, update_kv_cache
    b = xv.shape[0]
    h, d = ck.shape[3], ck.shape[4]
    qkv = xv.reshape(b, 1, 3, h, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    kc, vc = update_kv_cache(ck[0], ck[1], k, v, cur)
    out = cached_attention(q, kc, vc, cur + 1)
    return out.reshape(b, h * d), jnp.stack([kc, vc])


def fused_block_decode(x, ln1_weight, q_proj_weight, k_proj_weight,
                       v_proj_weight, out_proj_weight, ln2_weight,
                       gate_proj_weight, up_proj_weight, down_proj_weight,
                       key_cache, value_cache, block_tables, seq_lens,
                       num_heads: int, num_kv_heads: Optional[int] = None,
                       rope_theta: float = 10000.0, epsilon: float = 1e-6):
    """ONE fused transformer-block decode step over the paged KV cache —
    the TPU-native fusion of the chain the reference splits across
    fused_rms_norm + qkv matmuls + fused_rotary_position_embedding +
    block_multihead_attention + out-proj + swiglu:

        x  <- x + attn(rms_norm(x))        (RoPE + paged append/read
        x  <- x + ffn(rms_norm(x))          folded into the same kernel)

    ``x``: (B, hidden) — one token per slot. Linear weights use the
    (in, out) layout; caches/tables as in block_multihead_attention.
    Dispatches to the Pallas kernel on TPU (FLAGS_use_pallas) and to the
    jnp composition elsewhere; gated engine-side by
    ``FLAGS_fused_block_decode``. Returns (out, key_cache, value_cache).
    """
    from ...core.tensor import Tensor, _val
    from ...kernels.fused_block_decode import (BlockDecodeWeights,
                                               fused_block_decode as _fbd)
    w = BlockDecodeWeights(
        ln1=_val(ln1_weight), wq=_val(q_proj_weight), wk=_val(k_proj_weight),
        wv=_val(v_proj_weight), wo=_val(out_proj_weight),
        ln2=_val(ln2_weight), wg=_val(gate_proj_weight),
        wu=_val(up_proj_weight), wd=_val(down_proj_weight))
    out, kp, vp = _fbd(
        _val(x), w, _val(key_cache), _val(value_cache), _val(block_tables),
        _val(seq_lens), num_heads=num_heads,
        num_kv_heads=num_kv_heads or num_heads, rope_theta=rope_theta,
        epsilon=epsilon)
    return (Tensor(out, stop_gradient=True),
            Tensor(kp, stop_gradient=True), Tensor(vp, stop_gradient=True))


def variable_length_memory_efficient_attention(
        query, key, value, seq_lens=None, kv_seq_lens=None, mask=None,
        scale=None, causal=False, pre_cache_length=0):
    """reference: incubate.nn.functional.variable_length_memory_efficient
    _attention — varlen attention without materialized (S, S) scores.
    TPU-native: the flash kernel's segment-id masking IS the varlen
    mechanism; ragged lengths become per-row segment ids."""
    from ...core.tensor import Tensor, _val
    from ...kernels.flash_attention import flash_attention_bshd
    q, k, v = _val(query), _val(key), _val(value)
    # (B, H, S, D) reference layout -> (B, S, H, D)
    qb = jnp.swapaxes(q, 1, 2)
    kb = jnp.swapaxes(k, 1, 2)
    vb = jnp.swapaxes(v, 1, 2)
    b, s = qb.shape[0], qb.shape[1]
    if seq_lens is not None:
        lens = _val(seq_lens).reshape(-1)
        pos = jnp.arange(s)[None, :]
        seg = jnp.where(pos < lens[:, None], 0, 1).astype(jnp.int32)
    else:
        seg = None
    try:
        out = flash_attention_bshd(qb, kb, vb, segment_ids=seg,
                                   causal=causal, sm_scale=scale)
    except NotImplementedError:
        from ...kernels.decode_attention import cached_attention_dense
        out = cached_attention_dense(qb, kb, vb, s, sm_scale=scale)
    return Tensor(jnp.swapaxes(out, 1, 2))


def weight_quantize(x, algo="weight_only_int8", group_size=-1):
    """reference: paddle.nn.quant.weight_quantize (surfaced through
    incubate for the LLM serving path) — per-channel (or grouped)
    abs-max int8/int4 weight quantization.

    Returns (quantized_weight int8, scales float32). ``x`` is the f32/
    bf16 weight (in_features, out_features); scales are per output
    channel, or per (group, out) block when ``group_size`` > 0.
    int4 packs two nibbles per int8 byte along the in dimension
    (reference packing); on TPU the win is HBM bandwidth — the matmul
    dequantizes into bf16 registers (weight_only_linear)."""
    from ...core.tensor import Tensor, _val
    w = _val(x).astype(jnp.float32)
    if algo not in ("weight_only_int8", "weight_only_int4"):
        raise ValueError(f"unsupported weight_quantize algo {algo!r}")
    k, n = w.shape
    if group_size > 0:
        if k % group_size:
            raise ValueError(f"in_features {k} not divisible by "
                             f"group_size {group_size}")
        wg = w.reshape(k // group_size, group_size, n)
        amax = jnp.max(jnp.abs(wg), axis=1)              # (G, N)
    else:
        amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)  # (1, N)
    qmax = 127.0 if algo == "weight_only_int8" else 7.0
    scale = jnp.maximum(amax, 1e-8) / qmax
    if group_size > 0:
        q = jnp.clip(jnp.round(wg / scale[:, None, :]), -qmax, qmax)
        q = q.reshape(k, n)
    else:
        q = jnp.clip(jnp.round(w / scale), -qmax, qmax)
    q = q.astype(jnp.int8)
    if algo == "weight_only_int4":
        # pack two int4 values (rows 2i, 2i+1) into one int8 byte
        if k % 2:
            raise ValueError("int4 packing needs an even in_features")
        lo = q[0::2] & 0x0F
        hi = (q[1::2] & 0x0F) << 4
        q = (lo | hi).astype(jnp.int8)
    return (Tensor(q, stop_gradient=True),
            Tensor(scale.reshape(-1, n) if group_size > 0
                   else scale.reshape(n), stop_gradient=True))


def _dequantize_weight(q, scale, weight_dtype: str, group_size: int,
                       out_dtype):
    """Shared unpack + scale for weight_only_linear / nn.quant
    weight_dequantize — ONE packing convention (int4: low nibble = even
    row, arithmetic-shift sign extension)."""
    if weight_dtype in ("int4", "weight_only_int4"):
        lo = (q << 4).astype(jnp.int8) >> 4        # sign-extend low nibble
        hi = q >> 4                                # arithmetic shift: high
        w = jnp.zeros((q.shape[0] * 2, q.shape[1]), jnp.int8)
        w = w.at[0::2].set(lo).at[1::2].set(hi)
    elif weight_dtype in ("int8", "weight_only_int8"):
        w = q
    else:
        raise ValueError(f"unsupported weight dtype {weight_dtype!r}")
    # scale in f32, then cast once: bf16 weights keep the matmul on the
    # fast MXU path while the scales stay accurate
    wf = w.astype(jnp.float32)
    if group_size > 0:
        g = wf.shape[0] // group_size
        wf = (wf.reshape(g, group_size, -1) * scale[:, None, :]).reshape(
            wf.shape)
    else:
        wf = wf * scale.reshape(1, -1)
    return wf.astype(out_dtype)


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype="int8", group_size=-1):
    """reference: paddle.nn.quant.weight_only_linear (the
    weight_only_gemm CUDA kernel). TPU-native: dequantize into the
    matmul — XLA fuses the int8→bf16 convert and per-channel scale into
    the MXU feed, so the weight lives in HBM at 1/2 (int8) or 1/4
    (int4) the bytes, the GEMM runs in the ACTIVATION dtype (bf16 on
    the serving path) and accumulates in f32.

    Dispatches through ``apply_op`` so ACTIVATIONS and bias stay
    differentiable (the int8 weight is grad-free by dtype): adapter/
    LoRA-style training over a frozen int8 backbone works."""
    from ...core.tensor import apply_op

    def fn(xv, qw, bv, scale):
        wf = _dequantize_weight(qw, scale, weight_dtype, group_size,
                                xv.dtype)
        out = jnp.matmul(xv, wf, preferred_element_type=jnp.float32)
        if bv is not None:
            out = out + bv
        return out.astype(xv.dtype)

    return apply_op("weight_only_linear", fn, x, weight, bias, weight_scale)


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_encoder,
                              seq_lens_decoder, seq_lens_this_time,
                              block_tables, **kwargs):
    """reference: paddle.incubate.nn.functional.block_multihead_attention
    (paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu) —
    the block(page)-table serving attention. TPU-native subset over
    kernels/paged_attention:

      - decode phase (``seq_lens_this_time`` all 1): the token writes into
        its page and attends through the block-table Pallas kernel;
      - prefill phase (encoder lengths > 0, decoder lengths 0): prompt
        self-attention + page writes.

    ``qkv``: (B, S, 3, Hkv==H, D) packed (the reference packs q/k/v; MHA
    layout — GQA callers use paged_scaled_dot_product_attention
    directly). ``key_cache``/``value_cache``: (Hkv, num_pages, page, D)
    pools. Returns ``(out, key_cache, value_cache)`` with out (B, S, H*D).
    Options the CUDA kernel fuses (rope embeddings, cache-quant scales,
    shift/smooth) are not folded here — pass pre-roped qkv; unsupported
    kwargs raise rather than silently no-op."""
    # reference signature carries many fused options with non-None
    # defaults; only a NON-default value asks for unfolded behavior
    _ref_defaults = {"max_seq_len": -1, "block_size": None,
                     "use_neox_style": False, "use_neox_rotary_style": False,
                     "quant_round_type": 1, "quant_max_bound": 127.0,
                     "quant_min_bound": -127.0, "out_scale": -1,
                     "out_shift": None, "out_smooth": None,
                     "compute_dtype": "default", "rope_theta": 10000.0}
    unsupported = sorted(
        k for k, v in kwargs.items()
        if v is not None and v != _ref_defaults.get(k, None))
    if unsupported:
        raise NotImplementedError(
            "block_multihead_attention TPU subset does not fold "
            f"{unsupported} — apply rope/quant/offsets outside the op")
    from ...kernels.paged_attention import PagedDecodeState

    import numpy as _np
    try:
        this = _np.asarray(_val(seq_lens_this_time))
        enc = _np.asarray(_val(seq_lens_encoder))
    except Exception as e:   # traced lengths: the phase cannot be checked
        raise NotImplementedError(
            "block_multihead_attention needs CONCRETE seq_lens (the host-"
            "facing serving loop); inside jit use "
            "paged_scaled_dot_product_attention directly") from e
    qkv_t = qkv if isinstance(qkv, Tensor) else Tensor(qkv)
    b, s = qkv_t.shape[0], qkv_t.shape[1]
    # uniform-phase contract (the subset this wrapper supports): ALL rows
    # prefill (this==S, enc>0) or ALL rows decode one token (this==1).
    # Inactive rows (this==0) or mixed batches would silently scribble
    # into pool pages — refuse loudly instead.
    if (enc > 0).all() and (this == s).all():
        pass                      # prefill phase
    elif (enc == 0).all() and (this == 1).all() and s == 1:
        pass                      # decode phase
    else:
        raise NotImplementedError(
            "block_multihead_attention TPU subset handles uniform batches "
            "only (all-prefill or all-decode with every row active); for "
            "ragged/mixed scheduling drive ServingEngine or the paged "
            "pieces directly")
    q = qkv_t[:, :, 0]
    k = qkv_t[:, :, 1]
    v = qkv_t[:, :, 2]
    dec = _val(seq_lens_decoder)
    # the reference's phase encoding: encoder lens set during prefill,
    # decoder lens set during decode
    lens = jnp.where(jnp.asarray(enc) > 0, 0, jnp.asarray(dec))
    state = PagedDecodeState(key_cache, value_cache, block_tables,
                             lens.astype(jnp.int32))
    out, state = F.paged_scaled_dot_product_attention(q, k, v, state)
    h, d = out.shape[2], out.shape[3]
    return (out.reshape([b, s, h * d]), state.k_pages, state.v_pages)
