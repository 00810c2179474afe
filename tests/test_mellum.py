"""Mellum 2 trained on one chip's share of an expert group: the model
against the benchmark's plain reference, the windowed training flash
kernels against a dense masked twin (and the blocks their grids visit),
the differentiable dropless expert layer against a dense one-hot layer
(with garbage behind every row a grouped matmul leaves uncomputed), the
expert shares against the uncut layer, YaRN's frequencies, and the train
step's device-side counters. CPU, tiny sizes; Pallas in interpret mode.
"""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.jit import functional_call
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.models import MellumConfig, MellumForCausalLM
from paddle_tpu.models import mellum

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _reference():
    path = os.path.join(REPO, "benchmark", "refs",
                        "mellum2-12b-a2.5b-instruct.py")
    spec = importlib.util.spec_from_file_location("mellum_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _seeded(model, seed=0, std=0.3):
    """Every parameter drawn from the seed (large, so that routing and
    attention are far from uniform); norm scales near one."""
    params, _ = model.raw_state()
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, v) in enumerate(sorted(params.items())):
        k = jax.random.fold_in(key, i)
        if v.ndim >= 2:
            out[name] = std * jax.random.normal(k, v.shape, jnp.float32)
        else:
            out[name] = 1.0 + 0.1 * jax.random.normal(k, v.shape, jnp.float32)
    model.load_raw_state(out)
    return out


# ------------------------------------------------- the model vs the reference
@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_model_loss_and_grads_match_the_reference(monkeypatch, held):
    first, count = held
    monkeypatch.setattr(dropless, "TRAIN_CHUNK_TOKENS", 16)
    cfg = MellumConfig.tiny(router_experts=16, num_experts=count,
                            first_expert=first)
    model = MellumForCausalLM(cfg)
    params = _seeded(model)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 33)), jnp.int32)

    def program(p):
        return functional_call(model, p, ids[:, :-1], ids[:, 1:])

    loss_p, grads_p = jax.value_and_grad(program)(params)
    ref = _reference()
    spec = dataclasses.asdict(cfg)
    loss_r, grads_r = jax.value_and_grad(
        lambda w: ref.loss(w, ids, spec))(params)
    np.testing.assert_allclose(float(loss_p), float(loss_r), rtol=1e-5)
    for name in params:
        g_p, g_r = np.asarray(grads_p[name]), np.asarray(grads_r[name])
        err = np.linalg.norm(g_p - g_r) / max(np.linalg.norm(g_r), 1e-30)
        assert err < 1e-4, (name, err)
    # the balancing loss is in both: without it the two differ
    no_aux = dict(spec, router_aux_loss_coef=0.0)
    assert abs(float(ref.loss(params, ids, no_aux)) - float(loss_p)) > 1e-4


def test_reference_controls_move_the_loss():
    """Both controls of the cell's comparison change what the reference
    computes: 8-bit operands, and every layer full attention."""
    cfg = MellumConfig.tiny()
    model = MellumForCausalLM(cfg)
    params = _seeded(model)
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (1, 33)), jnp.int32)
    ref, spec = _reference(), dataclasses.asdict(cfg)
    base = float(ref.loss(params, ids, spec))
    assert abs(float(ref.loss(params, ids, spec,
                              matmul_dtype=jnp.float8_e4m3fn)) - base) > 1e-3
    assert abs(float(ref.loss(params, ids, spec, all_full=True)) - base) \
        > 1e-3


def test_reference_groups_cover_every_parameter():
    ref = _reference()
    model = MellumForCausalLM(MellumConfig.tiny())
    groups = {ref.group_of(n) for n, _ in model.named_parameters()}
    assert groups == set(ref.GRAD_RTOL)


# -------------------------------------------------------------- YaRN
def test_yarn_inverse_frequencies_against_the_formula():
    rope = dict(rope_type="yarn", rope_theta=500000, factor=16,
                original_max_position_embeddings=8192, beta_fast=32,
                beta_slow=1, attention_factor=1.2772588722239782)
    inv, scale = mellum.rope_parameters(rope, 128)
    d, theta = 128, 500000.0

    def dim_of(turns):
        return d * math.log(8192 / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = max(math.floor(dim_of(32)), 0), min(math.ceil(dim_of(1)), d - 1)
    want = []
    for i in range(d // 2):
        base = theta ** (2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append((1 / base) * (1 - ramp) + (1 / (16 * base)) * ramp)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    assert scale == pytest.approx(1.2772588722239782)
    # the attention factor the paper gives when none is published
    _, derived = mellum.rope_parameters(
        {k: v for k, v in rope.items() if k != "attention_factor"}, 128)
    assert derived == pytest.approx(0.1 * math.log(16) + 1.0)
    # plain RoPE for the window layers
    plain, one = mellum.rope_parameters(
        {"rope_type": "default", "rope_theta": 500000}, 128)
    np.testing.assert_allclose(
        np.asarray(plain), 1 / theta ** (np.arange(0, d, 2) / d), rtol=1e-6)
    assert one == 1.0


def test_yarn_matches_transformers():
    torch = pytest.importorskip("torch")
    mru = pytest.importorskip("transformers.modeling_rope_utils")
    rope = dict(rope_type="yarn", factor=16,
                original_max_position_embeddings=8192, beta_fast=32,
                beta_slow=1, attention_factor=1.2772588722239782)

    class Cfg:
        rope_theta = 500000
        hidden_size = 2304
        num_attention_heads = 32
        head_dim = 128
        max_position_embeddings = 131072
        rope_scaling = rope

    want, want_scale = mru._compute_yarn_parameters(Cfg(), torch.device("cpu"))
    inv, scale = mellum.rope_parameters(dict(rope, rope_theta=500000), 128)
    np.testing.assert_allclose(np.asarray(inv), want.numpy(), rtol=1e-6)
    assert scale == pytest.approx(want_scale)


# ------------------------------------------------ windowed training flash
def _qkv(s, d=128, h=4, hkv=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (h, s, d), jnp.float32),
            jax.random.normal(ks[1], (hkv, s, d), jnp.float32),
            jax.random.normal(ks[2], (hkv, s, d), jnp.float32),
            jax.random.normal(ks[3], (h, s, d), jnp.float32))


@pytest.mark.parametrize("window", [64, 128, 200, 512, 600])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_windowed_flash_fwd_dq_dkv_match_dense(window, blocks):
    """Windows under, at and over a block, at and over the sequence
    (512): the forward and the three gradients of the kernels against
    the dense masked twin."""
    s, (bq, bk) = 512, blocks
    q, k, v, do = _qkv(s)

    def kern(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, n_heads=4,
                                  n_kv_heads=2, window=window, block_q=bq,
                                  block_k=bk)

    def dense(q, k, v):
        return fa.flash_attention_ref(q, k, v, causal=True, n_heads=4,
                                      n_kv_heads=2, window=window)

    out, vjp = jax.vjp(kern, q, k, v)
    want, vjp_want = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, exp in zip(vjp(do), vjp_want(do)):
        np.testing.assert_allclose(got, exp, atol=2e-5)


def test_windowed_flash_lane_replicated_stats_match_dense():
    """The forward that keeps its statistics lane-replicated
    (``FLAGS_flash_compact_stats`` off) walks the same band."""
    from paddle_tpu import flags
    q, k, v, do = _qkv(512)
    was = flags.get_flag("flash_compact_stats")
    flags.set_flags({"flash_compact_stats": not was})
    try:
        out, vjp = jax.vjp(lambda *a: fa.flash_attention(
            *a, causal=True, n_heads=4, n_kv_heads=2, window=200,
            block_q=128, block_k=128), q, k, v)
        got = (out,) + vjp(do)
    finally:
        flags.set_flags({"flash_compact_stats": was})
    want, vjp_want = jax.vjp(lambda *a: fa.flash_attention_ref(
        *a, causal=True, n_heads=4, n_kv_heads=2, window=200), q, k, v)
    for a, b in zip(got, (want,) + vjp_want(do)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_window_none_is_the_causal_kernel_bit_for_bit():
    q, k, v, do = _qkv(512)
    kw = dict(causal=True, n_heads=4, n_kv_heads=2, block_q=128,
              block_k=128)

    def run(**extra):
        out, vjp = jax.vjp(lambda *a: fa.flash_attention(*a, **kw, **extra),
                           q, k, v)
        return [np.asarray(t) for t in (out,) + vjp(do)]

    plain = run()
    for same in (run(window=None), run(window=512), run(window=4096)):
        for a, b in zip(plain, same):
            assert np.array_equal(a, b)
    assert not np.array_equal(plain[0], run(window=256)[0])


class _Recorded(Exception):
    pass


def _grids_and_maps(s, d, window, h=32, hkv=4):
    """The (name, grid, index maps) of the three pallas calls of a
    windowed forward + backward at these shapes, recorded instead of
    run."""
    calls = []

    def fake(kernel, *, grid, in_specs, out_specs, out_shape, name, **kw):
        calls.append((name, grid, in_specs))

        def run(*args):
            shapes = out_shape if isinstance(out_shape, (list, tuple)) \
                else [out_shape]
            outs = [jnp.zeros(o.shape, o.dtype) for o in shapes]
            return outs if isinstance(out_shape, (list, tuple)) else outs[0]
        return run

    q = jax.ShapeDtypeStruct((h, s, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((hkv, s, d), jnp.bfloat16)
    orig = fa.pl.pallas_call
    fa.pl.pallas_call = fake
    try:
        jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, n_heads=h, n_kv_heads=hkv,
            window=window).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
            q, kv, kv)
    finally:
        fa.pl.pallas_call = orig
    return calls


def test_windowed_grids_visit_the_band_alone():
    """At s 8,192 and W 1,024 (the cell's), from the grids and the index
    maps the three kernels are given: every (query block, key block)
    pair a grid reaches overlaps the band, and the pairs reached are
    exactly the band's own."""
    s, w = 8192, 1024
    calls = {name: (grid, specs) for name, grid, specs in
             _grids_and_maps(s, 128, w)}
    assert set(calls) == {"flash_fwd_window", "flash_bwd_dq_window",
                          "flash_bwd_dkv_window"}
    bq, bk = fa.flash_tiling(s, s, 128, 2)
    n_q, n_k = s // bq, s // bk

    def in_band(i, j):              # some query of block i sees a key of j
        q_lo, q_hi, k_lo, k_hi = i * bq, i * bq + bq - 1, j * bk, j * bk + bk - 1
        return k_lo <= q_hi and k_hi > q_lo - w

    band = {(i, j) for i in range(n_q) for j in range(n_k) if in_band(i, j)}
    for name in ("flash_fwd_window", "flash_bwd_dq_window"):
        grid, specs = calls[name]
        assert grid[1:] == (n_q, fa.band_steps(s, bq, bk, w)[0])
        seen = {(i, int(specs[1].index_map(0, i, j)[1]))
                for i in range(grid[1]) for j in range(grid[2])}
        assert seen == band, name
        assert grid[1] * grid[2] < n_q * n_k / 2     # not the triangle
    grid, specs = calls["flash_bwd_dkv_window"]
    assert grid[1] == n_k and grid[3] == fa.band_steps(s, bq, bk, w)[1]
    seen = {(int(specs[0].index_map(0, j, 0, i)[1]), j)
            for j in range(grid[1]) for i in range(grid[3])}
    assert seen == band


def test_dispatch_table_sends_8k_to_the_kernel():
    assert fa.resolve_dispatch(8192) == "flash"


# ---------------------------------------------- the differentiable layer
def _garbage_grouped_matmul():
    """``ragged_dot`` with NaN in every row past the groups' sum, in the
    product AND in the gradient of its rows: what upstream's kernel
    leaves there is whatever the buffer held."""
    @jax.custom_vjp
    def gmm(x, w, sizes):
        out = jax.lax.ragged_dot(x, w, sizes,
                                 preferred_element_type=jnp.float32)
        live = jnp.arange(x.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    def fwd(x, w, sizes):
        return gmm(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        live = jnp.arange(x.shape[0]) < jnp.sum(sizes)
        g = jnp.where(live[:, None], g, 0.0)    # a cotangent there is none
        _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
            a, b, sizes, preferred_element_type=jnp.float32), x, w)
        dx, dw = vjp(g)
        return (jnp.where(live[:, None], dx, jnp.nan).astype(x.dtype),
                dw.astype(w.dtype), None)

    gmm.defvjp(fwd, bwd)
    return gmm


def _dense_layer(x, router_w, gate_up, down, top_k, first):
    """Every held expert over every token, weighted by the top k."""
    e = router_w.shape[1]
    probs = jax.nn.softmax(x @ router_w, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_e, e) * top_p[..., None], axis=1)
    width = down.shape[1]
    y = jnp.zeros_like(x)
    for i in range(gate_up.shape[0]):
        gu = x @ gate_up[i]
        y = y + weight[:, first + i, None] * (
            (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ down[i])
    chose = jnp.sum(jax.nn.one_hot(top_e, e), axis=1)
    balance = e * jnp.sum(jnp.mean(chose, 0) * jnp.mean(probs, 0))
    return y, balance


def _layer_weights(t=48, h=32, f=16, e=16, count=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (t, h)),
            0.5 * jax.random.normal(ks[1], (h, e)),
            0.3 * jax.random.normal(ks[2], (count, h, 2 * f)),
            0.3 * jax.random.normal(ks[3], (count, f, h)),
            jax.random.normal(ks[4], (t, h)))


@pytest.mark.parametrize("chunk", [None, 16])
@pytest.mark.parametrize("held", [(0, 16), (4, 6)])
def test_dropless_train_grads_match_dense_with_garbage_rows(
        monkeypatch, chunk, held):
    first, count = held
    monkeypatch.setattr(dropless, "grouped_matmul", _garbage_grouped_matmul())
    if chunk:
        monkeypatch.setattr(dropless, "TRAIN_CHUNK_TOKENS", chunk)
    x, rw, gu, dn, c = _layer_weights(count=count)

    def ours(x, rw, gu, dn):
        y, counts, bal = dropless.dropless_moe_train(
            x, rw, gu, dn, top_k=4, first=first)
        return jnp.sum(y * c) + bal, (y, counts)

    def dense(x, rw, gu, dn):
        y, bal = _dense_layer(x, rw, gu, dn, 4, first)
        return jnp.sum(y * c) + bal, y

    (l1, (y1, counts)), g1 = jax.value_and_grad(ours, argnums=(0, 1, 2, 3),
                                                has_aux=True)(x, rw, gu, dn)
    (l2, y2), g2 = jax.value_and_grad(dense, argnums=(0, 1, 2, 3),
                                      has_aux=True)(x, rw, gu, dn)
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-4)
    for a, b in zip(g1, g2):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    top_e = jax.lax.top_k(jax.nn.softmax(x @ rw), 4)[1]
    want = np.bincount(np.asarray(top_e).ravel(), minlength=16)
    assert counts.sum(0).tolist() == want[first:first + count].tolist()


def test_four_expert_shares_add_up_to_the_uncut_layer(monkeypatch):
    """Outputs AND gradients: the four chips' shares of 16 experts, 4
    each, sum to the layer that holds all 16 (the balancing loss, which
    every chip computes alike from the whole router, left out)."""
    monkeypatch.setattr(dropless, "TRAIN_CHUNK_TOKENS", 16)
    x, rw, gu, dn, c = _layer_weights(count=16)

    def part(x, rw, gu, dn, first):
        y, _, _ = dropless.dropless_moe_train(x, rw, gu, dn, top_k=4,
                                              first=first)
        return jnp.sum(y * c), y

    (whole, y), g = jax.value_and_grad(part, argnums=(0, 1, 2, 3),
                                       has_aux=True)(x, rw, gu, dn, 0)
    ys, gx, grw, ggu, gdn = 0.0, 0.0, 0.0, [], []
    for share in range(4):
        sl = slice(4 * share, 4 * share + 4)
        (_, y_s), (a, b, d1, d2) = jax.value_and_grad(
            part, argnums=(0, 1, 2, 3), has_aux=True)(
                x, rw, gu[sl], dn[sl], 4 * share)
        ys, gx, grw = ys + y_s, gx + a, grw + b
        ggu.append(d1)
        gdn.append(d2)
    np.testing.assert_allclose(ys, y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gx, g[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(grw, g[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(jnp.concatenate(ggu), g[2], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(jnp.concatenate(gdn), g[3], rtol=1e-5,
                               atol=1e-5)


def test_dropless_serving_forward_unchanged_by_the_train_path():
    """The served forward and the training one agree where both apply."""
    x, rw, gu, dn, _ = _layer_weights(count=8)
    y_serve, counts = dropless.dropless_moe(x, rw, gu, dn, top_k=4, first=4)
    y_train, per_chunk, _ = dropless.dropless_moe_train(x, rw, gu, dn,
                                                        top_k=4, first=4)
    np.testing.assert_allclose(y_serve, y_train, rtol=1e-5, atol=1e-5)
    assert per_chunk.sum(0).tolist() == np.asarray(counts).tolist()


# ------------------------------------------------------- the train step
def test_train_step_keeps_the_expert_counters(monkeypatch):
    from paddle_tpu import observability as obs
    from paddle_tpu.hapi import TrainStep
    monkeypatch.setattr(dropless, "TRAIN_CHUNK_TOKENS", 32)
    cfg = MellumConfig.tiny(router_experts=16, num_experts=8,
                            first_expert=8)
    paddle.seed(3)
    model = MellumForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = TrainStep(model, opt)

    def pairs():
        snap = obs.registry().snapshot()["metrics"]
        return {k: sum(s["value"] for s in snap[f"train_attn_pairs_{k}"]
                       ["series"]) for k in ("window", "full")
                if f"train_attn_pairs_{k}" in snap}

    before = pairs()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 65)).astype(np.int32)
    losses = [float(step(ids[:, :-1], ids[:, 1:])) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    c = step.counters()
    assert int(c["moe_assignments"]) == int(c["moe_expert_hist"].sum()) > 0
    # each (layer, chunk, expert) visit with a row: 128 tokens are 4
    # chunks of 32, so 4 layers x 4 chunks x 8 experts a step at most
    assert 0 < int(c["moe_experts_touched"]) <= 3 * 4 * 4 * 8
    after = pairs()
    window = 3 * 2 * 3 * mellum.attention_pairs(64, cfg.sliding_window)
    full = 3 * 2 * 1 * mellum.attention_pairs(64, None)
    assert after["window"] - before.get("window", 0) == window
    assert after["full"] - before.get("full", 0) == full
    assert mellum.attention_pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024
