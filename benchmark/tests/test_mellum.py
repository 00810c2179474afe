"""``mellum2-12b-a2.5b-instruct.train-8k``'s harness on the CPU: the
configuration's file against its own published copy and the issue's
arithmetic, the builder and loop end to end at a tiny size (control
flow, counters and the gradient check, never a time), and the readers on
hand-made counters and a hand-made trace."""

import json
import math
import os

import pytest

from benchmark.lib import mellum, registry

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "mellum2-12b-a2.5b-instruct"


def _config():
    with open(os.path.join(HERE, "configs", NAME + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module", autouse=True)
def _registered():
    registry.load_all()


def test_configuration_is_the_published_one_cut_as_stated():
    c = _config()
    m = c["model"]
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (4, 16, 24576)
    assert c["published"]["num_hidden_layers"] == 28
    assert (c["published"]["num_experts"], c["published"]["vocab_size"]) \
        == (64, 98304)
    # the model group: the held share, the router's published width, one
    # whole period of the published layer pattern
    assert (m["num_experts"], m["router_experts"], m["first_expert"]) \
        == (16, 64, 0)
    assert m["layer_types"] == c["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    for key in ("hidden_size", "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "rms_norm_eps",
                "num_experts_per_tok", "sliding_window", "rope_parameters",
                "norm_topk_prob", "max_position_embeddings"):
        assert m[key] == c[key], key
    for key in ("qk_norm", "mtp", "router_aux_loss_coef",
                "window_convention", "sliding_window_keys"):
        assert key in c["assumed"]


def test_sizes_are_the_issues_arithmetic():
    sizes = registry.ARCHS["mellum"](_config()["model"])
    # 4 layers of 120,476,160, embedding and head slices, final norm
    assert sizes["n_params"] == 595_153_152
    assert sizes["n_params"] * 14 / 1e9 == pytest.approx(8.33, abs=0.01)
    assert (sizes["window_layers"], sizes["full_layers"]) == (3, 1)


def test_readers_on_hand_made_counters_and_trace():
    sizes = registry.ARCHS["mellum"](_config()["model"])
    window = 4 * 3 * (1024 * 1025 // 2 + 7168 * 1024)
    full = 4 * (8192 * 8193 // 2)
    steps, assigned = 10.0, 10 * 4 * 65536.0
    ms = 1e6                                    # ns a millisecond
    ops = {"/device:TPU:0": [
        ("%jvp_flash_fwd_window_.3 = bf16[128,8192,128] custom-call()",
         0, 50 * ms),
        ("%transpose_jvp_flash_bwd_dkv__.1 = f32[16,8192,128] custom-call()",
         60 * ms, 50 * ms),
        ("%gmm.2 = f32[32768,1792] custom-call()", 120 * ms, 200 * ms),
        ("%tgmm.4 = bf16[16,2304,1792] custom-call()", 330 * ms, 100 * ms),
        ("%fusion.1 = f32[8] fusion()", 440 * ms, 10 * ms)]}
    ctx = dict(scalars=dict(train_attn_pairs_window=steps * window,
                            train_attn_pairs_full=steps * full,
                            moe_assignments=assigned,
                            moe_experts_touched=10 * 4 * 8 * 16.0,
                            steps=steps, tokens=steps * 32768,
                            window_s=20.0),
               device_ops=ops, busy_s=0.41, sizes=sizes, chips=1,
               traffic=dict(batch=4, seq_len=8192),
               peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
               notes={})
    flash = registry.READERS["window_flash_roofline"](ctx)
    need = 14 * 128 * 32 * steps * (window + full) / 197e12
    assert flash == pytest.approx(100 * need / 0.1)
    expert = registry.READERS["expert_train_roofline"](ctx)
    assert expert == pytest.approx(
        100 * 18 * 2304 * 896 * assigned / 197e12 / 0.3)
    assert ctx["notes"]["expert_train_bound"] == "compute"
    assert registry.READERS["expert_train_share"](ctx) \
        == pytest.approx(100 * 0.3 / 0.41)
    mfu = registry.READERS["moe_train_mfu"](ctx)
    want = mellum.step_flops(sizes, steps * 32768, assigned,
                             steps * (window + full)) / 20.0 / 197e12
    assert mfu == pytest.approx(100 * want)
    # a program without the counters or kernels: nothing, no error
    bare = dict(ctx, scalars=dict(steps=steps, tokens=1.0, window_s=20.0),
                device_ops={"/device:TPU:0": ops["/device:TPU:0"][-1:]})
    for name in ("window_flash_roofline", "expert_train_roofline",
                 "expert_train_share", "moe_train_mfu"):
        assert registry.READERS[name](bare) is None


def _tiny(monkeypatch):
    """The cell's configuration at a tiny size, the expert layer in two
    chunks as the cell's eight."""
    from paddle_tpu.incubate.distributed.models.moe import dropless
    monkeypatch.setattr(dropless, "TRAIN_CHUNK_TOKENS", 64)
    c = _config()
    return dict(c, model=dict(
        c["model"], vocab_size=128, hidden_size=64, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, router_experts=8, first_expert=4,
        num_experts_per_tok=2, sliding_window=16))


def test_builder_and_loop_end_to_end_tiny(monkeypatch):
    """The whole set-up and window at a tiny size, in bf16: the first
    step is judged and the verdict rests on its numbers; the counters
    come out of the window as deltas."""
    traffic = dict(loop="train_moe", batch=2, seq_len=64)
    sysm = registry.BUILDERS["train_moe"](_tiny(monkeypatch), traffic,
                                          2**31 + 7, 1)
    out = registry.LOOPS["train_moe"](sysm, 2**31 + 7, 1.0, False)
    n = out.notes
    for key in ("grad_errors", "update_errors", "update_vs_reference"):
        assert set(n[key]) == set(sysm.ref.GRAD_RTOL), key
        assert all(math.isfinite(e) for e in n[key].values()), key
    # the step's own update is AdamW's first step of its own gradient
    assert max(n["update_errors"].values()) < sysm.ref.UPDATE_RTOL / 10
    assert out.correct == (n["loss_diff"] <= n["loss_atol"]
                           and not n["over"])
    assert n["reference_loss"] == pytest.approx(math.log(128), rel=0.05)
    assert sysm.initial is None and sysm.ref_grads is None  # let go
    s = out.scalars
    assert s["tokens"] == out.attempted * 128 and s["step_traces"] == 0
    assert s["train_attn_pairs_window"] == out.attempted * 2 * 3 * (
        16 * 17 // 2 + 48 * 16)
    assert 0 < s["moe_assignments"] <= out.attempted * 4 * 128 * 2
    assert s["expert_load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("fault", ["unchanged", "doubled", "no_gradient"])
def test_the_first_step_judge_catches_a_planted_fault(monkeypatch, fault):
    """The timed step's state after its first call, spoiled three ways:
    the weights left as they were (the update reads 1), an update twice
    AdamW's, and a gradient that never reached the optimizer (its first
    moment zero): each fails the reference's limits."""
    import jax

    from benchmark.lib.system import autocast
    traffic = dict(loop="train_moe", batch=2, seq_len=64)
    sysm = registry.BUILDERS["train_moe"](_tiny(monkeypatch), traffic, 5, 1)
    with autocast():
        first_loss = float(sysm.step(sysm.stage(sysm.first_ids)))
    sound = mellum.judge_first_step(sysm)
    assert mellum.verdict(sysm.ref, first_loss, sysm.ref_loss,
                          sound["grad_errors"],
                          sound["update_errors"])["correct"]
    state = sysm.step.opt_state
    p0 = {k: jax.numpy.asarray(v, "float32")
          for k, v in sysm.initial.items()}
    if fault == "unchanged":
        state["master"] = p0
    elif fault == "doubled":
        state["master"] = {k: 2 * state["master"][k] - p0[k] for k in p0}
    else:
        state["slots"] = {k: dict(v, moment1=0 * v["moment1"])
                          for k, v in state["slots"].items()}
    bad = mellum.judge_first_step(sysm)
    judged = mellum.verdict(sysm.ref, first_loss, sysm.ref_loss,
                            bad["grad_errors"], bad["update_errors"])
    assert not judged["correct"]
    if fault == "unchanged":
        assert all(e == pytest.approx(1.0, abs=1e-6)
                   for e in bad["update_errors"].values())


@pytest.mark.parametrize("control", ["low_precision", "window_off"])
def test_controls_fail_the_limits_tiny(monkeypatch, control):
    """``control_train_moe.py``'s path at a tiny size: the reference's
    variant in the program's place fails the cell's limits."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import system
    tiny = _tiny(monkeypatch)
    ref = system.load_reference(tiny["name"])
    _, model = system.lazy_model(tiny)
    weights = system.make_weights(model, 7)
    ids = np.random.default_rng(0).integers(0, 128, (2, 65)).astype(np.int32)
    loss_r, grads_r = mellum.reference_gradients(ref, weights, ids,
                                                 tiny["model"])
    kw = (dict(matmul_dtype=jnp.float8_e4m3fn) if control == "low_precision"
          else dict(all_full=True))
    loss, grads = mellum.reference_gradients(ref, weights, ids,
                                             tiny["model"], **kw)
    errors = mellum.gradient_errors(ref, grads, grads_r)
    assert set(errors) == set(ref.GRAD_RTOL)
    assert not mellum.verdict(ref, loss, loss_r, errors)["correct"]
