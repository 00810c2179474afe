"""The hybrid (Mamba-2 + attention) configuration's own pieces of the
benchmark: its counts against the program's model, its weights' law,
its readers on a synthetic window, and its cell at toy size through the
closed loop on the CPU."""

import json
import math
import os

import numpy as np
import pytest

from benchmark.lib import hybrid, registry, system

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY = dict(
    name="granite-4.0-h-micro", arch="granite_hybrid", dtype="float32",
    model=dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, shared_intermediate_size=96,
               layer_types=["mamba", "attention", "mamba", "mamba"],
               attention_multiplier=0.0625, embedding_multiplier=1.0,
               logits_scaling=8.0, residual_multiplier=0.22,
               mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
               mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
               mamba_chunk_size=16, rms_norm_eps=1e-5,
               max_position_embeddings=512, tie_word_embeddings=True,
               initializer_range=0.02),
    program=dict(config_class="GraniteHybridConfig",
                 model_class="GraniteHybridForCausalLM"),
    builders=dict(closed="serve_hybrid"),
    serve=dict(max_batch=8, page_size=16, max_seq_len=128, prefill_chunk=32))
CLOSED = dict(loop="closed", clients=8, requests=24,
              prompt_len=dict(median=32, sigma=0.5, lo=16, hi=64, levels=4,
                              multiple=8),
              output_len=dict(median=8, sigma=0.5, lo=4, hi=16, levels=4,
                              multiple=1))


@pytest.fixture(scope="module", autouse=True)
def _registered():
    registry.load_all()


def _config_file():
    with open(os.path.join(HERE, "configs", "granite-4.0-h-micro.json")) as fh:
        return json.load(fh)


def test_config_file_keeps_the_published_config_whole():
    cfg = _config_file()
    assert cfg["reduced"] == [] and set(cfg["assumed"]) >= {
        "state_dtype", "in_proj_order", "weights"}
    # the flat copy and the harness's group say the same
    assert {k: cfg[k] for k in cfg["model"]} == cfg["model"]
    assert cfg["model"]["num_hidden_layers"] == 40 \
        == len(cfg["model"]["layer_types"])
    assert cfg["model"]["vocab_size"] == 100352
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "granite-4.0-h-micro")
    assert cfg["model"] == row["config"] and cfg["source"] == row["source_url"]


def test_arch_counts_equal_the_models_own():
    cfg = _config_file()
    sizes = system.sizes_of(cfg)
    _, model = system.lazy_model(cfg)
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert sizes["n_params"] == n == 3_191_396_096
    two_d = sum(int(np.prod(p.shape)) for name, p in model.named_parameters()
                if len(p.shape) == 2 and "conv_weight" not in name)
    assert sizes["matmul_params"] == two_d
    spec = model.cache_spec()
    pages = [e for e in spec if len(e) == 2 and isinstance(e[0], int)]
    assert sizes["layers"] == len(pages) == 4
    assert (sizes["kv_heads"], sizes["head_dim"]) == pages[0] == (8, 64)
    assert sizes["recurrent_layers"] == 36
    state = [e for e in spec if e not in pages][0]
    per_slot = 36 * (int(np.prod(state.ssm_shape)) * 4
                     + int(np.prod(state.conv_shape)) * 2)
    assert sizes["state_bytes_per_slot"] == per_slot == 76_437_504
    assert sizes["ssm_update_bytes"] == 2 * 2_097_152 + 3 * 16_384 + 1_024


def test_redrawn_ssm_weights_follow_the_published_law():
    import jax.numpy as jnp
    sysm = hybrid.build_serve_hybrid(TINY, CLOSED, 2**31 + 9, 1)
    model = TINY["model"]
    params = dict(sysm.model.raw_state()[0])
    seen = 0
    for i, kind in enumerate(model["layer_types"]):
        p = f"model.layers.{i}.mamba."
        if kind != "mamba":
            assert p + "A_log" not in sysm.weights
            continue
        seen += 1
        for name in ("A_log", "dt_bias", "D", "conv_weight", "conv_bias"):
            # the model the engine serves and the weights the reference
            # is given hold the same values
            np.testing.assert_array_equal(np.asarray(params[p + name]),
                                          np.asarray(sysm.weights[p + name]))
        a = np.exp(np.asarray(sysm.weights[p + "A_log"], np.float64))
        assert a.min() >= 1.0 - 1e-3 and a.max() <= 16.0 + 1e-3
        dt = np.log1p(np.exp(np.asarray(sysm.weights[p + "dt_bias"],
                                        np.float64)))      # softplus
        assert dt.min() >= 1e-3 * 0.99 and dt.max() <= 1e-1 * 1.01
        assert np.all(np.asarray(sysm.weights[p + "D"]) == 1.0)
        w = np.asarray(sysm.weights[p + "conv_weight"])
        assert np.abs(w).max() <= 0.5 and w.std() > 0.2
        # every decay a token lies in (0, 1), most of them well inside
        decay = np.exp(-dt * a)
        assert 0.15 < decay.min() and decay.max() < 1.0
    assert seen == 3
    # layer by layer and seed by seed the draws differ
    assert not np.array_equal(sysm.weights["model.layers.0.mamba.A_log"],
                              sysm.weights["model.layers.2.mamba.A_log"])
    other = hybrid.redraw_ssm_weights(sysm.weights, model, 5)
    assert not np.array_equal(other["model.layers.0.mamba.A_log"],
                              sysm.weights["model.layers.0.mamba.A_log"])
    assert other["model.embed_tokens.weight"] is \
        sysm.weights["model.embed_tokens.weight"]
    assert sysm.weights["model.layers.0.mamba.A_log"].dtype == jnp.float32


def _ctx(**over):
    sizes = hybrid.granite_hybrid_sizes(_config_file()["model"])
    kernel = ('%ssm_decode_update.7 = (f32[32,32,128,128]{3,2,1,0}, '
              'f32[32,32,1,128]{3,2,1,0}) custom-call(%p0, %p1), '
              'custom_call_target="tpu_custom_call"')
    other = "%fusion.3 = bf16[32,8512]{1,0} fusion(%p2), kind=kOutput"
    ops = {"/device:TPU:0": [(kernel, 0.0, 4e6), (other, 4e6, 6e6),
                             (kernel, 10e6, 4e6), (other, 14e6, 6e6)]}
    ctx = dict(sizes=sizes, device_ops=ops, busy_s=0.020,
               peaks=dict(hbm_bytes_per_s=819e9),
               scalars=dict(serving_decode_steps=2.0,
                            serving_decode_rows=48.0,
                            serving_decode_live_tokens=24000.0))
    ctx.update(over)
    return ctx


def test_readers_on_a_synthetic_window():
    read = registry.READERS
    ctx = _ctx()
    need = 48 * 36 * 4_244_480
    assert math.isclose(read["ssm_update_roofline"](ctx),
                        100 * need / 819e9 / 0.008)
    assert math.isclose(read["ssm_update_share"](ctx), 40.0)
    floor = (2 * 2 * 3_191_396_096 + 48 * 2 * 76_437_504
             + 24000 * 2 * 4 * 8 * 64 * 2)
    assert math.isclose(read["hybrid_decode_floor"](ctx),
                        100 * floor / 819e9 / 0.020)
    # nothing to read: a program without the kernel, the counters or
    # the sizes gives None, never an error
    bare = _ctx(device_ops={"/device:TPU:0": ctx["device_ops"][
        "/device:TPU:0"][1::2]})
    assert read["ssm_update_roofline"](bare) is None
    assert read["ssm_update_share"](bare) is None
    for name in ("ssm_update_roofline", "ssm_update_share",
                 "hybrid_decode_floor"):
        assert read[name](_ctx(device_ops={}, busy_s=None)) is None
        assert read[name](_ctx(scalars={})) is None or \
            name == "ssm_update_share"
    gpt = dict(layers=24, kv_heads=16, head_dim=64, n_params=1)
    assert read["ssm_update_roofline"](_ctx(sizes=gpt)) is None
    assert read["hybrid_decode_floor"](_ctx(sizes=gpt)) is None


def test_gauge_reader_reads_a_level_and_nothing_where_there_is_none():
    from paddle_tpu import observability as obs
    read = registry.READERS["gauge_value"]
    assert read({}, "no_such_gauge_of_the_program") is None
    g = obs.registry().gauge("bench_test_level", "a test's gauge",
                             labels=("replica",))
    g.labels(replica="0").set(2.5e9)
    g.labels(replica="1").set(0.5e9)
    assert math.isclose(read({}, "bench_test_level", scale=1e-9), 3.0)


def test_the_cell_at_toy_size_through_the_closed_loop():
    from benchmark.lib import loops
    sysm = hybrid.build_serve_hybrid(TINY, CLOSED, 11, 1)
    out = loops.closed_loop(sysm, 11, 3.0, False)
    assert out.correct and out.failed == 0 and out.attempted > 0
    notes = out.notes
    assert not notes["wrong"] and notes["checked_tokens"] == 32
    assert len(notes["near_ties"]) < 8
    s = out.scalars
    assert s["compiles"] == 0 and s["program_cache_traces"] == 0
    assert s["serving_state_resets"] >= out.attempted
    assert s["serving_decode_rows"] > 0
    # prompts of 40 and more pass the 32-token chunk: state is carried
    # from chunk to chunk inside the window
    assert s["serving_prefill_tokens"] > 0
    assert max(notes["warmed_prompt_lens"]) > 32
    assert registry.READERS["gauge_value"]({}, "serving_state_bytes") \
        == sysm.engine._state.nbytes
