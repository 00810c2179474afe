"""chip_smoke.py, rehearsed: the same phases the chip runs, at tiny size on
the CPU (Pallas kernels in interpret mode or their jnp twins), through the
function the script exposes — the script itself has no CPU switch and
refuses any platform but ``tpu``.
"""

import dataclasses
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402

TINY = dataclasses.replace(
    chip_smoke.FULL, on_chip=False, train_model="gpt_tiny",
    train_shape=(4, 64), train_steps=2, serve_batch=4, page_size=8,
    max_seq_len=64, prompt_lens=(6, 6, 20), new_tokens=3,
    prefill_chunk=8,
    llama=dict(vocab_size=256, hidden_size=64, num_attention_heads=4,
               intermediate_size=128, max_position_embeddings=128),
    fused_layers=2, fused_prompt_lens=(5, 9), fused_new_tokens=4,
    hybrid_layers=2, hybrid_shape=(4, 32), hybrid_steps=2,
    granite_prompt_len=27, granite_new_tokens=4,
    granite=dict(
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
                   max_position_embeddings=512, tie_word_embeddings=True),
        program=dict(config_class="GraniteHybridConfig",
                     model_class="GraniteHybridForCausalLM"),
        serve=dict(max_batch=2, page_size=8, max_seq_len=64,
                   prefill_chunk=16)),
    sdar_prompt_len=27, sdar_new_tokens=9,
    sdar=dict(
        name="sdar-30b-a3b-chat", arch="sdar_moe", dtype="float32",
        model=dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, moe_intermediate_size=32, num_experts=8,
                   num_experts_per_tok=2, norm_topk_prob=True,
                   max_position_embeddings=512, rms_norm_eps=1e-6,
                   rope_theta=1000000, block_length=4, mask_token_id=250,
                   initializer_range=0.3),
        program=dict(config_class="SDARMoEConfig",
                     model_class="SDARMoEForCausalLM"),
        serve=dict(max_batch=2, page_size=8, max_seq_len=64,
                   prefill_chunk=16)),
    trinity_prompt_len=61, trinity_new_tokens=6,
    trinity=dict(
        name="trinity-mini", arch="afmoe", dtype="float32",
        model=dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                   moe_intermediate_size=32, num_hidden_layers=5,
                   num_dense_layers=1, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, num_experts=8,
                   num_experts_per_tok=2, num_shared_experts=1,
                   score_func="sigmoid", route_norm=True, route_scale=2.826,
                   n_group=1, topk_group=1, sliding_window=16,
                   layer_types=["sliding_attention"] * 3
                   + ["full_attention", "sliding_attention"],
                   mup_enabled=True, max_position_embeddings=512,
                   rms_norm_eps=1e-5, rope_theta=10000,
                   initializer_range=0.3),
        program=dict(config_class="AfmoeConfig",
                     model_class="AfmoeForCausalLM"),
        serve=dict(max_batch=2, page_size=8, max_seq_len=96,
                   prefill_chunk=8)),
    mellum=dict(vocab_size=128, hidden_size=64, moe_intermediate_size=32,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_experts=4,
                router_experts=8, first_expert=4, num_experts_per_tok=2,
                sliding_window=16),
    mellum_shape=(2, 64))


@pytest.fixture
def cache_env(monkeypatch):
    """The cache variables unset, and restored whatever a test does."""
    for name in ("JAX_COMPILATION_CACHE_DIR",
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(name, "restore-me")
        monkeypatch.delenv(name)


def test_one_chip_phases_at_tiny_size(monkeypatch):
    from paddle_tpu.incubate.distributed.models.moe import dropless
    # the expert layer in chunks, as FULL's two of 4,096 tokens
    monkeypatch.setattr(dropless, "TRAIN_CHUNK_TOKENS", 32)
    lines = chip_smoke.run_phases(TINY)
    assert [ln["phase"] for ln in lines] == [
        "device", "train", "serve", "fused_decode", "hybrid", "blocks",
        "window", "moe_train"]
    device, train, serve, fused, hybrid, blocks, window, moe = lines
    assert device["platform"] == "cpu" and device["peak_flops"] is None
    assert train["traces"] == 1 and train["losses"][-1] < train["losses"][0]
    # no Pallas custom call can exist on the CPU — and none is claimed
    assert train["flash_custom_call"] is False
    assert serve["chunk_dispatches"] > 0 and serve["near_ties"] == 0
    assert set(serve["pallas_custom_calls"]) >= {"prefill_chunk",
                                                 serve["decode_kind"]}
    assert fused["decode_kind"] == "decode_fused"
    assert fused["tokens_equal_unfused"] == "exact"     # f32 on the CPU
    # one full chunk, one padded chunk, then decode, against the reference
    assert hybrid["chunk_dispatches"] == 2 and hybrid["near_ties"] == 0
    assert len(hybrid["tokens"]) == 4 and hybrid["statuses"] == "OK"
    assert hybrid["ssm_update_custom_call"] is False     # the jnp twin
    # 24 whole-block tokens through a full and a padded chunk, 3 known in
    # the first block: (1 + 4 + 4) denoising and 3 commit forwards
    assert blocks["chunk_dispatches"] == 2 and blocks["near_ties"] == 0
    assert blocks["forwards"] == 12 and blocks["reveals"] == 9
    assert len(blocks["tokens"]) == 9 and blocks["step_kind"] == "block_step"
    assert not any(blocks["kernels"].values())           # the jnp twins
    # 61 tokens through eight chunks of 8, then decode: 9 pages in all,
    # of which the window layers' row never held more than 3 of its 4
    assert window["chunk_dispatches"] == 8 and window["near_ties"] == 0
    assert window["span_pages"] == 9 and window["window_row_bound"] == 4
    assert window["window_pages_most"] == 3
    assert window["window_pages_released"] == 6
    assert len(window["tokens"]) == 6 and window["statuses"] == "OK"
    assert window["largest_gap"] == 0.0                 # f32 on the CPU
    # two steps on one batch: the loss falls, no retrace; no kernel on
    # the CPU, and none claimed
    assert moe["traces"] == 1 and moe["losses"][-1] < moe["losses"][0]
    assert not any(moe["kernels"].values())
    assert moe["moe_assignments"] > 0


def test_four_chip_phase_on_virtual_devices():
    (line,) = chip_smoke.run_phases(TINY, chips=4)
    assert line["phase"] == "hybrid_dp2_mp2"
    assert line["mesh"] == {"dp": 2, "mp": 2}
    assert line["losses_dp2_mp2"] == pytest.approx(line["losses_one_chip"],
                                                   rel=1e-4)
    assert line["device0_param_share"] == pytest.approx(0.5, abs=0.05)
    assert line["batch_row_ranges"] == [[0, 2], [2, 4]]
    assert "all-reduce" in line["collectives"]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_the_cpu(argv, cache_env, capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.main(argv)
    assert '"ok"' not in capsys.readouterr().out        # no result line


def test_cache_dir_honours_the_environment(cache_env, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert bench.use_compile_cache() == "/some/dir"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"


def test_cache_dir_default_is_fixed_in_the_checkout(cache_env):
    want = os.path.join(REPO, ".cache", "xla")
    assert bench.use_compile_cache() == want
    assert bench.use_compile_cache() == want    # no pid, time or temp name
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".cache/" in f.read().split()


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_nothing_else_sets_a_cache_dir(script):
    with open(os.path.join(REPO, script)) as f:
        source = f.read()
    assert "jax_compilation_cache_dir" not in source
    assert "tempfile" not in source and "getpid" not in source
    # one setdefault, in the one helper both scripts share
    assert source.count('"JAX_COMPILATION_CACHE_DIR"') == (
        2 if script == "bench.py" else 0)
