"""The three loops end to end at a tiny size on the CPU: control flow,
counts and the reference check, never a time. The real sizes run on the
chip only (``run.py`` refuses anything else)."""

import math

import pytest

from benchmark.lib import loops, registry, system

TINY_GPT = dict(
    name="gpt3-345m", arch="gpt2", dtype="bfloat16",
    model=dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=256,
               max_position_embeddings=128, layer_norm_epsilon=1e-5,
               tie_word_embeddings=True),
    program=dict(config_class="GPTConfig", model_class="GPTForCausalLM"),
    serve=dict(max_batch=8, page_size=16, max_seq_len=128, prefill_chunk=32))
TINY_LLAMA = dict(
    name="mistral-7b", arch="llama", dtype="bfloat16",
    model=dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, max_position_embeddings=128,
               rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=64,
               tie_word_embeddings=False),
    program=dict(config_class="LlamaConfig", model_class="LlamaForCausalLM"))
OPEN = dict(loop="open", rate_per_s=6.0, sample_share=0.8,
            prompt_len=dict(median=24, sigma=0.8, lo=8, hi=64, levels=6,
                            multiple=8),
            output_len=dict(median=8, sigma=0.5, lo=4, hi=16, levels=6,
                            multiple=1))
CLOSED = dict(loop="closed", clients=8, requests=24,
              prompt_len=dict(median=48, sigma=0.3, lo=40, hi=64, levels=4,
                              multiple=8),
              output_len=dict(median=8, sigma=0.5, lo=4, hi=16, levels=4,
                              multiple=1))


@pytest.fixture(scope="module", autouse=True)
def _registered():
    registry.load_all()


@pytest.mark.parametrize("config", [TINY_GPT, TINY_LLAMA],
                         ids=["gpt2", "llama"])
def test_train_loop_agrees_with_the_plain_reference(config):
    traffic = dict(loop="train", batch=4, seq_len=64)
    sysm = system.build_train(config, traffic, 2**31 + 5, 1)
    out = loops.train_loop(sysm, 2**31 + 5, 1.0, False)
    assert out.correct and out.failed == 0 and out.attempted >= 2
    assert out.notes["loss_diff"] < 5e-3
    assert math.isclose(out.notes["reference_loss"],
                        math.log(config["model"]["vocab_size"]), rel_tol=0.02)
    assert out.scalars["tokens"] == out.attempted * 4 * 64
    assert out.scalars["step_traces"] == 0 and out.scalars["compiles"] == 0
    assert len(out.series["step_ms"]) == out.attempted
    assert out.scalars["window_s"] >= 1.0


def test_train_loop_on_a_dp2_mp2_mesh_of_virtual_devices():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    config = dict(TINY_LLAMA, program=dict(
        TINY_LLAMA["program"],
        annotate="paddle_tpu.models.llama.annotate_llama_tp"))
    traffic = dict(loop="train", batch=4, seq_len=64, mesh=dict(dp=2, mp=2))
    sysm = system.build_train_mesh(config, traffic, 3, 4)
    assert len(sysm.devices) == 4
    spec = sysm.step.params["llama.layers.0.self_attn.q_proj.weight"]
    assert "mp" in str(spec.sharding.spec)
    out = loops.train_loop(sysm, 3, 1.0, False)
    assert out.correct and out.notes["loss_diff"] < 5e-3
    with pytest.raises(ValueError):
        system.build_train_mesh(config, traffic, 3, 1)


@pytest.mark.parametrize("traffic", [OPEN, CLOSED], ids=["open", "closed"])
def test_serving_loops(traffic):
    sysm = system.build_serve(TINY_GPT, traffic, 7, 1)
    run = registry.LOOPS[traffic["loop"]]
    out = run(sysm, 7, 3.0, False)
    assert out.correct and out.failed == 0 and out.attempted > 0
    assert not out.notes["wrong"] and out.notes["checked_tokens"] == 32
    s = out.scalars
    assert s["compiles"] == 0 and s["program_cache_traces"] == 0
    assert s["output_tokens"] > s["decode_tokens"] > 0
    assert len(out.series["ttft_ms"]) == out.attempted
    assert min(out.series["ttft_ms"]) > 0
    assert len(out.series["queue_wait_ms"]) > 0
    if traffic["loop"] == "open":
        assert out.notes["requests_offered"] == 18       # 6/s x 3 s
        assert len(out.series["gen_late_ms"]) == 18
        assert min(out.series["gen_late_ms"]) >= 0
    else:
        # saturated: more requests finish than the table holds once
        assert s["serving_requests_submitted"] >= out.attempted + 8 - 1
