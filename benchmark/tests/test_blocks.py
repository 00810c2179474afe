"""The block-diffusion expert configuration's own pieces of the
benchmark: its counts against the program's model and the issue's
arithmetic, its file against the catalog, its traffic table, its readers
on a synthetic window, and its cell at toy size through the
``closed_blocks`` loop on the CPU."""

import json
import math
import os

import numpy as np
import pytest

from benchmark.lib import blocks, registry, system
from benchmark.lib import traffic as traffic_lib

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "sdar-30b-a3b-chat"

TINY = dict(
    name=NAME, arch="sdar_moe", dtype="float32",
    model=dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, norm_topk_prob=True,
               max_position_embeddings=512, rms_norm_eps=1e-6,
               rope_theta=1000000, block_length=4, mask_token_id=250,
               initializer_range=0.3),
    program=dict(config_class="SDARMoEConfig",
                 model_class="SDARMoEForCausalLM"),
    builders=dict(closed_blocks="serve"),
    serve=dict(max_batch=8, page_size=16, max_seq_len=128, prefill_chunk=32))
CLOSED = dict(loop="closed_blocks", clients=8, requests=24,
              prompt_len=dict(median=32, sigma=0.5, lo=16, hi=64, levels=4,
                              multiple=8),
              output_len=dict(median=12, sigma=0.5, lo=4, hi=24, levels=4,
                              multiple=4),
              schedule_seed=5)


@pytest.fixture(scope="module", autouse=True)
def _registered():
    registry.load_all()


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def test_config_file_keeps_every_published_width():
    cfg = _load("configs", NAME + ".json")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert set(cfg["assumed"]) >= {
        "block_length", "denoising_steps", "reveal_rule", "sampling",
        "mask_token_id", "prompt_masking", "qk_norm", "weights"}
    assert "64 concurrent sequences" in cfg["deployment"]
    # the flat copy and the harness's group say the same
    assert {k: cfg[k] for k in cfg["model"]} == cfg["model"]
    m = cfg["model"]
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"]) == (2048, 32, 4, 128)
    assert (m["num_experts"], m["num_experts_per_tok"],
            m["moe_intermediate_size"], m["vocab_size"]) \
        == (128, 8, 768, 151936)
    assert m["num_hidden_layers"] == 6 and m["block_length"] == 4
    assert 0 <= m["mask_token_id"] < m["vocab_size"]
    assert cfg["serve"] == dict(max_batch=64, page_size=64, max_seq_len=1280)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"}
    assert row["config"]["num_hidden_layers"] == 48


def test_arch_counts_equal_the_models_own_and_the_issues_arithmetic():
    cfg = _load("configs", NAME + ".json")
    sizes = system.sizes_of(cfg)
    _, model = system.lazy_model(cfg)
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert sizes["n_params"] == n == 4_361_055_744          # 8.72 GB in bf16
    assert round(2 * n / 1e9, 2) == 8.72
    per_layer = (n - 2 * 151936 * 2048 - 2048) // 6
    assert per_layer == 623_120_640                         # 623.1 M a layer
    assert sizes["expert_params_per_layer"] == 128 * 4_718_592 == 603_979_776
    assert sizes["forward_params"] == n - 151936 * 2048
    matrices = sum(int(np.prod(p.shape))
                   for name, p in model.named_parameters()
                   if len(p.shape) >= 2 and "embed_tokens" not in name)
    assert sizes["matmul_params"] == matrices
    assert model.cache_spec() == [(sizes["kv_heads"], sizes["head_dim"])] * 6
    assert sizes["layers"] == 6 and sizes["block_length"] == 4
    from benchmark.lib import flops
    assert flops.kv_bytes_per_token(sizes) == 6 * 2048      # 2 KB a layer
    assert blocks.expert_weight_bytes(sizes) == 9_437_184    # of 128
    assert blocks.expert_activation_bytes(sizes) == 4096 + 3072 + 8192


def test_traffic_table():
    t = _load("traffic", "blocks-closed.json")
    cfg = _load("configs", NAME + ".json")
    assert (t["loop"], t["clients"], t["requests"]) \
        == ("closed_blocks", 64, 192)
    assert t["clients"] == cfg["serve"]["max_batch"]
    rows = traffic_lib.schedule(t, 1, 50.0)
    assert len(rows) == 192
    assert [(r.prompt_len, r.output_len) for r in rows] == \
        [(r.prompt_len, r.output_len)
         for r in traffic_lib.schedule(t, 2**31 + 77, 50.0)]   # one order
    prompts = sorted({r.prompt_len for r in rows})
    outputs = sorted({r.output_len for r in rows})
    assert len(prompts) <= 8 and len(outputs) <= 8
    assert all(n % 32 == 0 and 64 <= n <= 512 for n in prompts)
    assert all(n % 4 == 0 and 128 <= n <= 768 for n in outputs)
    assert max(r.prompt_len + r.output_len for r in rows) \
        <= cfg["serve"]["max_seq_len"]
    # 7 tokens in 10 are generated
    gen = sum(r.output_len for r in rows)
    assert 0.6 < gen / (gen + sum(r.prompt_len for r in rows)) < 0.8
    mask = cfg["model"]["mask_token_id"]
    for seed in (1, 2**31 + 77):
        for r in rows[:48]:
            ids = blocks.block_prompt(cfg["model"], seed, r.index,
                                      r.prompt_len)
            assert len(ids) == r.prompt_len and ids.max() < mask


def _ctx(**over):
    sizes = blocks.sdar_moe_sizes(_load("configs", NAME + ".json")["model"])
    kernel = ('%gmm.7 = f32[2048,1536]{1,0} custom-call('
              '%p0, %p1, %p2, %p3), custom_call_target="tpu_custom_call"')
    other = "%fusion.3 = bf16[256,151936]{1,0} fusion(%p2), kind=kOutput"
    # two forwards of six layers: 24 calls of 1 ms
    dev = []
    for i in range(24):
        dev += [(kernel, i * 2e6, 1e6), (other, i * 2e6 + 1e6, 0.5e6)]
    ctx = dict(sizes=sizes, device_ops={"/device:TPU:0": dev}, busy_s=0.036,
               peaks=dict(hbm_bytes_per_s=819e9),
               scalars=dict(serving_decode_steps=2.0,
                            serving_block_forwards=128.0,
                            serving_block_tokens=96.0,
                            serving_prefill_tokens=0.0,
                            serving_decode_live_tokens=20000.0,
                            moe_experts_touched=1400.0,
                            moe_assignments=24576.0,
                            expert_load_max_over_mean=1.7))
    ctx.update(over)
    return ctx


def test_readers_on_a_synthetic_window():
    read = registry.READERS
    ctx = _ctx()
    need = 1400 * 9_437_184 + 24576 * 15_360
    assert math.isclose(read["expert_mm_roofline"](ctx),
                        100 * need / 819e9 / 0.024)
    assert math.isclose(read["expert_mm_share"](ctx), 100 * 0.024 / 0.036)
    dense = 4_361_055_744 - 151936 * 2048 - 6 * 128 * 3 * 2048 * 768
    floor = 2 * 2 * dense + 1400 * 9_437_184 + 20000 * 12288
    assert math.isclose(read["block_step_floor"](ctx),
                        100 * floor / 819e9 / 0.036)
    assert math.isclose(read["ratio"](ctx, "serving_block_tokens",
                                      "serving_block_forwards"), 0.75)
    assert read["scalar"](ctx, "expert_load_max_over_mean") == 1.7
    # nothing to read: a program without the kernel, the counters or the
    # sizes gives None, never an error
    dev = ctx["device_ops"]["/device:TPU:0"]
    bare = _ctx(device_ops={"/device:TPU:0": dev[1::2]})
    assert read["expert_mm_roofline"](bare) is None
    assert read["expert_mm_share"](bare) is None
    for name in ("expert_mm_roofline", "expert_mm_share",
                 "block_step_floor"):
        assert read[name](_ctx(device_ops={}, busy_s=None)) is None
    assert read["block_step_floor"](_ctx(scalars={})) is None
    assert read["ratio"](_ctx(scalars={}), "serving_block_tokens",
                         "serving_block_forwards") is None
    assert read["scalar"](_ctx(scalars={}),
                          "expert_load_max_over_mean") is None
    gpt = dict(layers=24, kv_heads=16, head_dim=64, n_params=1)
    assert read["expert_mm_roofline"](_ctx(sizes=gpt)) is None
    assert read["block_step_floor"](_ctx(sizes=gpt)) is None


def test_the_cell_at_toy_size_through_the_closed_blocks_loop():
    sysm = system.build_serve(TINY, CLOSED, 2**31 + 11, 1)
    out = registry.LOOPS["closed_blocks"](sysm, 2**31 + 11, 3.0, False)
    assert out.correct and out.failed == 0 and out.attempted > 0
    notes = out.notes
    # four rows x four blocks x four denoising forwards; the fourth row's
    # prefix came through a whole chunk and a padded one
    assert not notes["wrong"] and notes["checked_reveals"] == 64
    assert len(notes["near_ties"]) < 16
    chunk = TINY["serve"]["prefill_chunk"]
    assert any(n > chunk and n % chunk for n in notes["checked_prompt_lens"])
    s = out.scalars
    assert s["compiles"] == 0 and s["program_cache_traces"] == 0
    assert s["serving_block_forwards"] > s["serving_block_commits"] > 0
    assert 0.7 < s["serving_block_tokens"] / s["serving_block_forwards"] \
        <= 0.8 + 1e-9
    assert s["serving_decode_rows"] == s["serving_block_forwards"]
    assert s["output_tokens"] > 0 and s["prompt_tokens_done"] > 0
    # two layers x two of eight experts a token: every row of the rung
    # (an idle row's tokens are routed like any), and prefill's tokens
    assert s["moe_assignments"] >= (s["serving_decode_slots"] * 4
                                    + s["serving_prefill_tokens"]) * 2 * 2
    forwards = s["serving_decode_steps"] + s["serving_prefills"]
    assert 2 * forwards <= s["moe_experts_touched"] <= 2 * 8 * 2 * forwards
    assert s["expert_load_max_over_mean"] >= 1.0
    assert max(notes["warmed_prompt_lens"]) > 32        # a chunked one


def test_the_control_reads_worse_than_the_engine_at_toy_size():
    """``benchmark/tests/control_blocks.py``'s readings at toy size: the
    engine (float32 here) is the reference's own; the reference with
    8-bit matmul operands differs from itself, and every reveal it got
    otherwise is judged by the cell's own comparison. That the control
    reads ``correct: false`` under ``TIE_ATOL`` is a property of the
    published widths: the script shows it on the chip."""
    import jax.numpy as jnp
    sysm = system.build_serve(TINY, CLOSED, 2**31 + 12, 1)
    requests = traffic_lib.schedule(CLOSED, 2**31 + 12, 3.0)
    got = blocks.control_readings(sysm, requests, jnp.float8_e4m3fn)
    assert got["limits"] == dict(conf_median=sysm.ref.CONF_MEDIAN_ATOL,
                                 conf=sysm.ref.CONF_ATOL,
                                 tie=sysm.ref.TIE_ATOL)
    engine, control = got["engine"], got["control"]
    assert engine["correct"] and engine["reveals"] == 64
    assert control["reveals"] == 64
    assert control["differ"] > engine["differ"]
    assert control["conf_error_median"] > 10 * engine["conf_error_median"]
    assert control["largest_gap"] > engine["largest_gap"]
    assert engine["largest_conf_error"] < 1e-3
    assert control["largest_conf_error"] > 10 * engine["largest_conf_error"]
    assert control["correct"] == (control["wrong"] == 0)
