"""The window-and-global expert configuration's own pieces of the
benchmark: its counts against the program's model and the issue's
arithmetic, its file against the catalog, its traffic table, its readers
on a synthetic window, its cell at toy size through the ``closed_mixed``
loop on the CPU, and its controls at toy size."""

import json
import math
import os

import numpy as np
import pytest

from benchmark.lib import afmoe, registry, system
from benchmark.lib import traffic as traffic_lib

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "trinity-mini"
CELL = "trinity-mini.serve-mixed"

TYPES = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
TINY = dict(
    name=NAME, arch="afmoe", dtype="float32",
    model=dict(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=5,
               num_dense_layers=1, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_experts=8,
               num_experts_per_tok=2, num_shared_experts=1,
               score_func="sigmoid", route_norm=True, route_scale=2.826,
               n_group=1, topk_group=1, sliding_window=32,
               layer_types=TYPES, mup_enabled=True,
               max_position_embeddings=512, rms_norm_eps=1e-5,
               rope_theta=10000, initializer_range=0.3),
    program=dict(config_class="AfmoeConfig", model_class="AfmoeForCausalLM"),
    builders=dict(closed_mixed="serve_afmoe"),
    serve=dict(max_batch=8, page_size=8, max_seq_len=192, prefill_chunk=16))
# prompts 16, 32, 48, 96: at the chunk, at the window, past the window,
# past window + chunk
CLOSED = dict(loop="closed_mixed", clients=8, requests=24,
              prompt_len=dict(median=40, sigma=0.8, lo=16, hi=96, levels=4,
                              multiple=8),
              output_len=dict(median=12, sigma=0.5, lo=4, hi=24, levels=4,
                              multiple=1),
              schedule_seed=5)


@pytest.fixture(scope="module", autouse=True)
def _registered():
    registry.load_all()


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def test_config_file_keeps_every_published_width():
    cfg = _load("configs", NAME + ".json")
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (32, 2)
    assert len(pub["layer_types"]) == 32
    assert set(cfg["assumed"]) >= {
        "attention_gate", "qk_norm", "rope_on_window_layers_only",
        "four_norms", "embedding_scale", "expert_bias", "weights",
        "prefill_chunk"}
    assert "32 concurrent sequences" in cfg["deployment"]
    m = cfg["model"]
    # the flat copy and the harness's group say the same; the group's
    # layer_types is the first five of the published list, one dense
    # layer and a whole period
    assert {k: cfg[k] for k in m if k != "layer_types"} \
        == {k: v for k, v in m.items() if k != "layer_types"}
    assert cfg["layer_types"] == pub["layer_types"]
    assert m["layer_types"] == pub["layer_types"][:5] == TYPES
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"]) == (2048, 32, 4, 128)
    assert (m["num_experts"], m["num_experts_per_tok"],
            m["moe_intermediate_size"], m["intermediate_size"],
            m["vocab_size"]) == (128, 8, 1024, 6144, 200192)
    assert (m["sliding_window"], m["route_scale"], m["score_func"]) \
        == (2048, 2.826, "sigmoid")
    assert (m["num_hidden_layers"], m["num_dense_layers"]) == (5, 1)
    assert cfg["serve"] == dict(max_batch=32, page_size=64,
                                max_seq_len=24576, prefill_chunk=1024)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Trinity-Mini")
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "num_dense_layers"}
    assert pub["layer_types"] == row["config"]["layer_types"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "refs", NAME + ".py")) as fh:
        text = fh.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]


def test_arch_counts_equal_the_models_own_and_the_issues_arithmetic():
    cfg = _load("configs", NAME + ".json")
    sizes = system.sizes_of(cfg)
    _, model = system.lazy_model(cfg)
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert sizes["n_params"] == n == 4_241_534_720          # 8.48 GB in bf16
    assert round(2 * n / 1e9, 2) == 8.48
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert attention == 27_262_976
    expert = 3 * 2048 * 1024
    assert sizes["expert_params_per_layer"] == 128 * expert == 805_306_368
    small, bias = 4 * 2048 + 2 * 128, 128
    sparse = attention + 128 * expert + expert + 2048 * 128 + small + bias
    dense = attention + 3 * 2048 * 6144 + small
    assert (sparse, dense) == (839_131_520, 65_020_160)
    assert n == dense + 4 * sparse + 2 * 200192 * 2048 + 2048
    # what every forward reads: all but the routed experts and the
    # embedding table
    assert sizes["dense_forward_params"] \
        == n - 4 * 128 * expert - 200192 * 2048
    matrices = sum(int(np.prod(p.shape))
                   for name, p in model.named_parameters()
                   if len(p.shape) >= 2 and "embed_tokens" not in name)
    assert sizes["matmul_params"] == matrices
    assert (sizes["window_layers"], sizes["global_layers"],
            sizes["window"], sizes["sparse_layers"]) == (4, 1, 2048, 4)
    spec = model.cache_spec()
    assert [len(e) for e in spec] == [3, 3, 3, 2, 3]
    assert afmoe.kv_bytes_per_token_layer(sizes) == 2048    # 2 KB a layer
    from benchmark.lib import blocks
    assert blocks.expert_weight_bytes(sizes) == 12_582_912
    assert blocks.expert_activation_bytes(sizes) == 4096 + 4096 + 8192
    # the pools the engine will build: 12,289 global pages of one layer,
    # 32 rows x 49 window pages of four
    pages = 1 + 32 * (24576 // 64)
    bound = -(-(2048 + 1024) // 64) + 1
    assert (pages, bound) == (12289, 49)
    page_bytes = 64 * 2048
    assert round(pages * page_bytes / 1e9, 2) == 1.61
    assert round((1 + 32 * bound) * 4 * page_bytes / 1e9, 2) == 0.82


def test_traffic_table():
    t = _load("traffic", "mixed-closed.json")
    cfg = _load("configs", NAME + ".json")
    assert (t["loop"], t["clients"], t["requests"], t["schedule_seed"]) \
        == ("closed_mixed", 32, 96, 3404)
    assert t["prompt_len"] == dict(median=3072, sigma=1.3, lo=256, hi=22528,
                                   levels=8, multiple=256)
    assert t["output_len"] == dict(median=256, sigma=0.5, lo=64, hi=768,
                                   levels=8, multiple=1)
    assert t["clients"] == cfg["serve"]["max_batch"]
    rows = traffic_lib.schedule(t, 1, 50.0)
    assert len(rows) == 96
    assert [(r.prompt_len, r.output_len) for r in rows] == \
        [(r.prompt_len, r.output_len)
         for r in traffic_lib.schedule(t, 2**31 + 77, 50.0)]   # one order
    prompts = sorted({r.prompt_len for r in rows})
    outputs = sorted({r.output_len for r in rows})
    assert prompts == [512, 1024, 1536, 2560, 3840, 5888, 9728, 22528]
    assert outputs == [119, 164, 200, 237, 277, 327, 399, 551]
    assert all(sum(r.prompt_len == n for r in rows) == 12 for n in prompts)
    assert sum(r.prompt_len for r in rows) / 96 == 5952
    assert round(sum(r.output_len for r in rows) / 96) == 284
    window, chunk = (cfg["model"]["sliding_window"],
                     cfg["serve"]["prefill_chunk"])
    assert sum(n <= window for n in prompts) == 3
    assert sum(n <= chunk for n in prompts) == 2
    assert max(r.prompt_len + r.output_len for r in rows) \
        <= 22528 + 551 <= cfg["serve"]["max_seq_len"]
    # the prompts that decide ``correct`` are in the table, and cross
    # the chunk, the window, and window + chunk
    assert set(afmoe.CHECK_PROMPTS) <= set(prompts)
    c = afmoe.CHECK_PROMPTS
    assert c[0] <= chunk < c[1] <= window < c[2] <= window + chunk < c[3]
    assert -(-c[3] // 64) > -(-(window + chunk) // 64) + 1


def _ctx(**over):
    sizes = afmoe.afmoe_sizes(_load("configs", NAME + ".json")["model"])
    kernel = ('%paged_attention.7 = bf16[32,4,8,128]{3,2,1,0} custom-call('
              '%p0, %p1, %p2, %p3), custom_call_target="tpu_custom_call"')
    chunk = ('%paged_chunk_attention.2 = bf16[1,4,8192,128]{3,2,1,0} '
             'custom-call(%p0, %p1), custom_call_target="tpu_custom_call"')
    other = "%fusion.3 = bf16[32,200192]{1,0} fusion(%p2), kind=kOutput"
    # ten decode steps of five layers: 50 decode calls of 0.1 ms
    dev = []
    for i in range(50):
        dev += [(kernel, i * 2e6, 1e5), (chunk, i * 2e6 + 2e5, 3e5),
                (other, i * 2e6 + 1e6, 5e5)]
    ctx = dict(sizes=sizes, device_ops={"/device:TPU:0": dev}, busy_s=0.045,
               peaks=dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12),
               scalars=dict(serving_decode_steps=10.0,
                            serving_chunk_attn_pairs=40_000_000.0,
                            serving_chunk_window_pairs=8_000_000.0,
                            forward_programs=14.0,
                            serving_decode_live_tokens=1_500_000.0,
                            serving_decode_window_tokens=400_000.0,
                            moe_experts_touched=5000.0,
                            moe_assignments=200_000.0))
    ctx.update(over)
    return ctx


def test_readers_on_a_synthetic_window(monkeypatch):
    read = registry.READERS
    ctx = _ctx()
    attention = 2048 * (1_500_000 * 1 + 400_000 * 4)
    assert afmoe.decode_attention_bytes(ctx) == attention
    # the decode kernel's 5 ms, not the chunk kernel's
    assert math.isclose(read["mixed_attn_roofline"](ctx),
                        100 * attention / 819e9 / 0.005)
    dense = 4_241_534_720 - 4 * 805_306_368 - 200192 * 2048
    floor = 14 * 2 * dense + 5000 * 12_582_912 + attention
    assert math.isclose(read["moe_step_floor"](ctx),
                        100 * floor / 819e9 / 0.045)
    # the chunk kernel's 15 ms, not the decode kernel's: 16,384 FLOPs a
    # query-key pair a layer, one global layer and four window layers
    assert math.isclose(read["chunk_attn_share"](ctx), 100 * 0.015 / 0.045)
    assert math.isclose(
        read["chunk_attn_roofline"](ctx),
        100 * 4 * 32 * 128 * (40e6 * 1 + 8e6 * 4) / 197e12 / 0.015)
    levels = {"global": 1.0e9, "window": 1.6e9}
    monkeypatch.setattr(afmoe, "_pool_gauge", lambda pool: levels[pool])
    # four window layers would hold 4 GB at the global pool's pages
    assert math.isclose(read["window_kv_resident"](ctx), 40.0)
    # nothing to read: a program without the kernel, the counters, the
    # gauge or the sizes gives None, never an error
    monkeypatch.setattr(afmoe, "_pool_gauge", lambda pool: None)
    assert read["window_kv_resident"](ctx) is None
    monkeypatch.undo()
    assert read["window_kv_resident"](ctx) is None      # no such gauge set
    dev = ctx["device_ops"]["/device:TPU:0"]
    bare = _ctx(device_ops={"/device:TPU:0": dev[1::3] + dev[2::3]})
    assert read["mixed_attn_roofline"](bare) is None
    no_chunk = _ctx(device_ops={"/device:TPU:0": dev[0::3] + dev[2::3]})
    assert read["chunk_attn_share"](no_chunk) is None
    assert read["chunk_attn_roofline"](no_chunk) is None
    assert read["chunk_attn_share"](_ctx(device_ops={}, busy_s=None)) is None
    for name in ("mixed_attn_roofline", "moe_step_floor",
                 "chunk_attn_roofline"):
        assert read[name](_ctx(device_ops={}, busy_s=None)) is None
        assert read[name](_ctx(scalars={})) is None
    parent = dict(_ctx()["scalars"])
    for new in ("serving_decode_window_tokens", "serving_chunk_attn_pairs",
                "serving_chunk_window_pairs"):
        del parent[new]
    assert read["mixed_attn_roofline"](_ctx(scalars=parent)) is None
    assert read["moe_step_floor"](_ctx(scalars=parent)) is None
    assert read["chunk_attn_roofline"](_ctx(scalars=parent)) is None
    gpt = dict(layers=24, heads=16, kv_heads=16, head_dim=64, n_params=1)
    for name in ("mixed_attn_roofline", "moe_step_floor",
                 "window_kv_resident", "chunk_attn_roofline"):
        assert read[name](_ctx(sizes=gpt)) is None


def test_the_selection_bias_is_drawn_from_the_seed():
    sysm = afmoe.build_serve_afmoe(TINY, CLOSED, 2**31 + 9, 1)
    other = afmoe.build_serve_afmoe(TINY, CLOSED, 2**31 + 10, 1)
    names = [n for n in sysm.weights if n.endswith("expert_bias")]
    assert len(names) == 4
    for n in names:
        bias = np.asarray(sysm.weights[n], np.float32)
        assert bias.shape == (8,) and 0.002 < bias.std() < 0.06
        assert not np.array_equal(bias, np.asarray(other.weights[n]))
    assert len({np.asarray(sysm.weights[n]).tobytes() for n in names}) == 4


def test_the_cell_at_toy_size_through_the_closed_mixed_loop(monkeypatch):
    monkeypatch.setattr(afmoe, "CHECK_PROMPTS", (16, 32, 48, 96))
    sysm = afmoe.build_serve_afmoe(TINY, CLOSED, 2**31 + 11, 1)
    out = registry.LOOPS["closed_mixed"](sysm, 2**31 + 11, 3.0, False)
    assert out.correct and out.failed == 0 and out.attempted > 0
    notes = out.notes
    assert not notes["wrong"] and notes["checked_tokens"] == 32
    assert notes["checked_prompt_lens"] == [16, 32, 48, 96]
    assert notes["warmed_prompt_lens"] == [16, 32, 48, 96]
    s = out.scalars
    assert s["compiles"] == 0 and s["program_cache_traces"] == 0
    assert s["output_tokens"] > 0 and s["prompt_tokens_done"] > 0
    assert 0 < s["serving_decode_window_tokens"] \
        < s["serving_decode_live_tokens"]
    assert s["serving_window_pages_released"] > 0
    # a query sees at most a window of keys in a window layer
    assert 0 < s["serving_chunk_window_pairs"] < s["serving_chunk_attn_pairs"]
    assert s["forward_programs"] > s["serving_decode_steps"] > 0
    # four sparse layers x two of eight experts a token
    assert s["moe_assignments"] >= (s["serving_decode_rows"]
                                    + s["serving_prefill_tokens"]) * 4 * 2
    assert 4 * s["forward_programs"] <= s["moe_experts_touched"] \
        <= 4 * 8 * (s["forward_programs"] + 24)
    assert s["expert_load_max_over_mean"] >= 1.0
    # the gauges of both pools stand as the window closed
    ctx = dict(sizes=sysm.sizes)
    resident = registry.READERS["window_kv_resident"](ctx)
    assert resident is not None and 0 < resident < 100


def test_the_controls_read_worse_than_the_engine_at_toy_size(monkeypatch):
    """``benchmark/tests/control_mixed.py``'s readings at toy size: the
    engine (float32 here) is the reference's own; the reference with
    8-bit matmul operands, and with the window left off, differ from
    it, the second only on the prompts past the window. That both read
    ``correct: false`` under ``TIE_ATOL`` is a property of the published
    widths: the script shows it on the chip."""
    import jax.numpy as jnp
    monkeypatch.setattr(afmoe, "CHECK_PROMPTS", (16, 32, 48, 96))
    sysm = afmoe.build_serve_afmoe(TINY, CLOSED, 2**31 + 12, 1)
    requests = traffic_lib.schedule(CLOSED, 2**31 + 12, 3.0)
    got = afmoe.control_readings(sysm, requests, jnp.float8_e4m3fn)
    assert got["limits"] == dict(tie=sysm.ref.TIE_ATOL,
                                 mean_gap=sysm.ref.MEAN_GAP_ATOL)
    assert got["same_precision"]["largest_gap"] < 1e-3  # float32 here
    engine, low, off = got["engine"], got["low_precision"], got["window_off"]
    assert engine["correct"] and engine["tokens"] == 32
    assert engine["largest_gap"] < 1e-3
    assert low["differ"] > engine["differ"]
    assert low["largest_gap"] > 10 * max(engine["largest_gap"], 1e-4)
    assert low["mean_gap"] > 10 * max(engine["mean_gap"], 1e-5)
    # inside the window (the prompt of 16 and its 8 new tokens) nothing
    # moves; past it the tokens differ
    assert off["differ_by_prompt"][0] == 0
    assert sum(off["differ_by_prompt"][2:]) > 0
    for reading in (low, off):
        assert reading["correct"] == (reading["wrong"] == 0)
