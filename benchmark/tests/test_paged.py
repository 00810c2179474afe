"""The readers of the decode paged-attention kernel on a synthetic
window: what they count, what they leave out, and that a program with
nothing to read gives None."""

import math

from benchmark.lib import flops, registry

registry.load_all()


def _ctx(**over):
    sizes = flops.gpt2_sizes(dict(
        hidden_size=1024, num_hidden_layers=24, vocab_size=50304,
        num_attention_heads=16, max_position_embeddings=1024))
    kernel = ('%paged_attention.7 = bf16[32,16,8,128]{3,2,1,0} '
              'custom-call(%p0, %p1), custom_call_target="tpu_custom_call"')
    chunk = ('%paged_chunk_attention.2 = bf16[1,16,256,128]{3,2,1,0} '
             'custom-call(%p0, %p1), custom_call_target="tpu_custom_call"')
    ops = {"/device:TPU:0": [(kernel, 0.0, 3e6), (chunk, 3e6, 5e6),
                             (kernel, 10e6, 1e6)]}
    ctx = dict(sizes=sizes, device_ops=ops, busy_s=0.010,
               peaks=dict(hbm_bytes_per_s=819e9),
               scalars=dict(serving_decode_live_tokens=5000.0,
                            serving_decode_read_pages=90.0,
                            serving_decode_table_pages=512.0))
    ctx.update(over)
    return ctx


def test_roofline_and_share_read_the_decode_kernel_alone():
    read = registry.READERS
    ctx = _ctx()
    # K and V of a token over 24 layers x 16 heads of 64, bf16
    need = 5000 * 2 * 24 * 16 * 64 * 2
    assert math.isclose(read["paged_attn_roofline"](ctx),
                        100 * need / 819e9 / 0.004)
    assert math.isclose(read["paged_attn_share"](ctx), 40.0)
    assert math.isclose(read["ratio"](
        ctx, num="serving_decode_read_pages",
        den="serving_decode_table_pages", scale=100.0), 100 * 90 / 512)


def test_nothing_to_read_gives_none():
    read = registry.READERS
    only_chunk = {"/device:TPU:0": _ctx()["device_ops"]["/device:TPU:0"][1:2]}
    for name in ("paged_attn_roofline", "paged_attn_share"):
        assert read[name](_ctx(device_ops=only_chunk)) is None
        assert read[name](_ctx(device_ops={}, busy_s=None)) is None
    # an engine without the counter (or a window that never decoded)
    assert read["paged_attn_roofline"](_ctx(scalars={})) is None
    assert read["ratio"](_ctx(scalars={}), num="serving_decode_read_pages",
                         den="serving_decode_table_pages") is None
