"""Percentile and tokens-per-second arithmetic on hand-made samples."""

import pytest

from benchmark.lib import readers, stats


def test_percentile_by_hand():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile([3.0, 1.0], 50) == 2.0       # order-free
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.percentile([], 50) is None
    with pytest.raises(ValueError):
        stats.percentile(xs, 101)


def test_percentile_is_numpys_linear_rule():
    import numpy as np
    rng = np.random.default_rng(0)
    xs = rng.lognormal(size=1001).tolist()
    for q in (1, 25, 50, 90, 99):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)


def test_token_gaps_and_spread():
    assert stats.token_gaps([1.0, 1.5, 2.5]) == [0.5, 1.0]
    assert stats.token_gaps([1.0]) == []
    # quartiles of 1..6 by Python's rule: 1.75, 3.5, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def _ctx(**scalars):
    return dict(scalars=scalars, series={})


def test_serve_tok_s_counts_prompt_and_output_tokens_over_the_window():
    ctx = _ctx(prompt_tokens_done=50_000.0, output_tokens=5_000.0,
               window_s=50.0)
    num = ["prompt_tokens_done", "output_tokens"]
    assert readers.rate(ctx, num, "window_s") == pytest.approx(1100.0)
    assert readers.ratio(ctx, "prompt_tokens_done", num, scale=100.0) == \
        pytest.approx(100.0 * 50 / 55)
    # nothing to read: nothing is returned
    assert readers.rate(_ctx(window_s=50.0), num, "window_s") is None
    assert readers.rate(_ctx(output_tokens=1.0, prompt_tokens_done=1.0,
                             window_s=0.0), num, "window_s") is None


def test_series_readers():
    ctx = dict(scalars=dict(setup_s=41.5), series=dict(ttft_ms=[1, 2, 3]))
    assert readers.percentile(ctx, "ttft_ms", 50) == 2.0
    assert readers.percentile(ctx, "absent", 50) is None
    assert readers.scalar(ctx, "setup_s") == 41.5
    assert readers.scalar(ctx, "absent") is None


def test_train_mfu_against_the_published_peak():
    from benchmark.lib import flops
    from benchmark.lib.peaks import peaks
    sizes = flops.gpt2_sizes(dict(
        hidden_size=1024, num_hidden_layers=24, vocab_size=50304,
        num_attention_heads=16, max_position_embeddings=1024))
    assert sizes["n_params"] == 354_871_296
    per_token = flops.train_flops_per_token(sizes, 1024)
    assert per_token == 6 * 353_501_184 + 6 * 24 * 1024 * 1024
    ctx = dict(scalars=dict(tokens=34_000.0 * 50, window_s=50.0), series={},
               sizes=sizes, traffic=dict(seq_len=1024), chips=1,
               peaks=peaks("TPU v5 lite"))
    assert readers.train_mfu(ctx) == pytest.approx(
        100 * 34_000 * per_token / 197e12)
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
