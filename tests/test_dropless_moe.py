"""The dropless expert layer (incubate/distributed/models/moe/dropless.py)
and its grouped matmul (kernels/grouped_matmul.py) at a tiny size on the
CPU: against a per-token loop over experts, by shares of the experts
held, and upstream's Pallas kernel in interpret mode against the branch
the CPU runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models.moe import (DroplessMoE,
                                                        dropless_moe)
from paddle_tpu.kernels import grouped_matmul as gm

H, F, E, K = 32, 24, 8, 3


def _weights(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (H, E), dtype) * 0.5,
            jax.random.normal(ks[1], (E, H, 2 * F), dtype) * 0.2,
            jax.random.normal(ks[2], (E, F, H), dtype) * 0.2)


def _loop(x, router, gate_up, down, top_k=K):
    """Token by token, expert by expert, in float64 numpy."""
    x, router, gate_up, down = (np.asarray(a, np.float64)
                                for a in (x, router, gate_up, down))
    out = np.zeros_like(x)
    for t, row in enumerate(x):
        logits = row @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p, kind="stable")[:top_k]
        for e in top:
            gu = row @ gate_up[e]
            g, u = gu[:F], gu[F:]
            out[t] += p[e] / p[top].sum() * ((g / (1 + np.exp(-g)) * u)
                                             @ down[e])
    return out


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("tokens", [1, 5, 37])
def test_against_token_loop(tokens):
    router, gate_up, down = _weights()
    x = jax.random.normal(jax.random.PRNGKey(7), (tokens, H), jnp.float32)
    y, counts = dropless_moe(x, router, gate_up, down, top_k=K)
    np.testing.assert_allclose(y, _loop(x, router, gate_up, down),
                               rtol=2e-5, atol=2e-5)
    assert int(counts.sum()) == tokens * K


@pytest.mark.parametrize("case", ["one_expert_gets_none",
                                  "one_expert_gets_all"])
def test_empty_and_full_experts(case):
    router, gate_up, down = _weights(1)
    if case == "one_expert_gets_none":
        router = router.at[:, 2].set(-50.0)      # never in any top-k
    else:
        router = router.at[:, 5].set(0.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (40, H)))
    if case == "one_expert_gets_all":
        router = router.at[:, 5].set(2.0)        # x > 0: always the top
    y, counts = dropless_moe(x, router, gate_up, down, top_k=K)
    np.testing.assert_allclose(y, _loop(x, router, gate_up, down),
                               rtol=2e-5, atol=2e-5)
    if case == "one_expert_gets_none":
        assert int(counts[2]) == 0
    else:
        assert int(counts[5]) == 40


@pytest.mark.parametrize("share", [1, 2, 4])
def test_shares_of_the_experts_add_up(share):
    """The guide's shares test: the layer computed by ``share``-sized
    shares of the experts held, each told which it holds, adds up to the
    whole layer's output."""
    router, gate_up, down = _weights(2)
    x = jax.random.normal(jax.random.PRNGKey(11), (23, H), jnp.float32)
    whole, counts = dropless_moe(x, router, gate_up, down, top_k=K)
    parts, seen = 0.0, []
    for first in range(0, E, share):
        part, c = dropless_moe(x, router, gate_up[first:first + share],
                               down[first:first + share], top_k=K,
                               first=first)
        parts = parts + part
        seen.append(np.asarray(c))
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(seen), counts)


def _rows_and_weights(k=128, n=256, experts=6, rows=256):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (rows, k),
                          jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (experts, k, n), jnp.float32)
         * 0.1).astype(jnp.bfloat16)
    return x, w


@pytest.mark.parametrize("sizes", [[40, 0, 70, 3, 60, 83],
                                   [40, 0, 70, 3, 60, 10]],
                         ids=["every_row_in_a_group", "rows_past_the_groups"])
def test_grouped_matmul_against_a_loop_over_groups(sizes):
    """Each expert's run of rows against that expert's weights, an
    expert with no row among them; rows past the groups' sum belong to
    no expert and are nobody's to read."""
    x, w = _rows_and_weights()
    got = gm.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32))
    assert got.shape == (256, 256) and got.dtype == jnp.float32
    lo = 0
    for e, size in enumerate(sizes):
        want = np.asarray(x[lo:lo + size], np.float32) \
            @ np.asarray(w[e], np.float32)
        np.testing.assert_allclose(got[lo:lo + size], want, rtol=1e-5,
                                   atol=1e-5)
        lo += size


def test_upstream_kernel_at_this_tiling_matches_the_other_branch():
    """The TPU branch (upstream's Pallas kernel at this module's tiling,
    in interpret mode) against what the CPU runs, on the rows that
    belong to a group."""
    x, w = _rows_and_weights()
    sizes = jnp.asarray([40, 0, 70, 3, 60, 10], jnp.int32)
    got = gm.grouped_matmul_tpu(x, w, sizes, interpret=True)
    want = gm.grouped_matmul(x, w, sizes)
    np.testing.assert_allclose(got[:183], want[:183], rtol=1e-5, atol=1e-5)


def test_rows_are_whole_tiles():
    assert [gm.padded_rows(n) for n in (1, 128, 129, 2048)] \
        == [128, 128, 256, 2048]
    x, w = _rows_and_weights(rows=100)
    with pytest.raises(ValueError, match="whole tiles"):
        gm.grouped_matmul(x, w, jnp.asarray([100, 0, 0, 0, 0, 0], jnp.int32))


def test_layer_holds_a_share_and_leaves_counts():
    import paddle_tpu as paddle
    layer = DroplessMoE(H, F, E, K, first=2, count=4)
    assert tuple(layer.gate_up.shape) == (4, H, 2 * F)
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 5, H)).astype("float32"))
    y, counts = layer(x, return_counts=True)
    assert tuple(y.shape) == (2, 5, H)
    assert tuple(counts.shape) == (4,)
    np.testing.assert_array_equal(layer(x).numpy(), y.numpy())
    with pytest.raises(ValueError, match="not inside"):
        DroplessMoE(H, F, E, K, first=6, count=4)
