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


# ------------------------------- the sigmoid router, the bias, the shared
def _loop_sigmoid(x, router, gate_up, down, bias, scale, top_k=K,
                  weigh_by_biased=False, select_by_bare=False):
    """The ``afmoe`` router token by token in float64: each expert
    scored by a sigmoid of its own, the top k CHOSEN by score + bias,
    WEIGHTED by the bare scores of the chosen, normalised and scaled.
    The two flags are the two ways to get it wrong."""
    x, router, gate_up, down, bias = (np.asarray(a, np.float64) for a in
                                      (x, router, gate_up, down, bias))
    out = np.zeros_like(x)
    tops = []
    for t, row in enumerate(x):
        s = 1.0 / (1.0 + np.exp(-(row @ router)))
        top = np.argsort(-(s if select_by_bare else s + bias),
                         kind="stable")[:top_k]
        w = (s + bias if weigh_by_biased else s)[top]
        w = w / (w.sum() + 1e-20) * scale
        tops.append(sorted(top))
        for e, we in zip(top, w):
            gu = row @ gate_up[e]
            g, u = gu[:F], gu[F:]
            out[t] += we * ((g / (1 + np.exp(-g)) * u) @ down[e])
    return out, tops


def _sigmoid_case():
    router, gate_up, down = _weights(4)
    x = jax.random.normal(jax.random.PRNGKey(13), (29, H), jnp.float32)
    # a bias as large as the scores' spread: it changes the choice
    bias = jax.random.normal(jax.random.PRNGKey(17), (E,), jnp.float32) * 0.3
    return x, router, gate_up, down, bias


def test_sigmoid_router_selects_by_biased_score_and_weighs_by_bare():
    x, router, gate_up, down, bias = _sigmoid_case()
    y, counts = dropless_moe(x, router, gate_up, down, top_k=K,
                             score_func="sigmoid", select_bias=bias,
                             route_scale=2.826)
    want, tops = _loop_sigmoid(x, router, gate_up, down, bias, 2.826)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert int(counts.sum()) == 29 * K
    # the case shows something: the bias changes some token's choice, and
    # either wrong reading of it changes the output
    _, bare_tops = _loop_sigmoid(x, router, gate_up, down, bias, 2.826,
                                 select_by_bare=True)
    assert tops != bare_tops
    for wrong in (dict(select_by_bare=True), dict(weigh_by_biased=True)):
        other, _ = _loop_sigmoid(x, router, gate_up, down, bias, 2.826,
                                 **wrong)
        assert np.max(np.abs(other - want)) > 1e-3


@pytest.mark.parametrize("scale", [1.0, 2.826])
def test_route_scale_multiplies_the_normalised_weights(scale):
    x, router, gate_up, down, _ = _sigmoid_case()
    one, _ = dropless_moe(x, router, gate_up, down, top_k=K,
                          score_func="sigmoid")
    got, _ = dropless_moe(x, router, gate_up, down, top_k=K,
                          score_func="sigmoid", route_scale=scale)
    np.testing.assert_allclose(got, scale * np.asarray(one), rtol=1e-5,
                               atol=1e-6)


def test_softmax_call_is_unchanged_by_the_new_arguments():
    """The defaults spelled out are the call without them, to the bit."""
    x, router, gate_up, down, _ = _sigmoid_case()
    a, ca = dropless_moe(x, router, gate_up, down, top_k=K)
    b, cb = dropless_moe(x, router, gate_up, down, top_k=K,
                         score_func="softmax", select_bias=None,
                         route_scale=1.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    with pytest.raises(ValueError, match="score_func"):
        dropless_moe(x, router, gate_up, down, top_k=K, score_func="tanh")


def test_shared_expert_counts_once_over_the_shares():
    """Every holder of a share has the shared expert alike: the shares'
    outputs added up hold it once a holder, so the uncut layer is their
    sum less the surplus copies."""
    import paddle_tpu as paddle
    kw = dict(score_func="sigmoid", route_scale=2.826, select_bias=True,
              shared_intermediate_size=F)
    whole = DroplessMoE(H, F, E, K, **kw)
    rng = np.random.default_rng(1)
    state = {n: rng.normal(size=tuple(p.shape)).astype("float32") * 0.2
             for n, p in whole.named_parameters()}
    whole.load_raw_state({n: jnp.asarray(v) for n, v in state.items()})
    x = paddle.to_tensor(rng.normal(size=(3, 7, H)).astype("float32"))
    parts = 0.0
    for first in (0, 4):
        share = DroplessMoE(H, F, E, K, first=first, count=4, **kw)
        cut = dict(state, gate_up=state["gate_up"][first:first + 4],
                   down=state["down"][first:first + 4])
        share.load_raw_state({n: jnp.asarray(v) for n, v in cut.items()})
        parts = parts + share(x).numpy()
    xv = jnp.asarray(x.numpy().reshape(-1, H))
    gu = xv @ state["shared_gate_up"]
    shared = np.asarray((jax.nn.silu(gu[:, :F]) * gu[:, F:])
                        @ state["shared_down"]).reshape(3, 7, H)
    np.testing.assert_allclose(parts - shared, whole(x).numpy(), rtol=1e-4,
                               atol=1e-5)
    # and the shared expert is there at all
    routed, _ = dropless_moe(
        xv, *(jnp.asarray(state[n]) for n in ("router", "gate_up", "down")),
        top_k=K, score_func="sigmoid", route_scale=2.826,
        select_bias=jnp.asarray(state["expert_bias"]))
    np.testing.assert_allclose(
        whole(x).numpy().reshape(-1, H) - np.asarray(routed),
        shared.reshape(-1, H), rtol=1e-4, atol=1e-5)
