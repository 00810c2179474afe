"""The Mamba-2 recurrence (kernels/ssm_update.py): the decode kernel in
interpret mode against its ``jnp`` twin and against the recurrence
position by position, the chunked scan against the same, the packed
layout's round trip."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import ssm_update as S

pytestmark = pytest.mark.pallas_interpret


def _inputs(b, s, n_heads, p, n, g, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(
        x=f(b, s, n_heads, p),
        dt=jnp.asarray(rng.uniform(1e-3, 0.3, (b, s, n_heads)), jnp.float32),
        A=-jnp.asarray(rng.uniform(1, 16, (n_heads,)), jnp.float32),
        B=f(b, s, g, n), C=f(b, s, g, n), D=f(n_heads),
        h0=f(b, n_heads, p, n))


@pytest.mark.parametrize("shape", [(4, 32, 16, 1), (8, 16, 32, 2),
                                   (2, 128, 8, 1)],
                         ids=["pack4", "pack1-groups2", "pack1-wide"])
def test_pack_round_trip(shape):
    n_heads, p, n, g = shape
    pack = S.heads_per_row(n_heads, p, g)
    h = _inputs(3, 1, n_heads, p, n, g)["h0"]
    packed = S.pack_state(h, pack)
    assert packed.shape == (3,) + S.packed_shape(n_heads, p, n, g)
    assert packed.shape[-1] == pack * p
    np.testing.assert_array_equal(S.unpack_state(packed, pack), h)
    # element (r, n, q * P + p) is h[r * pack + q, p, n]
    r, q, pi, ni = n_heads // pack - 1, pack - 1, p - 1, 2
    assert packed[1, r, ni, q * p + pi] == h[1, r * pack + q, pi, ni]


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("groups", [1, 2])
def test_chunk_scan_equals_sequential(chunk, groups):
    a = _inputs(2, 37, 8, 16, 32, groups)
    y_seq, h_seq = S.ssd_sequential_scan(**a)
    y, h = S.ssd_chunk_scan(**a, chunk=chunk)
    np.testing.assert_allclose(y, y_seq, atol=2e-5)
    np.testing.assert_allclose(h, h_seq, atol=2e-5)


def test_chunk_scan_zero_dt_is_no_step():
    """``dt = 0`` past position 20 (a padded chunk's mask): the state
    is what 20 positions leave."""
    a = _inputs(1, 32, 4, 32, 16, 1)
    masked = dict(a, dt=a["dt"].at[:, 20:].set(0.0))
    short = {k: (v[:, :20] if k in ("x", "dt", "B", "C") else v)
             for k, v in a.items()}
    _, h_masked = S.ssd_chunk_scan(**masked, chunk=8)
    _, h_short = S.ssd_chunk_scan(**short, chunk=8)
    np.testing.assert_allclose(h_masked, h_short, atol=1e-6)


@pytest.mark.parametrize("b", [1, 4, 32])
def test_decode_kernel_matches_jnp_and_recurrence(b):
    """Interpret mode, the engine's geometry in small (two heads to a
    128-lane row): kernel == jnp twin == one step of the recurrence;
    the rows past ``b`` keep their bits."""
    n_heads, p, n, g, slots = 16, 64, 32, 1, 34
    a = _inputs(b, 1, n_heads, p, n, g, seed=b)
    pack = S.heads_per_row(n_heads, p, g)
    assert pack == 2
    rng = np.random.default_rng(7)
    store = jnp.asarray(
        rng.normal(size=(slots,) + S.packed_shape(n_heads, p, n, g)),
        jnp.float32)
    step = (a["x"][:, 0], a["dt"][:, 0], a["A"], a["B"][:, 0], a["C"][:, 0],
            a["D"])
    y_ref, h_ref = S.ssd_sequential_scan(
        **dict(a, h0=S.unpack_state(store[:b], pack)))
    out_k, y_k = S.ssm_decode_update_pallas(store, *step, interpret=True)
    out_x, y_x = S.ssm_decode_update_xla(store, *step)
    for out, y in ((out_k, y_k), (out_x, y_x)):
        np.testing.assert_allclose(y, y_ref[:, 0], atol=1e-5)
        np.testing.assert_allclose(S.unpack_state(out[:b], pack), h_ref,
                                   atol=1e-5)
        np.testing.assert_array_equal(out[b:], store[b:])
    np.testing.assert_allclose(out_k, out_x, atol=1e-6)
    assert out_k.dtype == jnp.float32 and y_k.dtype == jnp.float32


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_decode_zero_dt_keeps_the_row_bit_for_bit(impl):
    """A row that must not move is given ``dt = 0``: decay exp(0) = 1,
    input 0 — its state comes back with the same bits."""
    n_heads, p, n, g, b = 4, 32, 16, 2, 3
    a = _inputs(b, 1, n_heads, p, n, g)
    store = _inputs(b, 1, n_heads, p, n, g, seed=3)["h0"]
    store = S.pack_state(store, S.heads_per_row(n_heads, p, g))
    dt = a["dt"][:, 0].at[1].set(0.0)
    args = (store, a["x"][:, 0], dt, a["A"], a["B"][:, 0], a["C"][:, 0],
            a["D"])
    out, _ = (S.ssm_decode_update_pallas(*args, interpret=True)
              if impl == "pallas" else S.ssm_decode_update_xla(*args))
    np.testing.assert_array_equal(out[1], store[1])
    assert not np.array_equal(out[0], store[0])
