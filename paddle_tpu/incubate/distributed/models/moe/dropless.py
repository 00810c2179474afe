"""A dropless expert layer for serving: top-k over the router's full
width, tokens sorted by expert, ONE grouped matmul a projection, a
weighted un-sort.

    p = softmax_fp32(x W_r)                      over all E experts
    y = sum_{e in top_k(p)} (p_e / sum_top_k p) * W_down_e (silu(W_gate_e x) * W_up_e x)

The router has a second published form (``afmoe``, after DeepSeek-V3's
loss-free balancing): ``score_func="sigmoid"`` scores each expert on its
own, ``select_bias`` (E,) is added to the scores for the CHOICE of the
top k only — the weights are the bare scores of the chosen — and
``route_scale`` multiplies the normalised weights. A layer may also hold
a SHARED expert every token passes through, added to the routed sum.

:class:`MoELayer` (beside this file) routes by a dense one-hot
``(T, E, C)`` dispatch and drops tokens over a capacity: right for
training under a load-balancing loss, wrong for inference (a dropped
token changes the answer) and at 128 experts a ``(T, 128, C)`` tensor a
layer. Here nothing is dropped and nothing has a capacity: the sorted
rows are the assignments, padded to whole row tiles
(``kernels/grouped_matmul.py``).

The layer is told which experts it HOLDS (``first``, ``count``): the
router always scores all ``num_experts``, and an assignment to an expert
not held contributes nothing — the parts computed by shares of the
experts add up to the whole layer's output, which is how the experts
spread over chips.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .....core.tensor import apply_op
from .....kernels.grouped_matmul import grouped_matmul, padded_rows
from .....nn import initializer as I
from .....nn.layer import Layer
from .....nn.param_attr import ParamAttr

__all__ = ["DroplessMoE", "dropless_moe"]


def dropless_moe(x, router_w, w_gate_up, w_down, *, top_k: int,
                 first: int = 0, norm_topk_prob: bool = True,
                 score_func: str = "softmax", select_bias=None,
                 route_scale: float = 1.0):
    """``x`` (T, H); ``router_w`` (H, E); ``w_gate_up`` (E_held, H, 2F)
    with gate | up side by side; ``w_down`` (E_held, F, H). The experts
    held are ``first .. first + E_held - 1``. ``score_func``:
    ``"softmax"`` over the experts or ``"sigmoid"`` of each;
    ``select_bias`` (E,): added to the scores for the choice of the top
    k, not to the weights; ``route_scale``: on the normalised weights.
    Returns ``(y, counts)``: ``y`` (T, H) in ``x``'s dtype and the
    assignments each held expert got, (E_held,) int32."""
    count, width = w_gate_up.shape[0], w_down.shape[1]
    t = x.shape[0]
    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    if score_func == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    elif score_func == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    else:
        raise ValueError(f"score_func must be 'softmax' or 'sigmoid', got "
                         f"{score_func!r}")
    if select_bias is None:
        top_p, top_e = jax.lax.top_k(probs, top_k)           # (T, k)
    else:
        _, top_e = jax.lax.top_k(
            probs + select_bias.astype(jnp.float32), top_k)
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    if norm_topk_prob:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        # sigmoid scores can all be tiny; softmax's top k cannot
        top_p = top_p / (total if score_func == "softmax" else total + 1e-20)
    if route_scale != 1.0:
        top_p = top_p * route_scale
    # the assignments sorted by held expert; one not held sorts last,
    # past every group, where nothing is computed
    n = t * top_k
    local = top_e.reshape(-1).astype(jnp.int32) - first
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)
    order = jnp.argsort(local, stable=True)
    counts = jnp.sum(jax.nn.one_hot(local, count + 1, dtype=jnp.int32),
                     axis=0)[:count]
    row_token = jnp.zeros((padded_rows(n),), jnp.int32).at[:n].set(
        (order // top_k).astype(jnp.int32))
    gu = grouped_matmul(x[row_token], w_gate_up, counts)     # (rows, 2F)
    h = (jax.nn.silu(gu[:, :width]) * gu[:, width:]).astype(x.dtype)
    ys = grouped_matmul(h, w_down, counts)                   # (rows, H)
    # the weighted un-sort: assignment (t, j) sits in row slot_row[t, j]
    slot_row = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)).reshape(t, top_k)
    held = held.reshape(t, top_k)
    weight = jnp.where(held, top_p, 0.0)[..., None]
    y = jnp.sum(jnp.where(held[..., None], ys[slot_row], 0.0) * weight,
                axis=1)
    return y.astype(x.dtype), counts


class DroplessMoE(Layer):
    """The layer over stacked expert weights. ``first`` / ``count``: the
    experts this instance holds (default: all). ``forward`` takes
    ``(..., H)`` and returns the same shape; with ``return_counts`` also
    the assignments each held expert got in the call, (count,) int32,
    for whoever accumulates a load histogram.

    ``score_func`` / ``route_scale`` / ``select_bias`` (True: an
    ``expert_bias`` parameter over all ``num_experts``): the router of
    :func:`dropless_moe`. ``shared_intermediate_size``: the width of a
    shared SwiGLU expert, ``shared_gate_up`` / ``shared_down``, which
    every token passes through; every holder of a share of the experts
    has it alike, so where the shares' outputs are added up it counts
    once."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, top_k: int, *,
                 norm_topk_prob: bool = True, first: int = 0,
                 count: Optional[int] = None,
                 initializer_range: float = 0.02,
                 score_func: str = "softmax", route_scale: float = 1.0,
                 select_bias: bool = False,
                 shared_intermediate_size: Optional[int] = None):
        super().__init__()
        count = num_experts - first if count is None else count
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(
                f"experts held [{first}, {first + count}) are not inside "
                f"the router's {num_experts}")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} exceeds {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.count = first, count
        self.norm_topk_prob = norm_topk_prob
        self.score_func, self.route_scale = score_func, float(route_scale)
        init = ParamAttr(initializer=I.Normal(0.0, initializer_range))
        self.router = self.create_parameter(
            (hidden_size, num_experts), attr=init)
        self.gate_up = self.create_parameter(
            (count, hidden_size, 2 * intermediate_size), attr=init)
        self.down = self.create_parameter(
            (count, intermediate_size, hidden_size), attr=init)
        # the loss-free balancing buffer: it steers the choice, and
        # training moves it by the load, not by a gradient
        self.expert_bias = (self.create_parameter(
            (num_experts,), attr=ParamAttr(initializer=I.Constant(0.0)))
            if select_bias else None)
        self.shared_gate_up = self.shared_down = None
        if shared_intermediate_size:
            self.shared_gate_up = self.create_parameter(
                (hidden_size, 2 * shared_intermediate_size), attr=init)
            self.shared_down = self.create_parameter(
                (shared_intermediate_size, hidden_size), attr=init)

    def forward(self, x, return_counts: bool = False):
        shape = x.shape
        extra = [p for p in (self.expert_bias, self.shared_gate_up,
                             self.shared_down) if p is not None]

        def fn(xv, rw, gu, dn, *rest):
            rest = list(rest)
            bias = rest.pop(0) if self.expert_bias is not None else None
            xv = xv.reshape(-1, shape[-1])
            y, counts = dropless_moe(
                xv, rw, gu, dn, top_k=self.top_k,
                first=self.first, norm_topk_prob=self.norm_topk_prob,
                score_func=self.score_func, select_bias=bias,
                route_scale=self.route_scale)
            if rest:
                sgu, sdn = rest
                width = sdn.shape[0]
                gu = jnp.dot(xv, sgu, preferred_element_type=jnp.float32)
                h = (jax.nn.silu(gu[:, :width]) * gu[:, width:]
                     ).astype(xv.dtype)
                y = y + jnp.dot(h, sdn, preferred_element_type=jnp.float32
                                ).astype(y.dtype)
            return y, counts

        y, counts = apply_op("dropless_moe", fn, x, self.router,
                             self.gate_up, self.down, *extra)
        y = y.reshape(list(shape))
        return (y, counts) if return_counts else y
