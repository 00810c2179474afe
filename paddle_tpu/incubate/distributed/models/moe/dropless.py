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

__all__ = ["DroplessMoE", "dropless_moe", "dropless_moe_train"]


def _route(x, router_w, top_k, score_func, select_bias, norm_topk_prob,
           route_scale):
    """``(probs, top_p, top_e)``: the router's scores over all its
    experts (float32), and each token's chosen ``top_k`` with their
    weights."""
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
    return probs, top_p, top_e


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
    _, top_p, top_e = _route(x, router_w, top_k, score_func, select_bias,
                             norm_topk_prob, route_scale)
    return _held_experts(x, top_p, top_e, w_gate_up, w_down, first,
                         train=False)


# The rows a grouped matmul leaves past ``sum(group_sizes)`` hold whatever
# the buffer held, forward and backward (upstream's ``gmm`` VJP computes
# ``dx`` rows and ``tgmm`` sums over the groups' rows alone). The training
# path never lets them reach a value or a cotangent: every row buffer is
# masked by ``valid`` right after each product, and the two un-sorts are
# gathers whose transposes are gathers too, indexed from the assignments'
# side, so an uncomputed row is never read.
@jax.custom_vjp
def _dispatch_rows(x, row_token, slot_row, held):
    """``x`` (T, H) -> the sorted rows (R, H): row ``r`` is token
    ``row_token[r]``'s, zero where that is out of range (a row past the
    groups')."""
    return x.at[row_token].get(mode="fill", fill_value=0)


def _dispatch_fwd(x, row_token, slot_row, held):
    return _dispatch_rows(x, row_token, slot_row, held), (slot_row, held)


def _dispatch_bwd(res, g):
    slot_row, held = res                        # (T, k) each
    # token t's gradient: the rows of its held assignments, summed
    picked = jnp.where(held[..., None], g[slot_row].astype(jnp.float32), 0.0)
    return jnp.sum(picked, axis=1).astype(g.dtype), None, None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect_rows(ys, slot_row, held, assign):
    """``ys`` (R, H) -> each assignment's row (n, H), zero for one whose
    expert is not held (its row lies past the groups')."""
    return jnp.where(held[:, None], ys[slot_row], 0.0)


def _collect_fwd(ys, slot_row, held, assign):
    return _collect_rows(ys, slot_row, held, assign), assign


def _collect_bwd(assign, g):
    # row r's gradient: its assignment's, zero past the groups' rows
    # (``assign`` is out of range there)
    return (g.at[assign].get(mode="fill", fill_value=0), None, None, None)


_collect_rows.defvjp(_collect_fwd, _collect_bwd)


def _held_experts(x, top_p, top_e, w_gate_up, w_down, first, *, train):
    """Tokens ``x`` (T, H) through the experts held, each to its chosen
    ``top_e`` (T, k) weighted by ``top_p``: the assignments sorted by held
    expert (one not held sorts last, past every group, where nothing is
    computed), ONE grouped matmul a projection, the weighted un-sort.
    ``train``: differentiable, no uncomputed row reaching a value or a
    cotangent (above). Returns ``(y (T, H) in x's dtype, counts (E_held,)
    int32)``."""
    count, width = w_gate_up.shape[0], w_down.shape[1]
    t, top_k = top_e.shape
    n = t * top_k
    with jax.named_scope("moe.dispatch"):
        local = top_e.reshape(-1).astype(jnp.int32) - first
        held = (local >= 0) & (local < count)
        local = jnp.where(held, local, count)
        order = jnp.argsort(local, stable=True)   # row r: assignment order[r]
        counts = jnp.sum(jax.nn.one_hot(local, count + 1, dtype=jnp.int32),
                         axis=0)[:count]
        rows = padded_rows(n)
        # assignment (t, j) sits in row slot_row[t, j]
        slot_row = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        if train:
            valid = jnp.arange(rows, dtype=jnp.int32) < jnp.sum(counts)
            # the assignment each row holds, n (out of range) past the
            # groups'
            assign = jnp.where(valid, jnp.full((rows,), n, jnp.int32).at[
                :n].set(order.astype(jnp.int32)), n)
            xr = _dispatch_rows(x, assign // top_k, slot_row.reshape(t, top_k),
                                held.reshape(t, top_k))

            def mask(r):
                return jnp.where(valid[:, None], r, 0.0)
        else:
            xr = x[jnp.zeros((rows,), jnp.int32).at[:n].set(
                (order // top_k).astype(jnp.int32))]

            def mask(r):
                return r
        gu = mask(grouped_matmul(xr, w_gate_up, counts))     # (rows, 2F)
        h = (jax.nn.silu(gu[:, :width]) * gu[:, width:]).astype(x.dtype)
        ys = mask(grouped_matmul(h, w_down, counts))         # (rows, H)
    with jax.named_scope("moe.combine"):
        if train:
            picked = _collect_rows(ys, slot_row, held, assign)
            y = jnp.einsum("tkh,tk->th", picked.reshape(t, top_k, -1),
                           top_p.astype(jnp.float32))
        else:
            held = held.reshape(t, top_k)
            weight = jnp.where(held, top_p, 0.0)[..., None]
            y = jnp.sum(jnp.where(held[..., None],
                                  ys[slot_row.reshape(t, top_k)], 0.0)
                        * weight, axis=1)
    return y.astype(x.dtype), counts


# tokens a pass of the training layer holds rows for (``T x top_k`` rows
# each, the worst case, as no token is dropped)
TRAIN_CHUNK_TOKENS = 4096


def dropless_moe_train(x, router_w, w_gate_up, w_down, *, top_k: int,
                       first: int = 0, norm_topk_prob: bool = True):
    """The softmax-routed layer of :func:`dropless_moe`, differentiable
    (``gmm``'s VJP, ``tgmm`` for the weights, where the TPU runs it), with
    the Switch balancing loss over the router's full width:

        f_e = share of the tokens that chose e,  P_e = mean_t p_e(t)
        balance = E * sum_e f_e P_e               (E = router_w.shape[1])

    both over ALL this call's tokens and all E experts (the router is
    held whole). The tokens go through the experts
    ``TRAIN_CHUNK_TOKENS`` at a time, each chunk recomputed in the
    backward, so a layer holds one chunk's row buffers; exact, since the
    experts see each token alone. Returns ``(y (T, H), counts (chunks,
    E_held) int32, balance)``."""
    t = x.shape[0]
    e = router_w.shape[1]
    with jax.named_scope("moe.route"):
        probs, top_p, top_e = _route(x, router_w, top_k, "softmax", None,
                                     norm_topk_prob, 1.0)
        chose = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1)
        balance = e * jnp.sum(jnp.mean(chose, axis=0)
                              * jnp.mean(probs, axis=0))
    chunk = min(t, TRAIN_CHUNK_TOKENS)
    if t % chunk:
        raise ValueError(f"{t} tokens are not whole chunks of {chunk}")

    def one(args):
        return _held_experts(*args, w_gate_up, w_down, first, train=True)

    if chunk == t:
        y, counts = one((x, top_p, top_e))
        return y, counts[None], balance
    k = t // chunk
    y, counts = jax.lax.map(
        jax.checkpoint(one),
        (x.reshape(k, chunk, -1), top_p.reshape(k, chunk, top_k),
         top_e.reshape(k, chunk, top_k)))
    return y.reshape(t, -1), counts, balance


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

    def forward_train(self, x):
        """The differentiable layer (:func:`dropless_moe_train`; softmax
        router, no shared expert or selection bias). Returns ``(y,
        counts, balance)``: ``y`` shaped as ``x``; the assignments each
        held expert got, per chunk of ``TRAIN_CHUNK_TOKENS``, (chunks,
        count) int32; the balancing loss of these tokens."""
        if (self.score_func != "softmax" or self.expert_bias is not None
                or self.shared_gate_up is not None
                or self.route_scale != 1.0):
            raise NotImplementedError(
                "forward_train: the softmax router alone (no selection "
                "bias, shared expert or route scale)")
        shape = x.shape

        def fn(xv, rw, gu, dn):
            y, counts, balance = dropless_moe_train(
                xv.reshape(-1, shape[-1]), rw, gu, dn, top_k=self.top_k,
                first=self.first, norm_topk_prob=self.norm_topk_prob)
            return y.reshape(xv.shape), counts, balance

        return apply_op("dropless_moe_train", fn, x, self.router,
                        self.gate_up, self.down)
