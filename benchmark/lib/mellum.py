"""A sparse-expert decoder with window and full attention layers, trained
on one chip's share of an expert group: how its parameters and required
work are counted (``@arch``), how the system under test is put together
and checked (``@builder``: the TIMED step's first call, its loss, its
gradient as its optimizer state holds it and its update of the weights,
against the plain reference's loss, ``jax.grad`` and AdamW's first step),
the loop that drives it (``@loop``: the train loop's window, with the
train step's device-side expert counters read around it), and the
readers of what the windowed flash kernels, the differentiated expert
layer and the whole step add to the program.

Imports nothing of the program at module level: ``registry.load_all()``
imports this file for every cell, also on a checkout that has no such
model. There a reader finds no counter or kernel to read and returns
None.
"""

import gc
import math
import time

import numpy as np

from . import flops, trace
from .registry import arch, builder, loop, reader
from .system import (Phases, autocast, load_reference, lazy_model,
                     make_weights, sizes_of)
from .window import Window, span

# the grouped expert matmul and its weight-gradient twin in a device
# trace: upstream's kernels, by the names of their jits
EXPERT_KERNELS = ("gmm", "tgmm")
# the training flash kernels: every call's instruction holds one of these
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


# ------------------------------------------------------------- the counts
@arch("mellum")
def mellum_sizes(c: dict) -> dict:
    """GQA attention without q/k norm, a router over ``router_experts``
    and ``num_experts`` held SwiGLU experts at ``moe_intermediate_size``
    in every layer, an untied head over the vocabulary slice."""
    h, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    heads, kv_heads, d = (c["num_attention_heads"],
                          c["num_key_value_heads"], c["head_dim"])
    held, f = c["num_experts"], c["moe_intermediate_size"]
    router = h * (c.get("router_experts") or held)
    attention = 2 * h * d * (heads + kv_heads)
    expert = 3 * h * f
    per_layer = attention + router + held * expert
    kinds = c["layer_types"]
    return dict(
        matmul_params=L * per_layer + V * h,
        n_params=L * (per_layer + 2 * h) + 2 * V * h + h,
        layers=L, hidden=h, heads=heads, kv_heads=kv_heads, head_dim=d,
        expert_width=f, experts_held=held, top_k=c["num_experts_per_tok"],
        # what every token passes through: attention and router of every
        # layer, and the head slice (the embedding is a gather)
        dense_matmul_params=L * (attention + router) + V * h,
        window=c["sliding_window"],
        window_layers=sum(k == "sliding_attention" for k in kinds),
        full_layers=sum(k == "full_attention" for k in kinds))


def expert_train_flops(sizes: dict, assignments: float) -> float:
    """Forward + backward of the held experts: 6 h F FLOPs an
    assignment forward (three products of 2 h F), twice that backward."""
    return 18.0 * sizes["hidden"] * sizes["expert_width"] * assignments


def expert_train_bytes(sizes: dict, visits: float,
                       assignments: float) -> float:
    """Least HBM traffic of the same: each (layer, chunk) visit of an
    expert that got a row reads its bf16 weights in the forward and the
    backward's input gradient and writes its weight gradient (three
    times 3 h F x 2 bytes); each assignment's rows move in and out of the
    two products once forward and twice backward (bf16 in, float32 out)."""
    h, f = sizes["hidden"], sizes["expert_width"]
    weights = 3 * (3 * h * f * 2)
    rows = 3 * (2 * h + 4 * 2 * f + 2 * f + 4 * h)
    return float(visits) * weights + float(assignments) * rows


def attention_train_flops(sizes: dict, pairs: float) -> float:
    """Required attention FLOPs of forward + backward: per visible
    (query, key) pair and query head, QK^T and PV forward, QK^T again,
    dP, dV, dQ and dK backward, 2 d each."""
    return 14.0 * sizes["head_dim"] * sizes["heads"] * pairs


def step_flops(sizes: dict, tokens: float, assignments: float,
               pairs: float) -> float:
    """A step's required FLOPs (recomputation not counted): 6 a dense
    matmul parameter a token, 6 x 3 h F a held assignment, and 12 d per
    visible pair and query head (QK^T and PV, times three)."""
    dense = 6.0 * sizes["dense_matmul_params"] * tokens
    experts = 6.0 * 3 * sizes["hidden"] * sizes["expert_width"] * assignments
    attn = 12.0 * sizes["head_dim"] * sizes["heads"] * pairs
    return dense + experts + attn


# ------------------------------------------------------- the system
# the cell's optimizer, built with these and judged against them: AdamW
# (Loshchilov and Hutter, arXiv:1711.05101) with decoupled decay
LR, WEIGHT_DECAY, BETA1, BETA2, EPS = 1e-4, 0.01, 0.9, 0.999, 1e-8


class TrainMoESystem:
    """``system.TrainSystem``'s recipe (bf16 parameters, fp32 master
    weights, AdamW, O1 autocast), with what ``correct`` needs of the
    reference taken in set-up, before the step exists (its first call
    donates the weights): the reference's loss and ``jax.grad`` of the
    first batch, and the weights as they start. Both are kept on the
    host until the first step has been judged (:func:`judge_first_step`)."""

    def __init__(self, config, traffic, seed):
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.hapi import TrainStep

        self.phases = Phases()
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.sizes = sizes_of(config)
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq_len"])
        self.vocab = int(config["model"]["vocab_size"])
        self.cfg, self.model = lazy_model(config)
        self.phases.mark("import_and_model")
        weights = make_weights(self.model, self.seed)
        jax.block_until_ready(weights)
        self.phases.mark("weights")
        self._rng = np.random.default_rng([self.seed, 11])
        self.first_ids = self.next_ids()
        self.ref = load_reference(config["name"])
        self.ref_loss, self.ref_grads = reference_gradients(
            self.ref, weights, self.first_ids, config["model"])
        self.initial = {k: np.asarray(v) for k, v in weights.items()}
        self.phases.mark("reference_gradients")
        opt = paddle.optimizer.AdamW(
            LR, beta1=BETA1, beta2=BETA2, epsilon=EPS,
            parameters=self.model.parameters(), weight_decay=WEIGHT_DECAY,
            multi_precision=True)
        del weights                     # the step owns them now
        self.step = TrainStep(self.model, opt)
        self.phases.mark("train_step_built")
        self.devices = jax.devices()[:1]

    def next_ids(self) -> np.ndarray:
        """A fresh batch of token ids from the host-side iterator, over
        the configuration's vocabulary slice."""
        return self._rng.integers(0, self.vocab, (self.batch, self.seq + 1),
                                  dtype=np.int64).astype(np.int32)

    def stage(self, ids: np.ndarray):
        return self.step.stage(np.ascontiguousarray(ids[:, :-1]),
                               np.ascontiguousarray(ids[:, 1:]))


def reference_gradients(ref, weights, ids, model_cfg, **control):
    """The reference's loss of ``ids`` (b, s + 1) and its ``jax.grad``
    of float32 copies of ``weights``, the gradients pulled to the host
    one by one (float32 numpy). ``control``: the reference's
    ``matmul_dtype`` / ``all_full``."""
    import jax
    import jax.numpy as jnp

    def fn(w, ids):
        return jax.value_and_grad(lambda w: ref.loss(w, ids, model_cfg,
                                                     **control))(
            {k: v.astype(jnp.float32) for k, v in w.items()})

    # ids an argument, never a constant: one program for every seed
    loss, grads = jax.jit(fn)(weights, jnp.asarray(ids))
    host = {}
    for k in sorted(grads):
        host[k] = np.asarray(grads.pop(k))
    return float(loss), host


def group_errors(ref, sums: dict) -> dict:
    """``{group: sqrt(sum of squared differences / sum of squares)}``
    from per-parameter ``(difference, norm)`` sums, by the reference's
    ``group_of``."""
    out = {}
    for name, (diff, norm) in sums.items():
        d, n = out.get(ref.group_of(name), (0.0, 0.0))
        out[ref.group_of(name)] = (d + float(diff), n + float(norm))
    return {g: math.sqrt(d / n) if n > 0 else math.inf
            for g, (d, n) in sorted(out.items())}


def gradient_errors(ref, grads, ref_grads: dict) -> dict:
    """Each group's relative Frobenius error of ``grads`` (name ->
    array, on the device or the host) against the reference's."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(g, r):
        return {k: (jnp.sum((g[k].astype(jnp.float32) - r[k]) ** 2),
                    jnp.sum(r[k] ** 2)) for k in r}

    return group_errors(ref, jax.device_get(sums(grads, ref_grads)))


def judge_first_step(system) -> dict:
    """What the TIMED step did with the first batch, read from its state
    after its first call (before the second donates it), per parameter
    group:

    - ``grad_errors``: the step's own gradient, its first moment over
      ``1 - BETA1`` (AdamW's m_1 = (1 - beta1) g), against the
      reference's ``jax.grad``;
    - ``update_errors``: the step's change of the float32 master weights
      against AdamW's first step of that gradient (bias-corrected m and v
      are g and g^2: ``-LR (g / (|g| + EPS) + WEIGHT_DECAY p)``). A state
      left unchanged reads 1;
    - ``update_vs_reference``: the same change against AdamW's first step
      of the REFERENCE's gradient, read by no limit: the first step is
      about ``LR sign(g)``, so each gradient element whose sign the
      rounding flips counts as much as the largest."""
    import jax
    import jax.numpy as jnp

    state = system.step.opt_state

    @jax.jit
    def sums(p0, m1, master, r):
        out = {}
        for k in r:
            p = p0[k].astype(jnp.float32)
            g = m1[k] / (1.0 - BETA1)

            def step(g):
                return -LR * (g / (jnp.abs(g) + EPS) + WEIGHT_DECAY * p)
            done, mine, theirs = master[k] - p, step(g), step(r[k])
            out[k] = ((jnp.sum((g - r[k]) ** 2), jnp.sum(r[k] ** 2)),
                      (jnp.sum((done - mine) ** 2), jnp.sum(mine ** 2)),
                      (jnp.sum((done - theirs) ** 2), jnp.sum(theirs ** 2)))
        return out

    got = jax.device_get(sums(
        system.initial, {k: s["moment1"] for k, s in state["slots"].items()},
        state["master"], system.ref_grads))
    return {name: group_errors(system.ref, {k: v[i] for k, v in got.items()})
            for i, name in enumerate(("grad_errors", "update_errors",
                                      "update_vs_reference"))}


def verdict(ref, first_loss: float, ref_loss: float, grad_errors: dict,
            update_errors: dict = None) -> dict:
    """Whether the first loss, the gradients and (for the program) the
    update pass the reference's limits, and what is over."""
    diff = abs(first_loss - ref_loss)
    over = {g: e for g, e in grad_errors.items()
            if not e <= ref.GRAD_RTOL[g]}
    over.update({"update." + g: e for g, e in (update_errors or {}).items()
                 if not e <= ref.UPDATE_RTOL})
    return dict(correct=bool(math.isfinite(first_loss)
                             and diff <= ref.LOSS_ATOL and not over),
                loss_diff=diff, over=over)


@builder("train_moe")
def build_train_moe(config, traffic, seed, chips):
    return TrainMoESystem(config, traffic, seed)


# -------------------------------------------------------------- the loop
COUNTERS = ("moe_assignments", "moe_experts_touched")


def _counters(step) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in step.counters().items()}


@loop("train_moe")
def train_moe_loop(system, seed, seconds, traced):
    """The train loop's window (``loops.train_loop``: the same steps, the
    same scalars), with ``correct`` judging the first step's loss,
    gradient and update against the reference (:func:`judge_first_step`),
    and the train step's device-side expert counters read before the
    window opens and after it closes (each a sync outside the window)."""
    from .loops import Outcome

    step = system.step
    tokens_per_step = system.batch * system.seq
    with autocast():
        first_loss = float(step(system.stage(system.first_ids)))
    system.phases.mark("first_step")
    judged = judge_first_step(system)
    system.initial = system.ref_grads = None
    verdict_ = verdict(system.ref, first_loss, system.ref_loss,
                       judged["grad_errors"], judged["update_errors"])
    system.phases.mark("first_step_judged")
    staged = system.stage(system.next_ids())
    with autocast():
        float(step(staged))             # a second step: no retrace left
    staged = system.stage(system.next_ids())
    traces_before = step.trace_count
    before = _counters(step)
    system.phases.mark("second_step")
    # set-up's objects (the traced and compiled model among them) are
    # moved out of the collector's reach, as a long-running trainer
    # does: a full collection inside the window would walk them all
    gc.collect()
    gc.freeze()
    pauses = []

    def _timed(phase, info, t=[0.0]):
        if phase == "start":
            t[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - t[0])

    gc.callbacks.append(_timed)
    window = Window(traced)
    step_ms, losses = [], []
    window.open()
    t_prev = window.t_open
    while True:
        with span("bench.step"):
            with autocast():
                loss = step(staged)
            staged = system.stage(system.next_ids())
            with span("bench.pull_loss"):
                losses.append(float(loss))      # closes the step
        now = time.perf_counter()
        step_ms.append((now - t_prev) * 1e3)
        t_prev = now
        if now - window.t_open >= seconds:
            break
    window.close(at=t_prev)
    gc.callbacks.remove(_timed)
    gc.unfreeze()
    after = _counters(step)
    bad = sum(1 for v in losses if not math.isfinite(v))
    scalars = dict(window.counters,
                   tokens=float(len(losses) * tokens_per_step),
                   steps=float(len(losses)),
                   tokens_per_step=float(tokens_per_step),
                   window_s=window.seconds,
                   step_traces=float(step.trace_count - traces_before))
    if after:
        for k in COUNTERS:
            scalars[k] = float(after[k] - before[k])
        hist = after["moe_expert_hist"] - before["moe_expert_hist"]
        if hist.sum() > 0:
            scalars["expert_load_max_over_mean"] = float(hist.max()
                                                         / hist.mean())
    slowest = sorted(range(len(step_ms)), key=step_ms.__getitem__)[-3:]
    notes = dict(first_loss=first_loss, reference_loss=system.ref_loss,
                 loss_diff=verdict_["loss_diff"],
                 loss_atol=system.ref.LOSS_ATOL,
                 grad_rtol=system.ref.GRAD_RTOL,
                 update_rtol=system.ref.UPDATE_RTOL,
                 over=verdict_["over"], **judged,
                 slowest_steps_ms={i: step_ms[i] for i in slowest},
                 gc_in_window_s=dict(total=sum(pauses),
                                     longest=max(pauses, default=0.0),
                                     collections=len(pauses)),
                 last_loss=losses[-1], setup_phases_s=system.phases.seconds)
    return Outcome(dict(step_ms=step_ms), scalars, len(losses), bad,
                   verdict_["correct"] and bad == 0, window, notes)


# ------------------------------------------------------------ the readers
def _seconds_of(ctx, names):
    """Device seconds of the ops whose instruction name holds one of
    ``names``, averaged over the devices; None without a trace or
    without such an op."""
    ops = ctx.get("device_ops")
    if not ops:
        return None
    total = sum(dur for dev in ops.values() for text, _, dur in dev
                if any(n in trace.parse_hlo(text)[0] for n in names))
    return total / len(ops) / 1e9 or None


def _expert_seconds(ctx):
    """The grouped products: instructions named ``gmm`` / ``tgmm`` (an
    upstream jit's name, with the ``.N`` suffix XLA adds)."""
    ops = ctx.get("device_ops")
    if not ops:
        return None
    total = sum(dur for dev in ops.values() for text, _, dur in dev
                if trace.parse_hlo(text)[0].split(".")[0] in EXPERT_KERNELS)
    return total / len(ops) / 1e9 or None


def _pairs(ctx):
    s = ctx["scalars"]
    window, full = (s.get("train_attn_pairs_window"),
                    s.get("train_attn_pairs_full"))
    if window is None or full is None:
        return None
    return window + full


@reader("window_flash_roofline")
def window_flash_roofline(ctx):
    """The training flash calls' share of their roofline, forward and
    backward of the window and full layers together: the least time is
    the larger of the required FLOPs (``attention_train_flops`` of the
    visible pairs the train step counted, ``train_attn_pairs_*``) over
    the bf16 peak and the least bytes (``flops.flash_train_bytes`` a
    step) over the HBM bandwidth; over the device time of the ops named
    after the three kernels (the windowed calls' names end ``_window``)."""
    kernel_s = _seconds_of(ctx, FLASH_KERNELS)
    pairs = _pairs(ctx)
    sizes = ctx["sizes"]
    if not kernel_s or not pairs or "window_layers" not in sizes:
        return None
    t = ctx["traffic"]
    need_flops = attention_train_flops(sizes, pairs)
    need_bytes = ctx["scalars"]["steps"] * flops.flash_train_bytes(
        sizes, t["batch"], t["seq_len"])
    by_flops = need_flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = need_bytes / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["notes"]["window_flash_bound"] = ("compute" if by_flops >= by_bytes
                                          else "memory")
    return 100.0 * max(by_flops, by_bytes) / kernel_s


@reader("expert_train_roofline")
def expert_train_roofline(ctx):
    """The grouped products' share of their roofline, forward and
    backward: the larger of ``expert_train_flops`` of the held
    assignments (the step's device-side ``moe_assignments``) over the
    bf16 peak and ``expert_train_bytes`` (with ``moe_experts_touched``)
    over the HBM bandwidth, over the device time of ``gmm`` and
    ``tgmm``. Recomputed forwards are time and not work."""
    kernel_s = _expert_seconds(ctx)
    s, sizes = ctx["scalars"], ctx["sizes"]
    assigned, visits = s.get("moe_assignments"), s.get("moe_experts_touched")
    if not kernel_s or not assigned or not visits \
            or "expert_width" not in sizes:
        return None
    by_flops = (expert_train_flops(sizes, assigned)
                / ctx["peaks"]["bf16_flops_per_s"])
    by_bytes = (expert_train_bytes(sizes, visits, assigned)
                / ctx["peaks"]["hbm_bytes_per_s"])
    ctx["notes"]["expert_train_bound"] = ("compute" if by_flops >= by_bytes
                                          else "memory")
    return 100.0 * max(by_flops, by_bytes) / kernel_s


@reader("expert_train_share")
def expert_train_share(ctx):
    kernel_s = _expert_seconds(ctx)
    if not kernel_s or not ctx.get("busy_s"):
        return None
    return 100.0 * kernel_s / ctx["busy_s"]


@reader("moe_train_mfu")
def moe_train_mfu(ctx):
    """The whole step's share of the chip's peak: ``step_flops`` of the
    window's tokens, held assignments and visible pairs (all counted by
    the program) over the window and the published bf16 peak."""
    s, sizes = ctx["scalars"], ctx["sizes"]
    pairs, assigned = _pairs(ctx), s.get("moe_assignments")
    if not pairs or not assigned or "dense_matmul_params" not in sizes \
            or not s.get("window_s"):
        return None
    need = step_flops(sizes, s["tokens"], assigned, pairs)
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / s["window_s"] / peak
