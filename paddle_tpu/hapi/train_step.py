"""The jitted train step — the performance path.

The reference's per-op C++ eager dispatch amortizes overhead per op; on TPU
the idiomatic equivalent is ONE compiled XLA program per train step:
forward + backward + optimizer update, with params and optimizer state
living on-device across steps (donated buffers, so updates are in-place in
HBM). The eager tape (core/autograd) is the debug path; this is the fast
path — both run the same Layer code.

Sharding: pass a ``mesh`` and a ``param_spec_fn(name, value) -> PartitionSpec``
and the step becomes a GSPMD program: batch sharded over ``dp``/``sharding``
axes, params per the spec (fleet wrappers provide TP/ZeRO specs).

ZeRO (group_sharded) integration: ``group_sharded_parallel`` /
``DygraphShardingOptimizer`` stamp ``_group_sharded_level`` on the model /
optimizer; stage>=1 stores optimizer slots + master weights sharded over the
sharding axis, stage>=2 additionally constrains gradients to that sharding
(XLA emits reduce-scatter instead of all-reduce), stage 3 stores the params
themselves sharded (GSPMD all-gathers at use sites). Reference:
python/paddle/distributed/fleet/meta_parallel/sharding/.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import observability as obs
from ..core.tensor import Tensor
from ..jit import functional_call, tree_to_values
from ..optimizer.lr import LRScheduler
from ..optimizer.optimizer import Optimizer


class _TrainTelemetry:
    """Pre-bound registry handles for the train loop (resolved once per
    TrainStep; the probe attributes sync_count/trace_count stay the
    test surface — these mirror them onto the exportable registry)."""

    def __init__(self):
        r = obs.registry()
        t = obs.tracer()
        self.span = t.span
        # ---- the step clock: a step is the interval from one call of
        # the TrainStep to the next (the device's work and the caller's
        # loss pull lie between the calls)
        self.clock = t.step_clock("train")
        self.dispatch = t.phase("train.dispatch")
        self.steps = r.counter(
            "train_steps", "step intervals closed: one a call, by the "
            "next call or by sync()")
        self.dispatch_seconds = r.counter(
            "train_dispatch_seconds",
            "the host inside train.dispatch: the lr upload and the "
            "call of the compiled step until it returns")
        self.slow_steps = r.counter(
            "train_slow_steps",
            "step intervals over 0.1 s and over 3 times the mean of "
            "the 64 before them: each left a record in "
            "tracer().slow_steps()")
        self.slow_step_seconds = r.counter(
            "train_slow_step_seconds",
            "what those intervals ran over the mean they were held "
            "against")
        self.syncs = r.counter(
            "train_syncs", "host-blocking loss pulls (pull_metrics/sync)")
        self.throttles = r.counter(
            "train_throttles",
            "hard in-flight-window blocks (0 in a healthy loop)")
        self.traces = r.counter(
            "train_step_traces",
            "(re)traces of the jitted train step (steady state: 1)")
        self.in_flight = r.gauge(
            "train_in_flight",
            "dispatched-but-unsynced steps in the async window")
        self.staleness = r.gauge(
            "train_metrics_staleness",
            "steps between the displayed loss and the newest dispatch")


def _split_axes(spec) -> set:
    """Every mesh axis a PartitionSpec names."""
    return {n for e in spec if e is not None
            for n in ((e,) if isinstance(e, str) else e)}


class StagedBatch:
    """A batch already converted to raw arrays and placed on device with
    the step's data sharding — what :meth:`TrainStep.stage` returns and
    ``TrainStep.__call__`` accepts. Staging is async (``jax.device_put``
    dispatches without blocking), so a loader can stage batch N+1 while
    the device runs step N (double buffering)."""

    __slots__ = ("vals", "raw")

    def __init__(self, vals: Tuple[Any, ...], raw: Any = None):
        self.vals = vals
        self.raw = raw   # original loader batch (eager-fallback replay)


class TrainStep:
    def __init__(
        self,
        model,
        optimizer: Optimizer,
        loss_fn: Optional[Callable] = None,
        mesh: Optional[Mesh] = None,
        param_spec_fn: Optional[Callable[[str, Any], P]] = None,
        data_axes: Tuple[str, ...] = ("dp",),
        donate: bool = True,
        grad_accum_steps: int = 1,
        fused_grad_accum: bool = True,
        remat: bool = False,
        sharding_level: Optional[int] = None,
        sharding_axis: Optional[str] = None,
        gradient_merge_k: Optional[int] = None,
        gradient_merge_avg: bool = True,
        localsgd_k: Optional[int] = None,
        metrics_every: int = 0,
        max_in_flight: Optional[int] = None,
    ):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.grad_accum_steps = grad_accum_steps
        self.fused_grad_accum = fused_grad_accum
        # ---- async dispatch window (the TRAIN_AB_r05 lesson: the same
        # step runs MFU 0.4627 pipelined vs 0.2772 when the host pulls the
        # loss every step). __call__ never blocks; losses ride an in-flight
        # deque. With metrics_every=k, every k-th call host-pulls the loss
        # dispatched ~k steps ago (already computed -> near-zero wait,
        # displayed stale-by-k); sync() is the explicit hard barrier. The
        # max_in_flight cap (FLAGS_train_max_in_flight) bounds dispatch-
        # ahead so queued batches can't grow HBM without bound even when
        # the caller never pulls.
        if max_in_flight is None:
            from .. import flags
            max_in_flight = int(flags.get_flag("train_max_in_flight"))
        self.metrics_every = max(0, int(metrics_every))
        self.max_in_flight = max(1, int(max_in_flight))
        self._inflight: deque = deque()
        self.sync_count = 0      # host-blocking loss pulls (probe-visible)
        self.throttle_count = 0  # hard-window blocks (0 in a healthy loop)
        self._trace_count = 0    # step-fn retraces (probe-visible)
        self._m = _TrainTelemetry()
        # memwatch: bank the compiled step's CompiledMemoryStats when a
        # dispatch (re)traced (construction-time binding, r09 idiom)
        self._memwatch = obs.memory.enabled()
        self._memwatch_model_sig = None   # computed on first capture
        # fault-injection sites (paddle_tpu.testing.faults): bound at
        # construction — NULL stubs when FLAGS_fault_inject is unset
        from ..testing import faults
        self._f_dispatch = faults.site("train_dispatch")
        self._f_sync = faults.site("train_sync")
        self._traces_seen = 0    # registry mirror high-water mark
        self.last_metrics: Optional[Dict[str, Any]] = None
        self._last_loss: Optional[float] = None
        # ---- strategy-driven transforms (reference: fleet/meta_optimizers/
        # gradient_merge_optimizer.py + localsgd_optimizer.py as Program
        # passes; here they are jit transforms of the step). Explicit
        # kwargs win; otherwise the DistributedStrategy riding on a
        # fleet-wrapped optimizer turns them on.
        st = getattr(optimizer, "_strategy", None)
        if gradient_merge_k is None and st is not None \
                and getattr(st, "gradient_merge", False):
            cfg = st.gradient_merge_configs
            gradient_merge_k = int(cfg.get("k_steps", 1))
            gradient_merge_avg = bool(cfg.get("avg", True))
        self._lsgd_begin = 1
        if localsgd_k is None and st is not None \
                and getattr(st, "localsgd", False):
            localsgd_k = int(st.localsgd_configs.get("k_steps", 1))
            self._lsgd_begin = int(st.localsgd_configs.get("begin_step", 1))
        self.gradient_merge_k = max(1, int(gradient_merge_k or 1))
        self.gradient_merge_avg = gradient_merge_avg
        self.localsgd_k = max(1, int(localsgd_k or 1))
        if self.localsgd_k > 1 and self.gradient_merge_k > 1:
            raise ValueError("localsgd and gradient_merge are mutually "
                             "exclusive (as in the reference meta_optimizer "
                             "ordering)")
        params, buffers = model.raw_state()
        from ..jit import ensure_live
        ensure_live(params, "call prev_step.sync_to_model() before building "
                            "a new TrainStep (or pass donate=False).")
        self.buffers = buffers

        if mesh is not None:
            data_axes = tuple(a for a in data_axes if a in mesh.axis_names)
            self._data_sharding = NamedSharding(mesh, P(data_axes if data_axes else None))
            if param_spec_fn is None:
                # parallel layers annotate params (mp_layers sets dist_attr);
                # default spec_fn reads those annotations
                declared = {
                    name: getattr(p, "dist_attr", None)
                    for name, p in model.named_parameters()
                }

                def spec_fn(name, v, _d=declared):
                    spec = _d.get(name)
                    if spec is None:
                        return P()
                    # drop axes absent from this mesh (e.g. layer built for
                    # mp but trained on a dp-only mesh)
                    entries = []
                    for e in spec:
                        if e is None:
                            entries.append(None)
                            continue
                        names = tuple(n for n in
                                      ((e,) if isinstance(e, str) else e)
                                      if n in mesh.axis_names)
                        entries.append(names[0] if len(names) == 1
                                       else (names or None))
                    return P(*entries)
            else:
                spec_fn = param_spec_fn

            # ---- ZeRO / group_sharded: resolve stage + sharding axis from
            # the wrappers' declarations (or explicit kwargs)
            from ..distributed.fleet.meta_parallel.sharding import (
                extend_spec_with_sharding, resolve_sharding_axis)
            level = sharding_level
            if level is None:
                level = max(getattr(optimizer, "_group_sharded_level", 0),
                            getattr(model, "_group_sharded_level", 0))
            axis = (sharding_axis
                    or getattr(optimizer, "_sharding_axis", None)
                    or getattr(model, "_sharding_axis", None))
            if level and (axis is None or axis not in mesh.shape
                          or mesh.shape[axis] <= 1):
                axis = resolve_sharding_axis(mesh)
            if axis is None:
                level = 0
            self.sharding_level, self.sharding_axis = level, axis

            param_specs = {k: spec_fn(k, v) for k, v in params.items()}
            # how this step lays activations out, for code that must
            # split by hand what GSPMD cannot (a Mosaic kernel): batch
            # rows over the data axes, and — Megatron TP — heads over
            # the one other axis the declared parameter specs name
            model_axes = set().union(
                *map(_split_axes, param_specs.values())) - set(data_axes)
            self._layout = (data_axes, model_axes.pop()
                            if len(model_axes) == 1 else None)
            if level >= 3:
                # honor GroupShardedStage3(exclude_layer=...) — the wrapper
                # records excluded param ids, extension happens only here
                excluded = getattr(model, "_sharding_exclude_ids", set())
                named = dict(model.named_parameters())
                param_specs = {
                    k: (s if id(named.get(k)) in excluded else
                        extend_spec_with_sharding(
                            s, params[k].shape, mesh, axis))
                    for k, s in param_specs.items()}
            self.param_shardings = {
                k: NamedSharding(mesh, s) for k, s in param_specs.items()}
            if level >= 1:
                self.opt_shardings = {
                    k: NamedSharding(mesh, extend_spec_with_sharding(
                        param_specs[k], params[k].shape, mesh, axis))
                    for k in params}
            else:
                self.opt_shardings = dict(self.param_shardings)
            params = {
                k: jax.device_put(v, self.param_shardings[k])
                for k, v in params.items()
            }
        else:
            self._data_sharding = None
            self.param_shardings = None
            self.opt_shardings = None
            self.sharding_level, self.sharding_axis = 0, None
            self._layout = None

        self.params = params
        if hasattr(optimizer, "resolve_decay_masks"):
            # evaluate weight-decay exclusion callbacks against Parameters
            # (eager contract) once, keyed by pytree key, so the jitted
            # path applies the identical mask
            optimizer.resolve_decay_masks(dict(model.named_parameters()))
        self.opt_state = optimizer.init_state_tree(params)
        if self.param_shardings is not None:
            # optimizer slots inherit their parameter's sharding, extended by
            # the ZeRO axis at stage>=1 (optimizer-state sharding)
            new_slots = {}
            for k, slot in self.opt_state["slots"].items():
                new_slots[k] = jax.tree.map(
                    lambda s, _k=k: jax.device_put(s, self.opt_shardings[_k]),
                    slot)
            self.opt_state["slots"] = new_slots
            if self.opt_state.get("master"):
                self.opt_state["master"] = {
                    k: jax.device_put(v, self.opt_shardings[k])
                    for k, v in self.opt_state["master"].items()}

        # device-side counters a model adds up over the steps
        # (``train_counters()``: name -> zeros; its forward then takes
        # ``return_counters=True`` and returns ``(loss, this step's)``),
        # carried through the step ({} for a model that keeps none) and
        # read with ``counters()``; and what the model counts from the
        # batch's shape alone (``attention_pairs(batch, seq)``), counted
        # on the host a call, into the registry
        counted = hasattr(model, "train_counters")
        self._counters = model.train_counters() if counted else {}
        if counted and (self.grad_accum_steps > 1 or self.localsgd_k > 1
                        or self.gradient_merge_k > 1):
            raise NotImplementedError(
                "train counters with gradient accumulation, merge or "
                "localsgd")
        self._attention_pairs = getattr(model, "attention_pairs", None)
        extra = {"return_counters": True} if counted else {}

        def model_loss(p, batch):
            if self.loss_fn is not None:
                from ..core import autograd
                from ..jit import tree_to_tensors
                out = functional_call(model, p, *batch[:-1], buffers=self.buffers)
                # loss_fn is user code over Tensors (a paddle loss Layer or
                # lambda); run it under the functional guard and unwrap
                with autograd.functional_guard():
                    loss = self.loss_fn(tree_to_tensors(out),
                                        tree_to_tensors(batch[-1]))
                return tree_to_values(loss)
            # default: the model returns the scalar loss itself
            return functional_call(model, p, *batch, buffers=self.buffers,
                                   **extra)

        def loss_of(p, batch):
            if mesh is None:
                return model_loss(p, batch)
            from ..kernels.flash_attention import activation_layout
            with activation_layout(mesh, *self._layout):
                return model_loss(p, batch)

        if remat:
            loss_of = jax.checkpoint(loss_of)

        if self.localsgd_k > 1:
            self._build_localsgd_step(loss_of, donate)
            return
        self._merge = None
        if self.gradient_merge_k > 1:
            # gradient merge: accumulate grads across k CALLS, update every
            # k-th (reference GradientMergeOptimizer). The buffer + counter
            # ride the jit boundary like opt_state (donated).
            zeros = jax.tree.map(jnp.zeros_like, self.params)
            if self.opt_shardings is not None:
                zeros = {k: jax.device_put(v, self.opt_shardings[k])
                         for k, v in zeros.items()}
            self._merge = (zeros, jnp.zeros((), jnp.int32))

        def compute_loss_grads(params, batch):
            if self.grad_accum_steps > 1:
                micro = [jax.tree.map(
                    lambda b: b.reshape(self.grad_accum_steps,
                                        b.shape[0] // self.grad_accum_steps,
                                        *b.shape[1:]), b) for b in batch]

                if self.fused_grad_accum:
                    # fused dW accumulation (reference:
                    # fused_linear_param_grad_add_kernel.cu): put the
                    # microbatch loop INSIDE the differentiated function,
                    # so the scan TRANSPOSE owns the single gradient
                    # accumulator (an aliased loop carry) and each dW
                    # matmul can fuse into its += epilogue. Measured
                    # compiled temp size equals the unfused path (XLA
                    # aliases that path's carries too) — the difference
                    # is the guaranteed in-loop accumulate (HBM traffic),
                    # not capacity. checkpoint bounds forward-activation
                    # residency to one microbatch (the eager behavior).
                    inner = loss_of if remat else jax.checkpoint(loss_of)

                    def total_loss(params):
                        def body(acc, mb):
                            return acc + inner(params, mb), None

                        s, _ = jax.lax.scan(body, jnp.zeros(()),
                                            tuple(micro))
                        return s / self.grad_accum_steps

                    loss, grads = jax.value_and_grad(total_loss)(params)
                else:
                    def acc_fn(carry, mb):
                        loss, g = jax.value_and_grad(loss_of)(params, mb)
                        return (carry[0] + loss,
                                jax.tree.map(jnp.add, carry[1], g)), None

                    zero = (jnp.zeros(()),
                            jax.tree.map(jnp.zeros_like, params))
                    (loss_sum, grads), _ = jax.lax.scan(
                        acc_fn, zero, tuple(micro))
                    loss = loss_sum / self.grad_accum_steps
                    grads = jax.tree.map(
                        lambda g: g / self.grad_accum_steps, grads)
            elif counted:
                (loss, counts), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, batch)
            else:
                loss, grads = jax.value_and_grad(loss_of)(params, batch)
            if self.sharding_level >= 2:
                # ZeRO-2: pin grads to the opt-state sharding so XLA lowers
                # the dp-sum to a reduce-scatter onto owner shards
                grads = {
                    k: jax.lax.with_sharding_constraint(
                        g, self.opt_shardings[k])
                    for k, g in grads.items()}
            return loss, grads, (counts if counted else {})

        def apply_update(params, opt_state, grads, lr):
            new_params, new_state = optimizer.functional_update(
                params, grads, opt_state, lr)
            if self.param_shardings is not None:
                # keep output layouts identical to inputs (donation + steady
                # state across steps; ZeRO update stays on the shard)
                new_params = {
                    k: jax.lax.with_sharding_constraint(
                        v, self.param_shardings[k])
                    for k, v in new_params.items()}
                new_state["slots"] = {
                    k: jax.tree.map(
                        lambda s, _k=k: jax.lax.with_sharding_constraint(
                            s, self.opt_shardings[_k]), slot)
                    for k, slot in new_state["slots"].items()}
                if new_state.get("master"):
                    new_state["master"] = {
                        k: jax.lax.with_sharding_constraint(
                            v, self.opt_shardings[k])
                        for k, v in new_state["master"].items()}
            return new_params, new_state

        def train_step(params, opt_state, counters, lr, *batch):
            self._trace_count += 1   # python body runs only while tracing
            loss, grads, counts = compute_loss_grads(params, batch)
            new_params, new_state = apply_update(params, opt_state, grads, lr)
            counters = {k: v + counts[k] for k, v in counters.items()}
            return loss, new_params, new_state, counters

        def train_step_merge(params, opt_state, merge, lr, *batch):
            self._trace_count += 1
            loss, grads, _ = compute_loss_grads(params, batch)
            buf, count = merge
            buf = jax.tree.map(jnp.add, buf, grads)
            count = count + 1
            kk = self.gradient_merge_k

            def do(op):
                p, s, b = op
                g = (jax.tree.map(lambda x: x / kk, b)
                     if self.gradient_merge_avg else b)
                np_, ns = apply_update(p, s, g, lr)
                return np_, ns, jax.tree.map(jnp.zeros_like, b)

            params, opt_state, buf = jax.lax.cond(
                count % kk == 0, do, lambda op: op,
                (params, opt_state, buf))
            return loss, params, opt_state, (buf, count)

        donate_argnums = (0, 1, 2) if donate else ()
        if self._merge is not None:
            self._jit_step = jax.jit(train_step_merge,
                                     donate_argnums=donate_argnums)
        else:
            self._jit_step = jax.jit(train_step,
                                     donate_argnums=donate_argnums)
        self._step_count = 0

    def _build_localsgd_step(self, loss_of, donate):
        """LocalSGD (reference: fleet/meta_optimizers/localsgd_optimizer.py):
        each dp worker updates a LOCAL parameter copy with purely local
        gradients (no per-step dp all-reduce); every ``k_steps`` the copies
        average across dp. TPU-native formulation: parameters and optimizer
        state carry a leading dp axis sharded ``P('dp')`` and the local
        step is ``jax.vmap`` over that axis — XLA partitions the mapped
        program with ZERO inter-chip communication, and the periodic
        average is the only collective (comm volume cut by ~k vs plain
        DP). Scope matches the reference meta optimizer: pure data
        parallelism (no TP/ZeRO/grad-accum composition)."""
        mesh, optimizer = self.mesh, self.optimizer
        if mesh is None or "dp" not in mesh.shape or mesh.shape["dp"] <= 1:
            raise ValueError("localsgd needs a mesh with a dp axis > 1")
        if self.grad_accum_steps > 1 or self.sharding_level:
            raise NotImplementedError(
                "localsgd composes with plain DP only (reference "
                "LocalSGDOptimizer has the same scope)")
        for k, sh in (self.param_shardings or {}).items():
            if sh.spec != P():
                raise NotImplementedError(
                    f"localsgd needs replicated params; {k!r} declares "
                    f"{sh.spec}")
        dp = mesh.shape["dp"]
        self._lsgd_dp = dp
        stack_sh = {
            k: NamedSharding(mesh, P("dp"))
            for k in self.params}
        self.params = {
            k: jax.device_put(
                jnp.broadcast_to(jnp.asarray(v)[None],
                                 (dp,) + tuple(np.shape(v))),
                stack_sh[k])
            for k, v in self.params.items()}
        self.param_shardings = stack_sh
        self.opt_state = jax.tree.map(
            lambda s: jnp.broadcast_to(jnp.asarray(s)[None],
                                       (dp,) + tuple(np.shape(s))),
            self.opt_state)
        self._lsgd_count = jnp.zeros((), jnp.int32)
        kk = self.localsgd_k
        begin = int(getattr(self, "_lsgd_begin", 1))

        def local(p, s, lr, mb):
            loss, g = jax.value_and_grad(loss_of)(p, mb)
            np_, ns = optimizer.functional_update(p, g, s, lr)
            return loss, np_, ns

        def train_step_localsgd(params, opt_state, count, lr, *batch):
            self._trace_count += 1
            micro = tuple(jax.tree.map(
                lambda b: b.reshape((dp, b.shape[0] // dp) + b.shape[1:]),
                b) for b in batch)
            losses, new_p, new_s = jax.vmap(
                local, in_axes=(0, 0, None, 0))(params, opt_state, lr,
                                                micro)
            count = count + 1

            def sync(t):
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        jnp.mean(x, axis=0, keepdims=True), x.shape), t)

            # reference localsgd warmup (begin_step): dense DP — i.e. a
            # sync every step — until step ``begin_step``, from which
            # local updates are allowed to drift (default 1 = no warmup)
            do_sync = jnp.logical_or(count < begin, count % kk == 0)
            new_p = jax.lax.cond(do_sync, sync, lambda t: t, new_p)
            new_p = {k: jax.lax.with_sharding_constraint(v, stack_sh[k])
                     for k, v in new_p.items()}
            return jnp.mean(losses), new_p, new_s, count

        self._merge = None
        self._jit_step = jax.jit(
            train_step_localsgd, donate_argnums=(0, 1, 2) if donate else ())
        self._step_count = 0

    def stage(self, *batch) -> StagedBatch:  # tracecheck: hotpath
        """Convert + place a batch on device (async dispatch, never
        blocks). ``__call__`` accepts the result directly, so a prefetching
        loader can stage batch N+1 while the device runs step N."""
        # once per staged batch  # tracecheck: disable=TRC007
        with self._m.span("train.stage"):
            vals = tuple(tree_to_values(b) for b in batch)
            if self._data_sharding is not None:
                if jax.process_count() > 1:
                    # multi-host: each process feeds its LOCAL batch shard
                    # (what its DataLoader/DistributedBatchSampler yields);
                    # the global array spans the mesh (reference analogue:
                    # per-trainer readers + NCCL data parallel). Per-leaf so
                    # pytree batch elements work like the single-process path
                    vals = tuple(jax.tree.map(
                        lambda leaf: jax.make_array_from_process_local_data(
                            self._data_sharding, np.asarray(leaf)), v)
                        for v in vals)
                else:
                    vals = tuple(jax.device_put(v, self._data_sharding)
                                 for v in vals)
            else:
                # unsharded: an explicit async H2D here (instead of letting
                # the jit dispatch do it) is what overlaps input transfer
                # with the previous step's compute
                vals = tuple(jax.tree.map(
                    lambda leaf: leaf if isinstance(leaf, jax.core.Tracer)
                    else jax.device_put(leaf), v) for v in vals)
        return StagedBatch(vals)

    def __call__(self, *batch) -> Tensor:  # tracecheck: hotpath
        # the injected train_dispatch failure fires HERE, before any
        # state mutates: params/opt_state still hold live buffers (the
        # donating call below never ran), so fit's recovery can sync to
        # last-good state and simply re-dispatch the same batch
        self._f_dispatch.check()
        self._observe_step_clock()
        if len(batch) == 1 and isinstance(batch[0], StagedBatch):
            vals = batch[0].vals
        else:
            vals = self.stage(*batch).vals
        # the host's share of a step: the lr upload and the call of the
        # compiled program until it returns (it does not wait for the
        # device)  # tracecheck: disable=TRC007
        with self._m.span("train.dispatch", step=self._step_count):
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            if getattr(self, "_lsgd_count", None) is not None:
                loss, self.params, self.opt_state, self._lsgd_count = \
                    self._jit_step(self.params, self.opt_state,
                                   self._lsgd_count, lr, *vals)
            elif self._merge is not None:
                loss, self.params, self.opt_state, self._merge = \
                    self._jit_step(self.params, self.opt_state,
                                   self._merge, lr, *vals)
            else:
                loss, self.params, self.opt_state, self._counters = \
                    self._jit_step(self.params, self.opt_state,
                                   self._counters, lr, *vals)
        if self._attention_pairs is not None:
            self._observe_pairs(vals[0].shape)
        if isinstance(self.optimizer._lr, LRScheduler):
            self.optimizer._lr.step()
        self._step_count += 1
        self._inflight.append((self._step_count - 1, loss))
        if self.metrics_every and self._step_count % self.metrics_every == 0:
            self.pull_metrics()
        while len(self._inflight) > self.max_in_flight:
            # HBM safety net: a caller that never pulls still can't run
            # dispatch unboundedly ahead of the chip. Already-executed
            # entries (a classic caller float()ing every returned loss
            # keeps the chip fully synced) retire for free — no transfer,
            # no throttle; only a genuinely outstanding oldest step costs
            # a wait, through the host pull of its loss.
            _, old = self._inflight.popleft()
            ready = getattr(old, "is_ready", None)
            if ready is not None and ready():
                continue
            # deliberate bounded sync — the documented HBM safety net;
            # a span, so the wait is not read as dispatch cost
            # tracecheck: disable=TRC007
            with self._m.span("train.throttle"):
                # tracecheck: disable=TRC002
                np.asarray(old)
            self.throttle_count += 1
            # throttles must be visible in exported snapshots (a nonzero
            # rate means the caller never pulls)
            # tracecheck: disable=TRC007
            self._m.throttles.inc()
        # gauge AFTER the pull/throttle drains: it must read what is
        # actually still outstanding, not the pre-drain peak
        self._observe_dispatch(vals)
        return Tensor(loss, stop_gradient=True)

    def counters(self) -> Dict[str, np.ndarray]:
        """The model's device-side counters (``train_counters``) summed
        over every step so far, pulled to the host (a sync); {} for a
        model that keeps none."""
        return {k: np.asarray(v) for k, v in self._counters.items()}

    def _observe_pairs(self, shape) -> None:
        """Add this call's attention pairs (the model's
        ``attention_pairs`` of the batch's (batch, seq)) to the
        registry's ``train_attn_pairs_<kind>`` counters."""
        for kind, n in self._attention_pairs(*shape[:2]).items():
            obs.registry().counter(
                f"train_attn_pairs_{kind}",
                f"query-key pairs the {kind} attention layers must "
                f"compute, over the steps called").inc(n)

    def _observe_step_clock(self, reopen: bool = True) -> None:
        """A call begins (or ``sync()`` ends the loop's run of steps):
        close the interval the last call opened, publish its length's
        verdict and its dispatch seconds, and open the next."""
        m = self._m
        if m.clock.open:
            _, over = m.clock.end(self._step_count - 1, self._trace_count)
            m.steps.inc()
            m.dispatch_seconds.inc(m.dispatch.in_step)
            if over:
                m.slow_steps.inc()
                m.slow_step_seconds.inc(over)
        if reopen:
            m.clock.begin(self._trace_count)

    def _observe_dispatch(self, vals=None) -> None:
        """Post-dispatch host-side telemetry: async-window depth and the
        retrace mirror (trace_count deltas observed HERE, on the host
        side of the jit boundary — never inside the traced body). A
        detected (re)trace additionally banks the step's
        CompiledMemoryStats under memwatch — an AOT lower over the
        post-donation state (``self.params`` already holds the returned
        live arrays with identical avals)."""
        m = self._m
        m.in_flight.set(len(self._inflight))
        if self._trace_count != self._traces_seen:
            m.traces.inc(self._trace_count - self._traces_seen)
            self._traces_seen = self._trace_count
            if self._memwatch and vals is not None:
                self._observe_compiled_memory(vals)

    def _observe_compiled_memory(self, vals) -> None:
        """Bank the jitted step's memory sections (memwatch). One
        duplicate lower+compile per (re)trace — steady state pays
        nothing; failures count, never raise. The lr scalar is rebuilt
        here (same aval as the dispatch's) rather than threaded through
        from ``__call__``."""
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        try:
            batch_dim = int(jax.tree.leaves(vals)[0].shape[0])
        except Exception:
            batch_dim = 0
        if getattr(self, "_lsgd_count", None) is not None:
            args = (self.params, self.opt_state, self._lsgd_count,
                    lr, *vals)
            extra = ("localsgd",)
        elif self._merge is not None:
            args = (self.params, self.opt_state, self._merge, lr, *vals)
            extra = ("gradient_merge",)
        else:
            args = (self.params, self.opt_state, self._counters, lr, *vals)
            extra = ()
        # model label = signature prefix, like the serving path: two
        # differently-sized models of one class must not collide in the
        # program table (class name alone would, last write winning)
        sig = self._memwatch_model_sig
        if sig is None:
            from ..generation.program_cache import model_signature
            try:
                sig = model_signature(self.model)[:8]
            except Exception:
                sig = type(self.model).__name__
            self._memwatch_model_sig = sig
        obs.memory.capture_program("train_step", batch_dim, extra,
                                   self._jit_step, args, model=sig)

    # -------------------------------------------------------- async metrics
    def pull_metrics(self, lag: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """Async metrics pull: host-read the loss dispatched ``lag`` steps
        ago (default ``metrics_every``), dropping older in-flight entries
        unread. The pulled value is normally already computed, so this
        costs one host round-trip, not a pipeline drain — the displayed
        loss is simply stale-by-``lag``. Counts as one blocking sync.
        Returns ``{"loss", "loss_step", "staleness"}`` (the previous
        metrics when nothing is old enough to pull yet)."""
        # injected train_sync failure fires before any window mutation,
        # so a caller can retry the pull verbatim
        self._f_sync.check()
        lag = (self.metrics_every or 1) if lag is None else max(0, int(lag))
        target = self._step_count - lag
        picked = None
        while self._inflight and self._inflight[0][0] <= target:
            picked = self._inflight.popleft()
        if picked is None:
            return self.last_metrics
        idx, dev = picked
        # a host pull, not block_until_ready: the value is what the
        # caller wants. The span is the pull's wall clock: near zero
        # when the loss was dispatched >= k steps ago
        # the k-step metrics cadence, not per-step
        # tracecheck: disable=TRC007
        with self._m.span("train.pull_metrics", step=idx):
            val = float(np.asarray(dev))
        self.sync_count += 1
        self._last_loss = val
        self.last_metrics = {"loss": val, "loss_step": idx,
                             "staleness": self._step_count - 1 - idx}
        # once per pull (every k steps)  # tracecheck: disable=TRC007
        self._m.syncs.inc()
        self._m.staleness.set(self.last_metrics["staleness"])
        self._m.in_flight.set(len(self._inflight))
        return self.last_metrics

    def sync(self) -> Optional[float]:
        """Hard barrier: block until every dispatched step has executed
        (per-device execution order is dispatch order) and return the
        latest loss. Epoch ends, checkpoints and early-stop decisions
        belong here — not in the per-step loop."""
        self._f_sync.check()
        if self._inflight:
            idx, dev = self._inflight[-1]
            self._inflight.clear()
            with self._m.span("train.sync", step=idx):
                self._last_loss = float(np.asarray(dev))
            self.sync_count += 1
            self.last_metrics = {"loss": self._last_loss, "loss_step": idx,
                                 "staleness": 0}
            self._m.syncs.inc()
            self._m.staleness.set(0)
            self._m.in_flight.set(0)
        # what follows a barrier (an evaluation, a checkpoint) is no
        # part of a step
        self._observe_step_clock(reopen=False)
        return self._last_loss

    @property
    def trace_count(self) -> int:
        """How many times the step function has been (re)traced — the
        zero-retrace probe: a steady-state loop must hold this at 1."""
        return self._trace_count

    # ------------------------------------------------------------- utilities
    def sync_to_model(self) -> None:
        """Write the on-device params back into the Layer's Tensors
        (for state_dict / eager eval). Under localsgd the dp-stacked
        copies collapse to their mean — exactly the value the next sync
        barrier would install on every worker."""
        params = self.params
        if getattr(self, "_lsgd_dp", None):
            params = {k: jnp.mean(v, axis=0) for k, v in params.items()}
        self.model.load_raw_state(params)

    def state_dict(self) -> Dict[str, Any]:
        self.sync_to_model()
        sd = self.model.state_dict()
        sd["@opt_state"] = jax.tree.map(np.asarray, self.opt_state)
        return sd

    def set_state_dict(self, sd: Dict[str, Any]) -> None:
        opt = sd.pop("@opt_state", None)
        self.model.set_state_dict(sd)
        params, _ = self.model.raw_state()
        if getattr(self, "_lsgd_dp", None):
            # restack to the (dp, ...) layout the compiled step expects;
            # a loaded checkpoint starts all workers synced
            dp = self._lsgd_dp
            params = {k: jnp.broadcast_to(jnp.asarray(v)[None],
                                          (dp,) + tuple(np.shape(v)))
                      for k, v in params.items()}
        if self.param_shardings is not None:
            params = {k: jax.device_put(v, self.param_shardings[k])
                      for k, v in params.items()}
        self.params = params
        if opt is not None:
            self.opt_state = jax.tree.map(jnp.asarray, opt)

    def lower(self, *batch):
        """``jax.stages.Lowered`` of the plain step at this batch's avals
        (a :class:`StagedBatch` or raw batch elements): ``.as_text()``
        shows which kernels the step took, ``.compile()`` its memory and
        collectives. Trace-time context (``amp.auto_cast``) is the
        caller's, as for ``__call__``."""
        if len(batch) == 1 and isinstance(batch[0], StagedBatch):
            vals = batch[0].vals
        else:
            vals = tuple(tree_to_values(b) for b in batch)
        lr = jnp.asarray(0.0, jnp.float32)
        return self._jit_step.lower(self.params, self.opt_state,
                                    self._counters, lr, *vals)

    def compile_stats(self, *batch):
        compiled = self.lower(*batch).compile()
        try:
            return compiled.cost_analysis()
        except Exception:
            return {}
