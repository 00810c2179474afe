"""Zero-bubble (ZBH1) pipeline schedule.

Reference: the ZBH1 mode of
python/paddle/distributed/passes/pipeline_scheduler_pass (zero-bubble
pipeline: split each backward into B = dx, the critical path, and
W = dW, deferrable, and fill pipeline bubbles with W work).

TPU-native formulation. The other schedules here (pipeline_parallel.py)
are LOCKSTEP: a vmap over the pp-sharded stage axis runs the SAME program
on every stage each tick, with fill/drain ticks masked — masked work still
executes, so the bubble burns real compute and no schedule permutation can
recover it. Zero bubble therefore needs per-stage DIVERGENT execution,
which on TPU is ``shard_map`` over the pp axis with ``lax.cond``-gated
work units: cond executes only the taken branch at runtime, so a tick
costs max-over-stages of the unit each stage actually runs, and ticks
where a stage has no unit cost it ~nothing.

Units per (stage, microbatch):
  F  forward through the stage's L blocks (stage 0 prepends the prefix /
     embedding; stage S-1 stores y for its B unit)
  B  dx-only backward (stage S-1 first runs suffix+loss and seeds the
     gradient; stage 0 stores its dx for the deferred prefix backward);
     sends dx down the ring
  W  the deferred parameter gradient (stage 0's W also runs the prefix
     backward) — the ZBH1 split
A greedy static scheduler (numpy, trace time) assigns at most one unit
per stage per tick with priority B > F > W — W fills what would be bubble
ticks. Ring messages (activations up, dx down) move via ppermute every
tick and are stashed into per-microbatch buffers on arrival, driven by
static stash tables (a message's slot is known from the schedule), so a
busy receiver can consume it any later tick.

Exactness: loss is computed per microbatch at stage S-1 and averaged —
mean of equal-size microbatch means == the full-batch mean for token-mean
criteria (suffixes must be per-token, which final-norm + head are).
Parity vs the serial model is pinned by tests/test_zbh1.py.

Cost model (per microbatch per stage, F = one forward): F + (Fr + Bdx)
+ (Fr + Bdw) ~ 5F vs the lockstep schedules' 4F — the extra forward
recompute is the price of decoupling W from B in a pure functional
program. The payoff is scheduling freedom: steady-state ticks cost
~max(2F) and fill/drain ticks shrink toward zero instead of burning
masked slots, so wall-clock beats lockstep once the bubble fraction
(S-1)/(M+S-1) outweighs the extra recompute.

Composition (round 4 lifts the v1 scope):
  - tied/shared layers: the tied weights ride as a third replicated param
    group ``shared_params`` visible to BOTH phases; stage 0 accumulates
    the prefix-side contribution (in W's deferred prefix backward) and
    stage S-1 the suffix-side one (in B's loss vjp), summed by the final
    masked psum — the cross-phase gradient routing the reference's shared
    comm group performs with an allreduce.
  - mp (tensor parallel): the shard_map is manual over the WHOLE mesh
    (check_vma=False) — GSPMD-auto collectives inside the divergent
    lax.cond units are unsound (stages take different branches,
    desynchronizing compiler-inserted collectives; observed as an XLA
    rendezvous deadlock). The TP layers detect manual mp via
    ``_manual_axis()`` and switch to explicit Megatron f/g collectives
    (mp_layers._mp_copy/_mp_reduce), which ARE sound inside units:
    every member of an mp group shares its pp stage and hence its
    branch. A NEW TP layer must get the same treatment — GSPMD will
    not handle it here.
  - ZeRO: levels 1/2 (optimizer-state / gradient sharding) compose — the
    functional optimizer update and the grad resharding happen OUTSIDE
    the manual region. Level 3 (param sharding) stays rejected: P()
    in_specs would all-gather the full parameter state at shard_map
    entry every step with no GSPMD control over the gather's placement.

Remaining v1 scope: V == 1 (no interleaved VPP), no abstract lowering.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def zbh1_schedule(S: int, M: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Greedy ZBH1 tables: (F, B, W), each (T, S), holding the microbatch
    index a stage processes at that tick, or -1. Priority B > F > W."""
    f_time = np.full((S, M), -1)
    b_time = np.full((S, M), -1)
    next_f = [0] * S
    next_b = [0] * S
    next_w = [0] * S
    rows_f, rows_b, rows_w = [], [], []
    t = 0
    cap = 6 * (M + S) + 8
    while any(n < M for n in next_w) and t < cap:
        rf, rb, rw = [-1] * S, [-1] * S, [-1] * S
        for s in range(S):
            m = next_b[s]
            b_ready = m < M and (
                (s == S - 1 and 0 <= f_time[s][m] < t)
                or (s < S - 1 and 0 <= b_time[s + 1][m] < t))
            mf = next_f[s]
            f_ready = mf < M and (s == 0 or 0 <= f_time[s - 1][mf] < t)
            if b_ready:
                rb[s] = m
                b_time[s][m] = t
                next_b[s] += 1
            elif f_ready:
                rf[s] = mf
                f_time[s][mf] = t
                next_f[s] += 1
            elif next_w[s] < next_b[s]:
                rw[s] = next_w[s]
                next_w[s] += 1
        rows_f.append(rf)
        rows_b.append(rb)
        rows_w.append(rw)
        t += 1
    if any(n < M for n in next_w):
        raise RuntimeError(f"zbh1 schedule did not complete in {cap} ticks")
    return (np.asarray(rows_f, np.int32), np.asarray(rows_b, np.int32),
            np.asarray(rows_w, np.int32))


def _stash_tables(Ft, Bt, S):
    """stash_f[t][s]: slot where the activation arriving at stage s at the
    START of tick t belongs (= what s-1 forwarded at t-1); stash_b the
    same for dx arriving from s+1. -1 = nothing arrived."""
    T = Ft.shape[0]
    sf = np.full((T, S), -1, np.int32)
    sb = np.full((T, S), -1, np.int32)
    for t in range(1, T):
        for s in range(S):
            if s > 0:
                sf[t][s] = Ft[t - 1][s - 1]
            if s < S - 1:
                sb[t][s] = Bt[t - 1][s + 1]
    return sf, sb


def _masked_store(buf, idx, val, pred):
    """buf[idx] = val where pred (idx may be -1 => no-op via pred)."""
    slot = jnp.maximum(idx, 0)
    prev = jax.lax.dynamic_index_in_dim(buf, slot, 0, keepdims=False)
    new = jnp.where(jnp.logical_and(pred, idx >= 0), val, prev)
    return jax.lax.dynamic_update_index_in_dim(buf, new, slot, 0)


def build_zbh1_loss_and_grads(
        mesh: Mesh, S: int, M: int,
        block_rels: List[str],
        template,
        prefix_apply: Callable,   # (prefix_params, shared_params, ids) -> x
        suffix_loss: Callable,    # (suffix_params, shared_params, y, lab) -> l
        act_sds: jax.ShapeDtypeStruct,
        remat: bool = True,
        dp_axis: str = None,
        stacked_specs=None,          # per-block_rel P, e.g. P('pp',None,'mp')
        pre_specs=None, suf_specs=None, shr_specs=None):
    """Returns f(stacked_tuple, prefix_params, suffix_params, shared_params,
    ids, labels) -> (loss, stacked_grads_tuple, prefix_grads, suffix_grads,
    shared_grads). ``shared_params``: tied weights read by both phases
    (empty dict when none) — their gradient sums the stage-0 prefix-side
    and stage-(S-1) suffix-side contributions. ids/labels
    are (M, mb, ...); stacked leaves are (S, L, ...) pp-sharded. With
    ``dp_axis`` the microbatch dim is additionally dp-sharded (params
    replicated over dp): loss and grads are pmean'd over dp — standard
    data parallelism composed INSIDE the manual region, so the pp ring
    stays per-dp-slice and the dp reduction is one collective at the
    end. ``act_sds`` must describe the LOCAL (per-dp-shard) activation."""

    if stacked_specs is None:
        stacked_specs = [P("pp") for _ in block_rels]
    pre_specs = pre_specs or {}
    suf_specs = suf_specs or {}
    shr_specs = shr_specs or {}

    def spec_axes(spec):
        out = set()
        for entry in spec:
            if entry is None:
                continue
            out.update(entry if isinstance(entry, tuple) else (entry,))
        return out

    # tensor-parallel (and any other) axes named by param specs become
    # MANUAL axes of the engine: GSPMD-auto collectives inside divergent
    # lax.cond units are unsound (different pp stages take different
    # branches, desynchronizing the compiler-inserted collective schedule
    # — observed as an XLA rendezvous deadlock), while explicit TP
    # collectives are sound because every member of an mp group shares
    # its stage and therefore its branch. The TP layers switch to their
    # explicit-collective path via _manual_axis().
    tp_axes = set()
    for sp in list(stacked_specs) + list(pre_specs.values()) \
            + list(suf_specs.values()) + list(shr_specs.values()):
        tp_axes |= spec_axes(sp)
    tp_axes -= {"pp", dp_axis}
    tp_axes = tuple(sorted(tp_axes))

    Ft, Bt, Wt = zbh1_schedule(S, M)
    sf_tab, sb_tab = _stash_tables(Ft, Bt, S)
    ring_up = [(i, (i + 1) % S) for i in range(S)]
    ring_dn = [(i, (i - 1) % S) for i in range(S)]

    from .pipeline_parallel import make_stage_fn
    stage_fn = make_stage_fn(template, block_rels, remat)

    # axes the kernel is manual over — every per-stage value varies on
    # them (vma); cond branches and the scan carry must agree on this
    vary_axes = ("pp",) + ((dp_axis,) if dp_axis else ()) + tp_axes

    def _vary(x):
        """Promote x to varying over the engine's manual axes (idempotent
        per axis) — cond branches and the scan carry must agree on vma."""
        missing = tuple(a for a in vary_axes
                        if a not in jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    def kernel(stacked, prefix_params, suffix_params, shared_params,
               ids, labels):
        local = tuple(a[0] for a in stacked)     # drop the stage dim
        s_idx = jax.lax.axis_index("pp")
        is_first = s_idx == 0
        is_last = s_idx == S - 1

        zbuf = jnp.zeros((M,) + tuple(act_sds.shape), act_sds.dtype)
        X = zbuf                                  # stage inputs, M slots
        Y = zbuf                                  # last-stage outputs
        G = zbuf                                  # stage-output grads
        DX0 = zbuf                                # stage-0 dx (prefix bwd)
        up = jnp.zeros(tuple(act_sds.shape), act_sds.dtype)
        dn = jnp.zeros(tuple(act_sds.shape), act_sds.dtype)
        loss_acc = jnp.zeros((), jnp.float32)
        f32z = lambda tree: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), tree)
        dW, dPre, dSuf = f32z(local), f32z(prefix_params), f32z(suffix_params)
        # tied-weight grads, accumulated on different stages per phase
        dShrP, dShrS = f32z(shared_params), f32z(shared_params)

        def f_unit(op):
            m, X, Y, up = op

            def from_prefix(m):
                return _vary(prefix_apply(
                    prefix_params, shared_params,
                    jax.lax.dynamic_index_in_dim(
                        ids, m, 0, keepdims=False)).astype(up.dtype))

            def from_stash(m):
                return jax.lax.dynamic_index_in_dim(X, m, 0, keepdims=False)

            x = jax.lax.cond(is_first, from_prefix, from_stash, m)
            X = jax.lax.dynamic_update_index_in_dim(X, x, m, 0)
            y = stage_fn(local, x)
            Y = _masked_store(Y, m, y, is_last)
            return X, Y, y

        def b_unit(op):
            m, X, Y, G, loss_acc, dSuf, dShrS, DX0 = op
            x = jax.lax.dynamic_index_in_dim(X, m, 0, keepdims=False)

            def seed_from_loss(op2):
                y, lab, dSuf, dShrS = op2
                # seed 1/M scales dSuf/dShrS and g so the sum is the mean
                lval, both_vjp = jax.vjp(
                    lambda sp, sh, yy: suffix_loss(sp, sh, yy, lab),
                    suffix_params, shared_params, y)
                # the cotangent must carry lval's vma (varying over the
                # manual axes when check_vma=True) — derive it from lval;
                # the value is exactly 1/M: seed scales dSuf/dShrS and g
                # so the sum over microbatches is the mean
                dsuf_m, dshr_m, g = both_vjp((lval * 0 + 1) / M)
                dSuf = jax.tree.map(lambda a, d: a + d.astype(a.dtype),
                                    dSuf, dsuf_m)
                dShrS = jax.tree.map(lambda a, d: a + d.astype(a.dtype),
                                     dShrS, dshr_m)
                return (g.astype(x.dtype), lval.astype(jnp.float32), dSuf,
                        dShrS)

            def seed_from_ring(op2):
                y, lab, dSuf, dShrS = op2
                g = jax.lax.dynamic_index_in_dim(G, m, 0, keepdims=False)
                return g, _vary(jnp.zeros((), jnp.float32)), dSuf, dShrS

            y_m = jax.lax.dynamic_index_in_dim(Y, m, 0, keepdims=False)
            lab_m = jax.lax.dynamic_index_in_dim(labels, m, 0,
                                                 keepdims=False)
            g, lval, dSuf, dShrS = jax.lax.cond(
                is_last, seed_from_loss, seed_from_ring,
                (y_m, lab_m, dSuf, dShrS))
            loss_acc = loss_acc + lval / M
            G = jax.lax.dynamic_update_index_in_dim(G, g, m, 0)
            _, x_vjp = jax.vjp(lambda xx: stage_fn(local, xx), x)
            (dx,) = x_vjp(g)
            DX0 = _masked_store(DX0, m, dx, is_first)
            return G, loss_acc, dSuf, dShrS, DX0, dx

        def w_unit(op):
            m, X, G, DX0, dW, dPre, dShrP = op
            x = jax.lax.dynamic_index_in_dim(X, m, 0, keepdims=False)
            g = jax.lax.dynamic_index_in_dim(G, m, 0, keepdims=False)
            _, p_vjp = jax.vjp(lambda lp: stage_fn(lp, x), local)
            (dw_m,) = p_vjp(g)
            dW = jax.tree.map(lambda a, d: a + d.astype(a.dtype), dW, dw_m)

            def prefix_bwd(op2):
                dPre, dShrP = op2
                dxin = jax.lax.dynamic_index_in_dim(DX0, m, 0,
                                                    keepdims=False)
                _, pre_vjp = jax.vjp(
                    lambda pp, sh: prefix_apply(
                        pp, sh, jax.lax.dynamic_index_in_dim(
                            ids, m, 0, keepdims=False)).astype(dxin.dtype),
                    prefix_params, shared_params)
                dpre_m, dshr_m = pre_vjp(dxin)
                return (jax.tree.map(lambda a, d: a + d.astype(a.dtype),
                                     dPre, dpre_m),
                        jax.tree.map(lambda a, d: a + d.astype(a.dtype),
                                     dShrP, dshr_m))

            dPre, dShrP = jax.lax.cond(is_first, prefix_bwd,
                                       lambda op2: op2, (dPre, dShrP))
            return dW, dPre, dShrP

        def tick(carry, xs):
            (X, Y, G, DX0, up, dn, loss_acc,
             dW, dPre, dSuf, dShrP, dShrS) = carry
            rf, rb, rw, sf, sb = xs
            pick = lambda row: row[s_idx]
            mf, mb_, mw = pick(rf), pick(rb), pick(rw)
            # stash last tick's ring arrivals into their static slots
            X = _masked_store(X, pick(sf), up, True)
            G = _masked_store(G, pick(sb), dn, True)

            X, Y, y_out = jax.lax.cond(
                mf >= 0, f_unit,
                lambda op: (op[1], op[2], jnp.zeros_like(op[3])),
                (jnp.maximum(mf, 0), X, Y, up))

            G, loss_acc, dSuf, dShrS, DX0, dx_out = jax.lax.cond(
                mb_ >= 0, b_unit,
                lambda op: (op[3], op[4], op[5], op[6], op[7],
                            jnp.zeros_like(up)),
                (jnp.maximum(mb_, 0), X, Y, G, loss_acc, dSuf, dShrS, DX0))

            dW, dPre, dShrP = jax.lax.cond(
                mw >= 0, w_unit, lambda op: (op[4], op[5], op[6]),
                (jnp.maximum(mw, 0), X, G, DX0, dW, dPre, dShrP))

            up = jax.lax.ppermute(y_out, "pp", ring_up)
            dn = jax.lax.ppermute(dx_out, "pp", ring_dn)
            return (X, Y, G, DX0, up, dn, loss_acc,
                    dW, dPre, dSuf, dShrP, dShrS), None

        carry = (X, Y, G, DX0, up, dn, loss_acc,
                 dW, dPre, dSuf, dShrP, dShrS)
        carry = jax.tree.map(_vary, carry)
        carry, _ = jax.lax.scan(
            tick, carry,
            tuple(jnp.asarray(t) for t in (Ft, Bt, Wt, sf_tab, sb_tab)))
        (X, Y, G, DX0, up, dn, loss_acc,
         dW, dPre, dSuf, dShrP, dShrS) = carry

        loss = jax.lax.psum(jnp.where(is_last, loss_acc, 0.0), "pp")
        dPre = jax.tree.map(lambda a: jax.lax.psum(
            jnp.where(is_first, a, jnp.zeros_like(a)), "pp"), dPre)
        dSuf = jax.tree.map(lambda a: jax.lax.psum(
            jnp.where(is_last, a, jnp.zeros_like(a)), "pp"), dSuf)
        # tied weights: prefix-side contribution lives on stage 0, the
        # suffix-side one on stage S-1 — one masked psum sums both (and
        # both land on the same device when S == 1)
        dShr = jax.tree.map(
            lambda ap, as_: jax.lax.psum(
                jnp.where(is_first, ap, jnp.zeros_like(ap))
                + jnp.where(is_last, as_, jnp.zeros_like(as_)), "pp"),
            dShrP, dShrS)
        if dp_axis is not None:
            # each dp shard computed the mean loss over ITS tokens; the
            # global mean (and its gradient) is the dp-mean of those
            loss = jax.lax.pmean(loss, dp_axis)
            dW = jax.tree.map(lambda a: jax.lax.pmean(a, dp_axis), dW)
            dPre = jax.tree.map(lambda a: jax.lax.pmean(a, dp_axis), dPre)
            dSuf = jax.tree.map(lambda a: jax.lax.pmean(a, dp_axis), dSuf)
            dShr = jax.tree.map(lambda a: jax.lax.pmean(a, dp_axis), dShr)
        if tp_axes:
            # grads of params NOT sharded over a tp axis are numerically
            # replicated across it (activations re-replicate at each row
            # psum); the pmean is an identity that discharges the
            # varying-axis bookkeeping so P()-style out_specs hold
            def drop_tp(a, spec):
                for ax in tp_axes:
                    if ax not in spec_axes(spec):
                        a = jax.lax.pmean(a, ax)
                return a
            loss = drop_tp(loss, P())
            dW = tuple(drop_tp(a, sp)
                       for a, sp in zip(dW, [P(*sp[1:]) for sp in
                                             stacked_specs]))
            dPre = {k: drop_tp(a, pre_specs.get(k, P()))
                    for k, a in dPre.items()}
            dSuf = {k: drop_tp(a, suf_specs.get(k, P()))
                    for k, a in dSuf.items()}
            dShr = {k: drop_tp(a, shr_specs.get(k, P()))
                    for k, a in dShr.items()}
        dW = jax.tree.map(lambda a: a[None], dW)   # re-add the stage dim
        return loss, dW, dPre, dSuf, dShr

    def loss_and_grads(stacked_tuple, prefix_params, suffix_params,
                       shared_params, ids, labels):
        data_spec = P(None, dp_axis) if dp_axis else P()

        def dict_specs(specs, tree):
            return {k: specs.get(k, P()) for k in tree}

        in_specs = (
            tuple(stacked_specs),
            dict_specs(pre_specs, prefix_params),
            dict_specs(suf_specs, suffix_params),
            dict_specs(shr_specs, shared_params),
            data_spec, data_spec)
        out_specs = (
            P(),
            tuple(stacked_specs),
            dict_specs(pre_specs, prefix_params),
            dict_specs(suf_specs, suffix_params),
            dict_specs(shr_specs, shared_params))
        # manual over the WHOLE mesh with check_vma=False: the engine's
        # vjp structure computes LOCAL grads inside divergent cond
        # branches and reduces them with the explicit masked psums at the
        # end. check_vma=True would auto-insert transpose collectives
        # INSIDE the divergent branches (unsound — different pp stages
        # take different branches, observed as an XLA rendezvous
        # deadlock). The TP layers' manual f/g ops carry the only
        # collectives that belong inside units, and they are sound
        # because an mp group shares its stage and hence its branch.
        # Mesh axes named by NO spec (e.g. mp with a non-TP model, or
        # size-1 sharding/sep axes) replicate the work — sound, since
        # full-manual means no GSPMD could use them anyway.
        return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
            stacked_tuple, prefix_params, suffix_params, shared_params,
            ids, labels)

    return loss_and_grads
