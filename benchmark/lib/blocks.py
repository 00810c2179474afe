"""A sparse-expert decoder that generates by diffusion over blocks,
served: how its parameters and cached bytes are counted (``@arch``), the
closed loop that drives it and decides ``correct`` from the engine's own
record of every forward (``@loop``), and the readers of what the block
step and the grouped expert matmul add to the program.

Imports nothing of the program at module level: ``registry.load_all()``
imports this file for every cell, also on a checkout that has no such
model. There a reader finds no counter or kernel to read and returns
None.
"""

import time

import numpy as np

from . import flops, trace
from . import traffic as traffic_lib
from .registry import arch, loop, reader

# the grouped expert matmul in a device trace: upstream's kernel, by the
# name of its jit
KERNEL = "gmm"
# blocks each checked request generates in set-up: 4 requests x 4 blocks
# x 4 denoising forwards = 64 checked reveals a run
CHECK_BLOCKS = 4


# ------------------------------------------------------------- the counts
@arch("sdar_moe")
def sdar_moe_sizes(c: dict) -> dict:
    """``sdar_moe`` (Qwen3-MoE's layer): GQA attention with per-head
    q/k norms, then a router over ``num_experts`` and that many SwiGLU
    experts at ``moe_intermediate_size``; untied head. ``experts_held``
    of them live here (all, unless the configuration says)."""
    h, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    heads, kv_heads, d = (c["num_attention_heads"],
                          c["num_key_value_heads"], c["head_dim"])
    held = c.get("experts_held") or c["num_experts"]
    expert = 3 * h * c["moe_intermediate_size"]
    attention = 2 * h * d * (heads + kv_heads)
    router = h * c["num_experts"]
    small = 2 * h + 2 * d               # two layer norms, q and k norms
    per_layer = held * expert + attention + router
    return dict(
        matmul_params=L * per_layer + V * h,
        n_params=L * (per_layer + small) + 2 * V * h + h,
        layers=L, hidden=h, heads=heads, kv_heads=kv_heads, head_dim=d,
        # a forward reads every parameter but the embedding table, of
        # which it gathers its tokens' rows; ``dense``: but the experts
        # too, of which it reads those that got a token
        forward_params=L * (per_layer + small) + V * h + h,
        dense_forward_params=(L * (attention + router + small)
                              + V * h + h),
        experts_held=held, top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        expert_params_per_layer=held * expert,
        block_length=c["block_length"])


def expert_weight_bytes(sizes: dict, itemsize: int = 2) -> float:
    """Bytes of ONE expert's weights in one layer (gate, up, down): what
    the layer's two grouped matmuls read for an expert that got a token
    (one that got none is not fetched)."""
    return float(sizes["expert_params_per_layer"]
                 // sizes["experts_held"]) * itemsize


def expert_activation_bytes(sizes: dict) -> float:
    """The least ONE token-to-expert assignment must move through ONE
    layer's two grouped matmuls: its bf16 row in (hidden), the bf16
    ``silu(gate) * up`` row out and in again (expert width), the float32
    row out (hidden). (The first call writes float32 ``gate | up`` and
    XLA makes the bf16 row of it: the kernel's doing, no need.)"""
    return 2.0 * sizes["hidden"] + 4.0 * sizes["expert_width"] \
        + 4.0 * sizes["hidden"]


# -------------------------------------------------------------- the loop
def block_prompt(model: dict, seed: int, index: int, length: int):
    """A prompt's token ids, drawn BELOW the mask token's id: a prompt
    never holds it."""
    return traffic_lib.prompt_tokens(seed, index, length,
                                     int(model["mask_token_id"]))


def checked_forwards(sent, out, records, blen):
    """Every denoising forward of the checked requests, as the engine
    saw it: its own committed tokens as the prefix, then the block
    before the forward. Returns the forwards (with what each revealed
    and the log-confidences the step chose by), their token ids padded
    to one width, their cursors, and the engine's (position, token)
    picks."""
    rows = []
    for prompt, rid in sent:
        seq = np.concatenate([prompt, np.asarray(out[rid], np.int32)])
        for rec in records[rid]:
            if rec["commit"]:
                continue
            changed = np.flatnonzero(rec["after"] != rec["before"])
            rows.append(dict(
                rid=rid, cursor=rec["cursor"], before=rec["before"],
                prefix=seq[:rec["cursor"]], position=int(changed[0]),
                token=int(rec["after"][changed[0]]),
                n_changed=len(changed), log_conf=rec["log_conf"]))
    width = max(r["cursor"] for r in rows) + blen
    ids = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        ids[i, :r["cursor"]] = r["prefix"]
        ids[i, r["cursor"]:r["cursor"] + blen] = r["before"]
    cursors = np.asarray([r["cursor"] for r in rows], np.int32)
    picked = np.asarray([(r["position"], r["token"]) for r in rows],
                        np.int32)
    return rows, ids, cursors, picked


def reference_verdict(system, ids, cursors, picked, matmul_dtype=None):
    """Per forward, one row at a time (6 layers of float32 experts fit
    so): the plain reference's own reveal (position, token, the log of
    its confidence, its top logit) and what it holds of the pick it is
    asked about (its logit, the log of its softmax probability, the top
    logit at its position); last, its log-confidence at every position
    of the block and which of them were masked. ``matmul_dtype``: only
    for the control (:func:`control_rows`), the reference's ``logits``
    keyword."""
    import jax
    import jax.numpy as jnp

    ref, model = system.ref, system.config["model"]
    blen, mask = int(model["block_length"]), int(model["mask_token_id"])
    low = {} if matmul_dtype is None else dict(matmul_dtype=matmul_dtype)

    def judge(w, ids, cursors, picked):
        def one(args):
            row, cursor, pick = args
            full = ref.logits(w, row, model, **low)
            block = jax.lax.dynamic_slice_in_dim(full, cursor, blen, 0)
            ids_b = jax.lax.dynamic_slice_in_dim(row, cursor, blen, 0)
            masked = ids_b == mask
            pos, tok, log_conf, top = ref.reveal(block, masked)
            lse = top - log_conf
            mine = block[pick[0], pick[1]]
            return (pos, tok, log_conf[pos], top[pos], mine,
                    mine - lse[pick[0]], top[pick[0]], log_conf, masked)
        return jax.lax.map(one, (ids, cursors, picked))

    return [np.asarray(v) for v in jax.jit(judge)(
        system.weights, jnp.asarray(ids), jnp.asarray(cursors),
        jnp.asarray(picked))]


def confidence_errors(rows, verdict):
    """Per forward, the largest distance between the log-confidence the
    step computed and the reference's over the masked positions."""
    return np.asarray([np.max(np.abs(r["log_conf"] - ref_conf),
                              where=masked, initial=0.0)
                       for r, ref_conf, masked in zip(rows, *verdict[7:])])


def compare(rows, verdict, ref):
    """(near ties, wrong) of the forwards ``rows`` under the reference's
    ``verdict``, by three limits of the reference's file. The numbers
    the reveal chooses by, compared directly: the log-confidence the
    step computed at every MASKED position lies within ``CONF_ATOL`` of
    the reference's in every forward (a fault in some rows), and the
    MEDIAN over the forwards of each one's largest such distance within
    ``CONF_MEDIAN_ATOL`` (a fault in the precision of all, which the
    tail of the sound program's own rounding would hide from a limit a
    forward). And what was revealed: a pick that is not the reference's
    own passes as a near tie only where the reference holds it within
    ``TIE_ATOL + TIE_RTOL * |top|`` of its own in the log-confidence
    AND within the same of the top logit at its position. Any other
    difference is wrong."""
    ties, wrong = [], []
    errors = confidence_errors(rows, verdict)
    for r, error, pos, tok, conf, top, mine, mine_conf, mine_top in zip(
            rows, errors, *verdict[:7]):
        same = (r["n_changed"] == 1 and r["position"] == int(pos)
                and r["token"] == int(tok))
        if same and error <= ref.CONF_ATOL:
            continue
        limit = ref.TIE_ATOL + ref.TIE_RTOL * abs(float(top))
        rec = dict(request=r["rid"], cursor=r["cursor"],
                   engine=[r["position"], r["token"]],
                   reference=[int(pos), int(tok)],
                   conf_gap=float(conf - mine_conf),
                   logit_gap=float(mine_top - mine), limit=limit,
                   conf_error=float(error), conf_limit=ref.CONF_ATOL)
        near = (r["n_changed"] == 1 and rec["conf_gap"] <= limit
                and rec["logit_gap"] <= limit and error <= ref.CONF_ATOL)
        (ties if near else wrong).append(rec)
    median = float(np.median(errors))
    if median > ref.CONF_MEDIAN_ATOL:
        wrong.append(dict(conf_error_median=median,
                          limit=ref.CONF_MEDIAN_ATOL, forwards=len(rows)))
    return ties, wrong


def control_rows(system, rows, ids, cursors, matmul_dtype):
    """The control of the comparison: the forwards ``rows`` with the
    engine's pick and log-confidences replaced by the reference's OWN
    with both operands of every weight matmul rounded to ``matmul_dtype``,
    the nearest precision below the configuration's. Judged like the
    engine's (:func:`reference_verdict`, :func:`compare`) it must come
    out not correct (``benchmark/tests/control_blocks.py``). Returns
    (rows, picked)."""
    low = reference_verdict(system, ids, cursors,
                            np.zeros((len(rows), 2), np.int32),
                            matmul_dtype)
    pos, tok, conf = low[0], low[1], low[7]
    rows = [dict(r, position=int(p), token=int(t), n_changed=1, log_conf=c)
            for r, p, t, c in zip(rows, pos, tok, conf)]
    return rows, np.stack([pos, tok], axis=1).astype(np.int32)


def _reading(rows, picked, verdict, ties, wrong):
    """What one judged set of reveals read: how many picks were not
    the reference's own and their gaps, the distances between the
    log-confidences (the largest of each forward), and how many
    forwards came out as near ties and as wrong."""
    pos, tok, conf, top, mine, mine_conf, mine_top = verdict[:7]
    differ = (picked[:, 0] != pos) | (picked[:, 1] != tok)
    gaps = np.maximum(conf - mine_conf, mine_top - mine)[differ]
    errors = confidence_errors(rows, verdict)
    return dict(correct=not wrong, reveals=len(rows),
                differ=int(differ.sum()), near_ties=len(ties),
                wrong=len(wrong),
                largest_gap=float(gaps.max()) if len(gaps) else 0.0,
                gaps=sorted(round(float(g), 4) for g in gaps),
                conf_error_median=float(np.median(errors)),
                largest_conf_error=float(errors.max()),
                conf_errors=sorted(round(float(e), 4) for e in errors))


def control_readings(system, requests, matmul_dtype):
    """The two readings the reference's limits lie between, at the
    cell's own sample and through the cell's own comparison: the
    ENGINE's reveals of the checked requests, and the CONTROL's
    (:func:`control_rows`) for the same forwards. ``benchmark/tests/control_blocks.py`` prints
    them; the control has to read ``correct: false``."""
    blen = int(system.config["model"]["block_length"])
    checked, out, records, _ = warm_up(system, requests)
    rows, ids, cursors, picked = checked_forwards(checked, out, records,
                                                  blen)
    verdict = reference_verdict(system, ids, cursors, picked)
    engine = _reading(rows, picked, verdict,
                      *compare(rows, verdict, system.ref))
    low_rows, low_picked = control_rows(system, rows, ids, cursors,
                                        matmul_dtype)
    verdict = reference_verdict(system, ids, cursors, low_picked)
    control = _reading(low_rows, low_picked, verdict,
                       *compare(low_rows, verdict, system.ref))
    ref = system.ref
    return dict(limits=dict(conf_median=ref.CONF_MEDIAN_ATOL,
                            conf=ref.CONF_ATOL, tie=ref.TIE_ATOL),
                engine=engine, control=control)


def warm_up(system, requests):
    """Set-up of the block cell, on the timed engine at the timed sizes.
    Warms one monolithic prefill a distinct prompt length at or under
    the chunk, the chunk program if any prompt is longer, and the block
    step at the rung the closed loop holds: one request more than the
    rung below takes the engine there. The first four are the CHECKED
    requests: the three shortest distinct prompts and the first that
    passes the chunk (its prefix comes through a whole chunk and a
    padded one from a cursor past 0), ``CHECK_BLOCKS`` blocks each, for
    which the engine records the block before and after every forward.
    Returns (checked, out, records, notes): the checked (prompt, rid)
    pairs, every request's tokens, the engine's records."""
    eng = system.engine
    model = system.config["model"]
    blen, mask = int(model["block_length"]), int(model["mask_token_id"])
    lens = sorted({r.prompt_len for r in requests})
    mono = [n for n in lens if not eng.chunk or n <= eng.chunk]
    chunked = [n for n in lens if eng.chunk and n > eng.chunk]
    shapes = mono + chunked[:1]
    clients = min(int(system.traffic["clients"]), eng.max_batch)
    rung = next(r for r in eng.ladder if r >= clients)
    at = list(eng.ladder).index(rung)
    count = max((eng.ladder[at - 1] if at else 0) + 1, 4)
    check_lens = ((mono[:3] + chunked[:1]) * 4)[:4]
    lengths = check_lens + [n for n in shapes if n not in check_lens]
    lengths += [shapes[0]] * (count - len(lengths))

    rng = np.random.default_rng([system.seed, 13])
    sent = []
    for n in lengths:
        prompt = rng.integers(0, mask, (n,)).astype(np.int32)
        sent.append((prompt, eng.submit(prompt, CHECK_BLOCKS * blen)))
    eng.record_blocks([rid for _, rid in sent[:len(check_lens)]])
    out = eng.run()
    records = eng.block_records()
    eng.record_blocks(None)
    if any(eng.status(rid) != "OK" for _, rid in sent):
        raise RuntimeError(f"warm-up requests ended {eng.statuses()}")
    system.phases.mark("warm_up")
    notes = dict(warmed_prompt_lens=shapes, warmed_rungs=[rung],
                 checked_prompt_lens=check_lens)
    return sent[:len(check_lens)], out, records, notes


def _warm_and_check(system, requests):
    """:func:`warm_up`, then ``correct``: the plain reference, fed the
    engine's own prefix and block for every denoising forward of the
    checked requests, must hold the log-confidences the step chose by
    and reveal the same position and token, or one it holds as good as
    its own, within the limits of :func:`compare`. Returns (correct,
    notes)."""
    blen = int(system.config["model"]["block_length"])
    checked, out, records, notes = warm_up(system, requests)
    rows, ids, cursors, picked = checked_forwards(checked, out, records,
                                                  blen)
    verdict = reference_verdict(system, ids, cursors, picked)
    ties, wrong = compare(rows, verdict, system.ref)
    errors = confidence_errors(rows, verdict)
    system.phases.mark("reference_reveals")
    notes.update(checked_reveals=len(rows), near_ties=ties, wrong=wrong,
                 conf_error_median=float(np.median(errors)),
                 conf_error_max=float(errors.max()),
                 setup_phases_s=system.phases.seconds)
    return not wrong, notes


@loop("closed_blocks")
def closed_blocks_loop(system, seed, seconds, traced):
    """``loops.closed_loop`` for a block-diffusion model: ``clients``
    callers, each sending its next request the moment its last one
    finished, cycling through the table in the file's recorded order.
    Differs in set-up (the check above; prompts never hold the mask
    token) and in what it reads after the window: the engine's
    per-expert histogram, kept on the device while the window ran."""
    from paddle_tpu import observability as obs
    from . import window as window_lib
    from .loops import _serve_outcome, _Tracker
    from .window import Window

    eng = system.engine
    requests = traffic_lib.schedule(system.traffic, seed, seconds)
    correct, notes = _warm_and_check(system, requests)
    prompts = [block_prompt(system.config["model"], seed, r.index,
                            r.prompt_len) for r in requests]
    eng.take_results()
    eng.expert_histogram()          # what warm-up counted goes
    obs.tracer().clear()

    tracker = _Tracker(eng)
    window = Window(traced)
    finished = set()
    sent = 0

    def send():
        nonlocal sent
        i = sent % len(requests)    # a table shorter than the window cycles
        tracker.submit(requests[i], prompts[i])
        sent += 1

    w0 = window.open()
    for _ in range(int(system.traffic["clients"])):
        send()
    while time.perf_counter() - w0 < seconds:
        for rid in tracker.step():
            finished.add(rid)
            send()
    window.close()
    out = _serve_outcome(tracker, window, finished, correct, notes)
    before = window_lib.program_counters()
    hist = eng.expert_histogram()   # a device read, after the window
    if hist is not None and hist.sum():
        after = window_lib.program_counters()
        out.scalars.update(
            {k: after[k] - before.get(k, 0.0)
             for k in ("moe_assignments", "moe_experts_touched")},
            expert_load_max_over_mean=float(hist.max() / hist.mean()))
    return out


# ------------------------------------------------------------ the readers
def _kernel_seconds(ctx):
    """Device seconds of the ops whose instruction is named after the
    grouped expert matmul, averaged over the devices; None without a
    trace or without such an op."""
    ops = ctx.get("device_ops")
    if not ops:
        return None
    total = sum(dur for dev in ops.values() for text, _, dur in dev
                if trace.parse_hlo(text)[0].split(".")[0] == KERNEL)
    return total / len(ops) / 1e9 or None


@reader("expert_mm_roofline")
def expert_mm_roofline(ctx):
    """The kernel's share of its roofline: at these shapes (16 rows an
    expert) it is bound by memory, so the least time is the bytes its
    calls must move over the published HBM bandwidth, over the kernel's
    device time. By the program's own counts, kept on the device while
    the window ran: the weights of every expert that got a token, once
    a call (``moe_experts_touched``: an expert that got none is not
    fetched), and every assignment's rows in and out
    (``moe_assignments``)."""
    kernel_s = _kernel_seconds(ctx)
    s, sizes = ctx["scalars"], ctx["sizes"]
    touched, assigned = (s.get("moe_experts_touched"),
                         s.get("moe_assignments"))
    if (not kernel_s or not touched or not assigned
            or "expert_params_per_layer" not in sizes):
        return None
    need = (touched * expert_weight_bytes(sizes)
            + assigned * expert_activation_bytes(sizes))
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s


@reader("expert_mm_share")
def expert_mm_share(ctx):
    kernel_s = _kernel_seconds(ctx)
    if not kernel_s or not ctx.get("busy_s"):
        return None
    return 100.0 * kernel_s / ctx["busy_s"]


@reader("block_step_floor")
def block_step_floor(ctx):
    """The window's byte floor over ALL the device's busy time, at the
    published HBM bandwidth: each block step reads the parameters that
    are no expert's once (bf16; the embedding table not: a forward
    gathers rows of it) and its rows' cached K and V (the engine's own
    counts); and every program reads the weights of the experts that
    got a token in it, as ``expert_mm_roofline`` counts them
    (``moe_experts_touched``, kept on the device; prefill's calls are
    among them, and their other parameters are left out). Prefill's
    device time is in the denominator, so this is the cell's share of
    the whole step."""
    s, sizes = ctx["scalars"], ctx["sizes"]
    steps, live, touched = (s.get("serving_decode_steps"),
                            s.get("serving_decode_live_tokens"),
                            s.get("moe_experts_touched"))
    if (not ctx.get("busy_s") or not steps or live is None or not touched
            or not s.get("serving_block_forwards")
            or "dense_forward_params" not in sizes):
        return None
    need = (steps * 2.0 * sizes["dense_forward_params"]
            + touched * expert_weight_bytes(sizes)
            + live * flops.kv_bytes_per_token(sizes))
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / ctx["busy_s"]
