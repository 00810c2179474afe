"""A sparse-expert decoder with window and global attention layers,
served: how its parameters and cached bytes are counted (``@arch``), how
the system under test is put together (``@builder``: the selection bias
drawn, not zero), the closed loop that drives it and decides ``correct``
on prompts chosen to cross the window (``@loop``), and the readers of
what the window pool and the two windowed reads add to the program.

Imports nothing of the program at module level: ``registry.load_all()``
imports this file for every cell, also on a checkout that has no such
model. There a reader finds no counter, gauge or kernel to read and
returns None.
"""

import time

import numpy as np

from . import blocks, paged
from . import traffic as traffic_lib
from .registry import arch, builder, loop, reader

WINDOW_LAYER = "sliding_attention"
# the prompts whose greedy tokens decide ``correct``, chosen to cross
# what is new and not the four shortest: monolithic and inside the
# window; two chunks, inside it; three chunks, past it (the mask bites);
# four chunks, past window + chunk (its 61 pages pass the 49 a window
# row may hold, so pages given back are taken again by the same row
# before the answer is decoded)
CHECK_PROMPTS = (512, 1536, 2560, 3840)
NEW_TOKENS = 8


# ------------------------------------------------------------- the counts
@arch("afmoe")
def afmoe_sizes(c: dict) -> dict:
    """``afmoe``: per layer gated GQA attention (q, k, v, o and an output
    gate as wide as q; per-head q/k norms) and four RMS norms; the first
    ``num_dense_layers`` layers a SwiGLU MLP at ``intermediate_size``,
    the others a router over ``num_experts``, that many SwiGLU experts
    at ``moe_intermediate_size`` (``experts_held`` of them here: all,
    unless the configuration says), a selection bias and
    ``num_shared_experts`` shared experts of the same width; untied
    head. ``window_layers`` / ``global_layers`` by ``layer_types``."""
    h, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    heads, kv_heads, d = (c["num_attention_heads"],
                          c["num_key_value_heads"], c["head_dim"])
    held = c.get("experts_held") or c["num_experts"]
    dense, sparse = c["num_dense_layers"], L - c["num_dense_layers"]
    expert = 3 * h * c["moe_intermediate_size"]
    attention = 3 * h * d * heads + 2 * h * d * kv_heads
    mlp = 3 * h * c["intermediate_size"]
    # per sparse layer beside its routed experts: router, shared experts
    beside = h * c["num_experts"] + c["num_shared_experts"] * expert
    small = 4 * h + 2 * d               # four layer norms, q and k norms
    bias = c["num_experts"]             # a sparse layer's selection bias
    window_layers = sum(t == WINDOW_LAYER for t in c["layer_types"])
    not_routed = (L * (attention + small) + dense * mlp
                  + sparse * (beside + bias))
    return dict(
        matmul_params=not_routed - L * small - sparse * bias
        + sparse * held * expert + V * h,
        n_params=not_routed + sparse * held * expert + 2 * V * h + h,
        layers=L, hidden=h, heads=heads, kv_heads=kv_heads, head_dim=d,
        # a forward reads every parameter but the embedding table (it
        # gathers its tokens' rows) and, of the routed experts, those
        # that got a token
        dense_forward_params=not_routed + V * h + h,
        experts_held=held, top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        expert_params_per_layer=held * expert, sparse_layers=sparse,
        window_layers=window_layers, global_layers=L - window_layers,
        window=c["sliding_window"])


def kv_bytes_per_token_layer(sizes: dict, itemsize: int = 2) -> float:
    """K and V of one cached token in ONE layer."""
    return 2.0 * sizes["kv_heads"] * sizes["head_dim"] * itemsize


def decode_attention_bytes(ctx):
    """The cached K and V the window's decode steps had to read, by the
    engine's own counts: a global layer reads a decoding row's whole
    length (``serving_decode_live_tokens``), a window layer its last
    ``window`` positions (``serving_decode_window_tokens``). None where
    the program has no such counter."""
    s, sizes = ctx["scalars"], ctx["sizes"]
    live, windowed = (s.get("serving_decode_live_tokens"),
                      s.get("serving_decode_window_tokens"))
    if not live or not windowed or "window_layers" not in sizes:
        return None
    return kv_bytes_per_token_layer(sizes) * (
        live * sizes["global_layers"] + windowed * sizes["window_layers"])


# ------------------------------------------------------------ the weights
def draw_selection_bias(weights: dict, seed: int) -> dict:
    """``weights`` with every ``expert_bias`` drawn normal(0, 0.02) from
    the seed, in the dtype it has. ``make_weights`` sets it to zero, its
    published initial value, under which a program that weighted by the
    biased score, or chose by the bare one, would pass the comparison."""
    import jax
    import jax.numpy as jnp
    from .system import INIT_STD, seed_key
    names = sorted(n for n in weights if n.endswith("expert_bias"))

    def draw(key):
        return {n: (INIT_STD * jax.random.normal(
            jax.random.fold_in(key, i), weights[n].shape, jnp.float32)
        ).astype(weights[n].dtype) for i, n in enumerate(names)}

    key = jax.random.fold_in(seed_key(seed), 0xAF)
    return {**weights, **jax.jit(draw)(key)}


class AfmoeServeSystem:
    """``system.ServeSystem``'s recipe (meta model, every weight in one
    jitted call from the seed, the program's own ``ServingEngine``),
    with the expert layers' selection bias drawn before the engine takes
    the weights; the reference is handed the same."""

    def __init__(self, config, traffic, seed):
        import jax
        from paddle_tpu.generation.serving import ServingEngine
        from . import system

        self.phases = system.Phases()
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.sizes = system.sizes_of(config)
        self.vocab = int(config["model"]["vocab_size"])
        self.cfg, self.model = system.lazy_model(config)
        self.phases.mark("import_and_model")
        self.weights = draw_selection_bias(
            system.make_weights(self.model, self.seed), self.seed)
        self.model.load_raw_state(self.weights)
        jax.block_until_ready(self.weights)
        self.phases.mark("weights")
        self.model.eval()
        self.ref = system.load_reference(config["name"])
        self.engine = ServingEngine(self.model, **config["serve"])
        self.phases.mark("engine_built")
        self.devices = jax.devices()[:1]


@builder("serve_afmoe")
def build_serve_afmoe(config, traffic, seed, chips):
    return AfmoeServeSystem(config, traffic, seed)


# -------------------------------------------------------------- the loop
def warm_up(system, requests):
    """Set-up of the cell, on the timed engine at the timed sizes. ONE
    batch that takes the engine to the rung the closed loop holds (one
    request more than the rung below) and through every shape the table
    can reach: the four CHECKED prompts first (``CHECK_PROMPTS``, those
    the table holds), then one monolithic prefill a distinct prompt
    length at or under the chunk, the shortest filling up; the checked
    prompts pass the chunk, so the chunk program is among them. Every
    request makes ``NEW_TOKENS`` tokens, so the checked ones decode side
    by side with others, as they will in the window. Returns (checked,
    notes): the checked (prompt, its tokens) pairs."""
    eng = system.engine
    lens = sorted({r.prompt_len for r in requests})
    check_lens = [n for n in CHECK_PROMPTS if n in lens] or lens[:4]
    mono = [n for n in lens if not eng.chunk or n <= eng.chunk]
    clients = min(int(system.traffic["clients"]), eng.max_batch)
    rung = next(r for r in eng.ladder if r >= clients)
    at = list(eng.ladder).index(rung)
    lengths = check_lens + [n for n in mono if n not in check_lens]
    count = max((eng.ladder[at - 1] if at else 0) + 1, len(lengths))
    lengths += [lens[0]] * (count - len(lengths))

    rng = np.random.default_rng([system.seed, 13])
    sent = []
    for n in lengths:
        prompt = rng.integers(0, system.vocab, (n,)).astype(np.int32)
        sent.append((prompt, eng.submit(prompt, NEW_TOKENS)))
    out = eng.run()
    if any(eng.status(rid) != "OK" for _, rid in sent):
        raise RuntimeError(f"warm-up requests ended {eng.statuses()}")
    system.phases.mark("warm_up")
    notes = dict(warmed_prompt_lens=sorted(set(lengths)),
                 warmed_rungs=[rung], checked_prompt_lens=check_lens)
    checked = [(p, out[rid]) for p, rid in sent[:len(check_lens)]]
    return checked, notes


def deciding_logits(system, checked, **variant):
    """The reference's float32 logits at the positions that decided each
    generated token of the checked requests, (requests, NEW_TOKENS,
    vocab): every sequence padded to one width (a position sees nothing
    after it), one at a time, the head on those positions only.
    ``variant``: only for the controls, the reference's own keywords."""
    import jax
    import jax.numpy as jnp

    ref, model = system.ref, system.config["model"]
    width = max(len(p) for p, _ in checked) + NEW_TOKENS
    ids = np.zeros((len(checked), width), np.int32)
    for i, (p, toks) in enumerate(checked):
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + len(toks)] = toks
    starts = np.asarray([len(p) - 1 for p, _ in checked], np.int32)

    def rows(w, ids, starts):
        return jax.lax.map(
            lambda a: ref.logits(w, a[0], model, rows=(a[1], NEW_TOKENS),
                                 **variant), (ids, starts))

    return np.asarray(jax.jit(rows)(system.weights, jnp.asarray(ids),
                                    jnp.asarray(starts)), np.float32)


def compare(tokens, ref_logits, ref):
    """(near ties, wrong, gaps) of ``tokens`` (per request, its generated
    tokens) under the reference's logits at the deciding positions, by
    two limits of the reference's file. A token that is not the
    reference's argmax passes as a near tie where the reference's own
    logit of it lies within ``TIE_ATOL + TIE_RTOL * |top|`` of the top;
    any other difference is wrong (a fault in some rows: a wrong mask, a
    wrong cursor, a page given back too early). And the MEAN of the gaps
    over all the tokens (0 where the token is the reference's own) lies
    within ``MEAN_GAP_ATOL`` (a fault in the precision of all, which the
    tail of the sound program's own rounding would hide from a limit a
    token: an expert chosen otherwise moves a logit by a tenth whatever
    the precision). ``gaps``: of every token that differs."""
    ties, wrong, gaps = [], [], []
    for i, toks in enumerate(tokens):
        for t, tok in enumerate(toks):
            row = ref_logits[i, t]
            best = int(row.argmax())
            if best == int(tok):
                continue
            gap = float(row[best] - row[int(tok)])
            gaps.append(gap)
            rec = dict(request=i, position=t, engine=int(tok),
                       reference=best, gap=gap, top=float(row[best]))
            if gap <= ref.TIE_ATOL + ref.TIE_RTOL * abs(float(row[best])):
                ties.append(rec)
            else:
                wrong.append(rec)
    total = sum(len(t) for t in tokens)
    mean = sum(gaps) / max(total, 1)
    if mean > ref.MEAN_GAP_ATOL:
        wrong.append(dict(mean_gap=mean, limit=ref.MEAN_GAP_ATOL,
                          tokens=total, differ=len(gaps)))
    return ties, wrong, gaps


def _reading(tokens, ref_logits, ref):
    ties, wrong, gaps = compare(tokens, ref_logits, ref)
    total = sum(len(t) for t in tokens)
    return dict(correct=not wrong, tokens=total,
                differ=len(gaps), near_ties=len(ties), wrong=len(wrong),
                largest_gap=max(gaps, default=0.0),
                mean_gap=sum(gaps) / max(total, 1),
                gaps=sorted(round(g, 4) for g in gaps))


def control_readings(system, requests, matmul_dtype):
    """The readings the reference's limit lies between, and the control
    that shows the comparison sees the window, all at the cell's own
    sample and through the cell's own comparison: the ENGINE's tokens of
    the checked requests; the reference's OWN greedy choice at the same
    positions with both operands of every weight matmul rounded to
    ``matmul_dtype`` (the nearest precision below the configuration's),
    and with the window left off every layer. Both controls have to read
    ``correct: false`` (``benchmark/tests/control_mixed.py``). Beside
    them, for whoever sets the limits: the reference's choice with
    operands rounded to the configuration's OWN dtype, which is what
    the engine's rounding should look like."""
    import jax.numpy as jnp
    checked, _ = warm_up(system, requests)
    ref = system.ref
    full = deciding_logits(system, checked)
    out = dict(limits=dict(tie=ref.TIE_ATOL, mean_gap=ref.MEAN_GAP_ATOL),
               engine=_reading([t for _, t in checked], full, ref))
    own = jnp.dtype(system.config["dtype"])
    for name, variant in (("low_precision", dict(matmul_dtype=matmul_dtype)),
                          ("window_off", dict(window=None)),
                          ("same_precision", dict(matmul_dtype=own))):
        own = deciding_logits(system, checked, **variant).argmax(-1)
        out[name] = _reading(own, full, ref)
        # the same reading of the prompts past the window alone
        out[name]["differ_by_prompt"] = [
            int((own[i] != full[i].argmax(-1)).sum())
            for i in range(len(checked))]
    return out


def _warm_and_check(system, requests):
    """:func:`warm_up`, then ``correct``: the checked requests' greedy
    tokens beside the plain reference's teacher-forced argmax, near ties
    by the reference's own logit (:func:`compare`). Returns (correct,
    notes)."""
    checked, notes = warm_up(system, requests)
    ties, wrong, gaps = compare(
        [t for _, t in checked], deciding_logits(system, checked),
        system.ref)
    system.phases.mark("reference_tokens")
    total = sum(len(t) for _, t in checked)
    notes.update(checked_tokens=total, near_ties=ties, wrong=wrong,
                 largest_gap=max(gaps, default=0.0),
                 mean_gap=sum(gaps) / max(total, 1),
                 setup_phases_s=system.phases.seconds)
    return not wrong, notes


@loop("closed_mixed")
def closed_mixed_loop(system, seed, seconds, traced):
    """``loops.closed_loop`` for a model with window layers and expert
    layers: ``clients`` callers, each sending its next request the
    moment its last one finished, cycling through the table in the
    file's recorded order. Differs in set-up (which prompts decide
    ``correct``: :func:`warm_up`) and in what it reads after the window:
    the programs it dispatched and the engine's per-expert histogram,
    kept on the device while the window ran."""
    from paddle_tpu import observability as obs
    from . import window as window_lib
    from .loops import _serve_outcome, _Tracker
    from .window import Window

    eng = system.engine
    requests = traffic_lib.schedule(system.traffic, seed, seconds)
    correct, notes = _warm_and_check(system, requests)
    prompts = [traffic_lib.prompt_tokens(seed, r.index, r.prompt_len,
                                         system.vocab) for r in requests]
    eng.take_results()
    eng.expert_histogram()          # what warm-up counted goes
    obs.tracer().clear()

    tracker = _Tracker(eng)
    window = Window(traced)
    finished = set()
    sent = 0
    chunks_before = eng.chunk_dispatches

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
    # the forward programs of the window: its decode steps, its chunks,
    # and one whole-prompt prefill a request that got its first token in
    # the window from a prompt at or under the chunk
    whole = sum(1 for rid, times in tracker.token_t.items()
                if times and tracker.req[rid].prompt_len <= eng.chunk)
    out.scalars["forward_programs"] = float(
        out.scalars.get("serving_decode_steps", 0.0)
        + eng.chunk_dispatches - chunks_before + whole)
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
CHUNK_KERNEL = "paged_chunk_attention"


def _chunk_kernel_seconds(ctx):
    """Device seconds of the ops whose instruction name holds the chunk
    kernel's, averaged over the devices; None without a trace or
    without such an op."""
    from . import trace
    ops = ctx.get("device_ops")
    if not ops:
        return None
    total = sum(dur for dev in ops.values() for text, _, dur in dev
                if CHUNK_KERNEL in trace.parse_hlo(text)[0])
    return total / len(ops) / 1e9 or None


@reader("chunk_attn_share")
def chunk_attn_share(ctx):
    kernel_s = _chunk_kernel_seconds(ctx)
    if not kernel_s or not ctx.get("busy_s"):
        return None
    return 100.0 * kernel_s / ctx["busy_s"]


@reader("chunk_attn_roofline")
def chunk_attn_roofline(ctx):
    """The chunk kernel's share of its roofline. Under a chunk's
    hundreds of query rows and ``heads / kv_heads`` query heads a KV
    head it is bound by compute (8,192 FLOPs a cached byte at the
    cell's sizes): the least time is the FLOPs of the query-key pairs
    the window's chunks had to compute, by the engine's own counts from
    each chunk's cursor (``serving_chunk_attn_pairs``: a query at
    position p sees p + 1 keys in a global layer;
    ``serving_chunk_window_pairs``: ``min(p + 1, window)`` in a window
    layer), 4 x heads x head_dim a pair a layer (q k^T and p v), over
    the published bf16 peak. Pad rows, masked keys of a visited page
    and float32 passes are time and not work: all pull the share down.
    None where the program has no such counter."""
    s, sizes = ctx["scalars"], ctx["sizes"]
    kernel_s = _chunk_kernel_seconds(ctx)
    whole, windowed = (s.get("serving_chunk_attn_pairs"),
                       s.get("serving_chunk_window_pairs"))
    if (not kernel_s or not whole or not windowed
            or "window_layers" not in sizes):
        return None
    need = 4.0 * sizes["heads"] * sizes["head_dim"] * (
        whole * sizes["global_layers"] + windowed * sizes["window_layers"])
    return 100.0 * need / ctx["peaks"]["bf16_flops_per_s"] / kernel_s


@reader("mixed_attn_roofline")
def mixed_attn_roofline(ctx):
    """The windowed decode kernel's share of its roofline. It is bound
    by memory: the least time is the cached K and V its calls must read
    (:func:`decode_attention_bytes`: a global layer a row's whole
    length, a window layer its last ``window`` positions) over the
    published HBM bandwidth; over the device time of the ops whose name
    holds ``paged_attention`` (``paged_chunk_attention`` does not)."""
    kernel_s = paged._kernel_seconds(ctx)
    need = decode_attention_bytes(ctx)
    if not kernel_s or need is None:
        return None
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s


def _pool_gauge(pool: str):
    """``serving_kv_pool_bytes{pool=}`` as it stands (a gauge is a
    level), summed over replicas; None where the program has no such
    gauge."""
    from paddle_tpu import observability as obs
    fam = obs.registry().snapshot()["metrics"].get("serving_kv_pool_bytes")
    if not fam or fam["type"] != "gauge":
        return None
    return float(sum(s["value"] for s in fam["series"]
                     if s["labels"].get("pool") == pool))


@reader("window_kv_resident")
def window_kv_resident(ctx):
    """What the window pool's rows hold, over what the window layers
    would hold if they kept every page as the global layers do (the
    global pool's bytes in use x window layers / global layers), as the
    window closes: the allocator's effect. 100 where nothing is given
    back."""
    sizes = ctx["sizes"]
    if not sizes.get("window_layers") or not sizes.get("global_layers"):
        return None
    held, whole = _pool_gauge("window"), _pool_gauge("global")
    if not held or not whole:
        return None
    return 100.0 * held / (whole * sizes["window_layers"]
                           / sizes["global_layers"])


@reader("moe_step_floor")
def moe_step_floor(ctx):
    """The window's byte floor over ALL the device's busy time, at the
    published HBM bandwidth: every forward program (decode step, chunk,
    whole-prompt prefill) reads the parameters that are no routed
    expert's once (bf16; the embedding table not: a forward gathers rows
    of it) and the weights of the routed experts that got a token in it
    (``moe_experts_touched``, kept on the device); every decode step
    reads its rows' cached K and V (:func:`decode_attention_bytes`; what
    prefill reads of the cache is left out). Prefill's device time is in
    the denominator, so this is the cell's share of the whole step."""
    s, sizes = ctx["scalars"], ctx["sizes"]
    programs, touched = (s.get("forward_programs"),
                         s.get("moe_experts_touched"))
    attention = decode_attention_bytes(ctx)
    if (not ctx.get("busy_s") or not programs or not touched
            or attention is None):
        return None
    need = (programs * 2.0 * sizes["dense_forward_params"]
            + touched * blocks.expert_weight_bytes(sizes) + attention)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / ctx["busy_s"]
