"""How load is offered. A loop warms up every shape its traffic can
reach (set-up), checks the outputs against the plain reference (set-up),
opens the window, drives the system for ``--seconds`` from ONE thread,
and returns what it saw:

    series    name -> list of readings (ms), ALL readings of the window
    scalars   name -> number (tokens, seconds, counter deltas)
    attempted, failed, correct, notes

Readers turn these into metrics; nothing here knows a metric's name.
"""

import dataclasses
import math
import time

import numpy as np

from . import traffic as traffic_lib
from .registry import loop
from .window import Window, span


@dataclasses.dataclass
class Outcome:
    series: dict
    scalars: dict
    attempted: int
    failed: int
    correct: bool
    window: Window
    notes: dict


# ------------------------------------------------------------- training
@loop("train")
def train_loop(system, seed, seconds, traced) -> Outcome:
    from .system import autocast

    step = system.step
    tokens_per_step = system.batch * system.seq
    # set-up: the first step compiles, and its loss on the first batch
    # is held against the plain reference's loss on the same batch
    with autocast():
        first_loss = float(step(system.stage(system.first_ids)))
    system.phases.mark("first_step")
    diff = abs(first_loss - system.ref_loss)
    correct = bool(math.isfinite(first_loss)
                   and diff <= system.ref.LOSS_ATOL)
    staged = system.stage(system.next_ids())
    with autocast():
        float(step(staged))             # a second step: no retrace left
    staged = system.stage(system.next_ids())
    traces_before = step.trace_count
    system.phases.mark("second_step")

    window = Window(traced)
    step_ms, losses = [], []
    window.open()
    t_prev = window.t_open
    while True:
        with span("bench.step"):
            with autocast():
                loss = step(staged)
            # the next batch goes to the device while this step runs
            staged = system.stage(system.next_ids())
            with span("bench.pull_loss"):
                losses.append(float(loss))      # closes the step
        now = time.perf_counter()
        step_ms.append((now - t_prev) * 1e3)
        t_prev = now
        if now - window.t_open >= seconds:
            break
    # the window ends with the step that crossed --seconds, so the rate
    # is whole steps over exactly the time they took
    window.close(at=t_prev)
    bad = sum(1 for v in losses if not math.isfinite(v))
    scalars = dict(window.counters,
                   tokens=float(len(losses) * tokens_per_step),
                   steps=float(len(losses)),
                   tokens_per_step=float(tokens_per_step),
                   window_s=window.seconds,
                   step_traces=float(step.trace_count - traces_before))
    notes = dict(first_loss=first_loss, reference_loss=system.ref_loss,
                 loss_diff=diff, loss_atol=system.ref.LOSS_ATOL,
                 last_loss=losses[-1], setup_phases_s=system.phases.seconds)
    return Outcome(dict(step_ms=step_ms), scalars, len(losses), bad,
                   correct and bad == 0, window, notes)


# -------------------------------------------------------------- serving
class _Tracker:
    """Per-request clocks, fed by the engine's ``on_token`` callbacks
    (they fire on this thread at the end of each engine step), and the
    per-step readings. One tracker lives for one window, so everything
    it holds was read inside the window."""

    def __init__(self, engine):
        self.engine = engine
        self.req = {}           # rid -> traffic Request
        self.due_t = {}         # rid -> when the request was due
        self.status = {}        # rid -> terminal status
        self.token_t = {}       # rid -> [perf_counter of each token]
        self.done = []          # rids finished since last taken
        self.late_ms = []       # due time to submit(), per request
        self.run_step_ms = []
        self.backlog = []       # requests queued or in flight, per step
        self.live_tokens = 0    # cached tokens of decoding requests
        self.live_tokens_sum = 0    # ... summed over the engine steps

    def submit(self, r, prompt, due_t=None):
        """``due_t`` None: a closed loop's request, due as it is sent."""
        with span("bench.submit"):
            rid = self.engine.submit(prompt, r.output_len,
                                     on_token=self._on_token)
        now = time.perf_counter()
        self.req[rid] = r
        self.due_t[rid] = now if due_t is None else due_t
        self.late_ms.append((now - self.due_t[rid]) * 1e3)
        self.token_t[rid] = []
        return rid

    def _on_token(self, rid, token, done):
        if done:
            self.done.append(rid)
            self.status[rid] = self.engine.status(rid)
            n = len(self.token_t[rid])
            if n:
                self.live_tokens -= self.req[rid].prompt_len + n
            return
        times = self.token_t[rid]
        times.append(time.perf_counter())
        self.live_tokens += (self.req[rid].prompt_len + 1
                             if len(times) == 1 else 1)

    def step(self) -> list:
        """One engine step under the benchmark's span; returns the rids
        that finished in it, their results drained from the engine."""
        t0 = time.perf_counter()
        self.live_tokens_sum += self.live_tokens
        with span("bench.run_step"):
            self.engine.run_step()
        self.run_step_ms.append((time.perf_counter() - t0) * 1e3)
        self.backlog.append(float(self.engine.load()[1]))
        finished, self.done = self.done, []
        if finished:
            self.engine.take_results()
        return finished


def _warm_and_check(system, requests):
    """Set-up for a serving cell. Warms exactly the shapes this table
    can reach: one monolithic prefill per distinct prompt length at or
    under the chunk size, the chunk program if any prompt is longer, and
    the decode program of every ladder rung the loop can visit. The
    first four warm-up requests (the four shortest distinct prompts)
    also decide ``correct``: their greedy tokens against the plain
    reference's argmax. Returns (correct, notes)."""
    import jax
    import jax.numpy as jnp

    eng = system.engine
    lens = sorted({r.prompt_len for r in requests})
    mono = [n for n in lens if not eng.chunk or n <= eng.chunk]
    chunked = [n for n in lens if eng.chunk and n > eng.chunk]
    shapes = mono + chunked[:1]
    loop_kind = system.traffic["loop"]
    clients = int(system.traffic.get("clients", 0))
    rungs = [r for r in eng.ladder
             if loop_kind == "open" or r >= min(clients, eng.max_batch)]
    rng = np.random.default_rng([system.seed, 13])
    new_tokens = 8

    def run_batch(lengths, tokens=2):
        """One request per length, ``tokens`` new tokens each; run until
        drained. Returns [(prompt, its tokens)]."""
        sent = []
        for n in lengths:
            prompt = rng.integers(0, system.vocab, (n,)).astype(np.int32)
            sent.append((prompt, eng.submit(prompt, tokens)))
        out = eng.run()
        if any(eng.status(rid) != "OK" for _, rid in sent):
            raise RuntimeError(f"warm-up requests ended {eng.statuses()}")
        return [(prompt, out[rid]) for prompt, rid in sent]

    # one request more than the rung below holds takes the engine to a
    # rung. The first batch also seats the four checks (the four
    # shortest distinct prompts); shapes not yet seen go first, then the
    # shortest shape fills up
    check_lens = (lens * 4)[:4]
    todo = [n for n in shapes if n not in check_lens]
    ladder = list(eng.ladder)
    checks = None
    for rung in rungs:
        at = ladder.index(rung)
        count = (ladder[at - 1] if at else 0) + 1
        first = check_lens if checks is None else []
        lengths = first + todo[:max(0, count - len(first))]
        todo = todo[len(lengths) - len(first):]
        lengths += [shapes[0]] * (count - len(lengths))
        # every request of the first batch makes the checks' 8 tokens, so
        # that they decode side by side as they will in the window
        done = run_batch(lengths, new_tokens if checks is None else 2)
        if checks is None:
            checks = done[:len(check_lens)]
    while todo:                         # more shapes than requests so far
        run_batch(todo[:eng.max_batch])
        todo = todo[eng.max_batch:]

    system.phases.mark("warm_up")
    ref = system.ref
    width = max(len(p) for p, _ in checks) + new_tokens
    ids = np.zeros((len(checks), width), np.int32)
    for i, (p, toks) in enumerate(checks):
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + len(toks)] = toks
    model = system.config["model"]
    starts = np.asarray([len(p) - 1 for p, _ in checks], np.int32)

    def deciding_logits(w, ids, starts):
        """The reference's logits at the positions that decided each
        generated token: (requests, new_tokens, vocab)."""
        def one(args):
            row, start = args
            full = ref.logits(w, row, model)
            return jax.lax.dynamic_slice_in_dim(full, start, new_tokens, 0)
        return jax.lax.map(one, (ids, starts))

    ref_logits = np.asarray(jax.jit(deciding_logits)(
        system.weights, jnp.asarray(ids), jnp.asarray(starts)), np.float32)
    ties, wrong, total = [], [], 0
    for i, (p, toks) in enumerate(checks):
        for t, tok in enumerate(toks):
            row = ref_logits[i, t]
            total += 1
            best = int(row.argmax())
            if best == tok:
                continue
            gap = float(row[best] - row[tok])
            rec = dict(request=i, position=t, engine=int(tok),
                       reference=best, gap=gap, top=float(row[best]))
            if gap <= ref.TIE_ATOL + ref.TIE_RTOL * abs(float(row[best])):
                ties.append(rec)
            else:
                wrong.append(rec)
    system.phases.mark("reference_tokens")
    notes = dict(checked_tokens=total, near_ties=ties, wrong=wrong,
                 warmed_prompt_lens=shapes, warmed_rungs=rungs,
                 setup_phases_s=system.phases.seconds)
    return not wrong, notes


def _serve_outcome(tracker, window, sampled, correct, notes) -> Outcome:
    """Series and scalars of a serving window, from the tracker's
    clocks. ``sampled`` is the set of rids the latencies are taken
    over; tokens are counted over ALL requests."""
    from paddle_tpu import observability as obs
    from .stats import token_gaps

    ttft, itl = [], []
    prompt_done = output = decode_tokens = failed = 0
    for rid, times in tracker.token_t.items():
        output += len(times)
        decode_tokens += max(0, len(times) - 1)
        if times:
            prompt_done += tracker.req[rid].prompt_len
        if rid in sampled:
            if not times or tracker.status.get(rid) in ("FAILED", "TIMEOUT"):
                failed += 1
                continue
            ttft.append((times[0] - tracker.due_t[rid]) * 1e3)
            itl.extend(gap * 1e3 for gap in token_gaps(times))
    w0, w1 = window.t_open * 1e6, window.t_close * 1e6
    queue_wait = [e["dur"] / 1e3 for e in obs.tracer().events()
                  if e["name"] == "request.queued" and w0 <= e["ts"] <= w1]
    series = dict(ttft_ms=ttft, itl_ms=itl, queue_wait_ms=queue_wait,
                  run_step_ms=tracker.run_step_ms,
                  gen_late_ms=tracker.late_ms, backlog=tracker.backlog)
    scalars = dict(window.counters,
                   prompt_tokens_done=float(prompt_done),
                   output_tokens=float(output),
                   decode_tokens=float(decode_tokens),
                   live_tokens_sum=float(tracker.live_tokens_sum),
                   window_s=window.seconds)
    return Outcome(series, scalars, len(sampled), failed,
                   correct and failed == 0, window, notes)


def _serving_setup(system, seed, seconds):
    """What both serving loops do before the window: this seed's
    requests and their prompts, warm-up and the reference check, and a
    clean slate in the engine's results and the program's span ring."""
    from paddle_tpu import observability as obs

    requests = traffic_lib.schedule(system.traffic, seed, seconds)
    correct, notes = _warm_and_check(system, requests)
    prompts = [traffic_lib.prompt_tokens(seed, r.index, r.prompt_len,
                                         system.vocab) for r in requests]
    system.engine.take_results()
    obs.tracer().clear()
    return requests, prompts, correct, notes


@loop("open")
def open_loop(system, seed, seconds, traced) -> Outcome:
    """Requests are sent when they are DUE, whether or not earlier ones
    have finished, and each is timed from its due time."""
    eng = system.engine
    requests, prompts, correct, notes = _serving_setup(system, seed, seconds)
    share = float(system.traffic.get("sample_share", 1.0))
    tracker = _Tracker(eng)
    window = Window(traced)
    sampled = set()
    nxt, n = 0, len(requests)
    w0 = window.open()
    while True:
        now = time.perf_counter() - w0
        if now >= seconds:
            break
        while nxt < n and requests[nxt].due_s <= now:
            r = requests[nxt]
            rid = tracker.submit(r, prompts[nxt], due_t=w0 + r.due_s)
            if r.due_s <= share * seconds:
                sampled.add(rid)
            nxt += 1
        if eng.has_work():
            tracker.step()
        else:
            with span("bench.wait_arrival"):
                wait = requests[nxt].due_s - now if nxt < n else seconds - now
                time.sleep(max(0.0, min(wait, 0.002)))
    window.close()
    notes["requests_offered"] = nxt
    return _serve_outcome(tracker, window, sampled, correct, notes)


@loop("closed")
def closed_loop(system, seed, seconds, traced) -> Outcome:
    """``clients`` callers, each sending its next request the moment its
    last one finished, cycling through the table in this seed's order:
    every slot is always taken."""
    requests, prompts, correct, notes = _serving_setup(system, seed, seconds)
    tracker = _Tracker(system.engine)
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
    return _serve_outcome(tracker, window, finished, correct, notes)
