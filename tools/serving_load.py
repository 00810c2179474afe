"""Seeded Poisson multi-tenant load generator for the serving engine
and (``--fleet``) the multi-replica fleet router.

The acceptance bench for the r12 production continuous-batching loop:
a deterministic (seeded) open-loop Poisson request stream from several
tenants — a chat tenant with short shared-prefix prompts, a long-prompt
tenant (the decode-stall antagonist), and an SLO tenant submitting with
deadlines — is paced in real time against a ServingEngine, twice:

  chunked      chunked prefill + the bucket ladder (the r12 loop)
  monolithic   whole-prompt prefill, fixed top-rung bucket (pre-r12)

Each arm runs a WARMUP pass first (same prompt-length set, every ladder
rung dispatched) so the measured pass exercises steady state; metrics
come from the r09 telemetry snapshot DELTA across the measured pass:

  - sustained throughput (generated tokens / wall)
  - p50/p99 TTFT and inter-token latency (histogram bucket deltas)
  - ZERO program-cache traces at steady state (the retrace ledger)
  - max decode stall (engine probe): with chunking the worst stall a
    long-prompt arrival imposes on decoding requests is ~one chunk;
    monolithic pays the whole prompt — the artifact asserts
    chunked_max < monolithic_max

plus a cross-arm greedy BIT-IDENTITY check (same schedule, same rids,
same tokens). ``--out SERVING_LOAD_r12.json`` banks the ledger;
``--quick`` is the deterministic tier-1 slice driven by
tests/test_serving_load.py (marker ``serving_load``).

``--fleet`` (r14) runs the FLEET acceptance bench instead — three
sections over ``paddle_tpu/generation/fleet.py``:

  routing     N replicas, per-org shared-prefix tenants, Poisson
              arrivals, prefix-AFFINITY vs ROUND-ROBIN arms: affinity
              concentrates each org's prefix on one replica (shared
              admissions skip prefill) while round-robin smears it
              across all N and thrashes eviction — TTFT p99 must be
              lower under affinity, outputs bit-identical, with
              per-replica telemetry deltas banked.
  preemption  2 replicas saturated by no-deadline long generations
              while tight-deadline arrivals land: FLAGS_serving_preempt
              on vs off. The on-arm must hold tight-tenant TTFT p99
              under the off-arm's while every preempted victim still
              finishes bit-identically (replay-from-host-state IS the
              preemption mechanism).
  tiering     one replica whose device page budget is SMALLER than the
              org-prefix working set, host tier armed, vs a big-pool
              no-tier reference: spills + restores must occur, the
              registered working set must exceed the device budget,
              and every output must match the reference bit-for-bit.

``--out FLEET_LOAD_r14.json`` banks that ledger; the quick slice is
driven by tests/test_fleet.py (marker ``fleet``).

``--spec`` (r16) runs the SPECULATIVE-DECODING acceptance bench — two
sections over the ServingEngine's draft/verify mode:

  throughput  the batch-1 A/B the feature exists for: one request,
              plain decode vs speculative rounds, REPEATS measured
              passes per arm after a warmup pass that compiles every
              γ-rung program. Bars: ≥1.8x tokens/s (min over passes,
              both arms), greedy outputs bit-identical, ZERO
              steady-state retraces across all measured passes.
  occupancy   the γ+1 slot bill made visible: 1/2/4/8 concurrent
              requests against the same engine geometry, recording the
              largest γ any round ran at while ALL rows were live —
              the ladder must fall monotonically (8, 4, 2, then 0 =
              speculation priced out entirely at a full batch), with
              every row's outputs bit-identical to the plain engine.

The draft-agreement rig mirrors the production shape (a truncated /
distilled draft of the serving target): the 4-layer target's upper
layers are damped to near-identity residuals and the 1-layer draft
SHARES the target's embedding, layer-0, final-norm and head weights —
high agreement with real rejections, at a quarter of the layer cost.
``--out SPEC_DECODE_r16.json`` banks the ledger.

``--kv-dtype int8`` (r18) runs the QUANTIZED-KV acceptance bench — a
native-vs-int8 pool A/B at FIXED pool memory: the native arm's pool
bytes re-spent on int8 pages (payload + per-token f32 scales) must buy
~2x the usable page budget, measured from the pool LEDGER rather than
the planner, the page-pressure queueing regime must recede (smaller
queue-depth integral over the drain), int8 re-runs are bit-identical
(deterministic amax quantization), the analytic ``memwatch plan`` pool
term agrees with the ledger within 10%, and the retrace ledger stays
at zero. ``--out KV_QUANT_r18.json`` banks the ledger.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA = 1

# tenant mix: (name, rate req/s, prompt lengths cycled, shared-prefix
# tokens, max_new, deadline seconds or None)
TENANTS = (
    ("chat", 24.0, (12, 24), 8, 12, None),
    ("long", 4.0, (320,), 0, 8, None),
    ("slo", 12.0, (16,), 0, 8, 30.0),
)
QUICK_TENANTS = (
    ("chat", 20.0, (12,), 8, 6, None),
    # long prompts must be long enough that prefill cost is token-work,
    # not dispatch overhead, or the stall comparison loses its margin
    # at tiny-model scale
    ("long", 6.0, (320,), 0, 6, None),
    ("slo", 10.0, (16,), 0, 4, 30.0),
)


def make_arrivals(tenants, per_tenant, vocab, seed):
    """The deterministic request schedule: per-tenant exponential
    inter-arrival gaps and prompt bodies from a private seeded stream
    (tenant prompts share a fixed prefix to exercise the prefix cache),
    merged by arrival time."""
    import numpy as np

    arrivals = []
    for ti, (name, rate, lens, shared, max_new, deadline) in \
            enumerate(tenants):
        rng = np.random.default_rng((seed, ti))
        prefix = rng.integers(0, vocab, (shared,)).astype(np.int32)
        t = 0.0
        for i in range(per_tenant):
            t += float(rng.exponential(1.0 / rate))
            ln = int(lens[i % len(lens)])
            body = rng.integers(0, vocab, (ln - shared,)).astype(np.int32)
            prompt = np.concatenate([prefix, body]).astype(np.int32)
            arrivals.append(dict(t=t, tenant=name, prompt=prompt,
                                 max_new=int(max_new), deadline=deadline))
    arrivals.sort(key=lambda a: (a["t"], a["tenant"]))
    return arrivals


def make_engine(model, arm, cfg):
    from paddle_tpu.generation.serving import ServingEngine

    chunked = arm == "chunked"
    return ServingEngine(
        model, max_batch=cfg["max_batch"], page_size=cfg["page_size"],
        max_seq_len=cfg["max_seq_len"], prefix_cache=True,
        bucket_ladder=(cfg["ladder"] if chunked
                       else (cfg["max_batch"],)),
        prefill_chunk=(cfg["chunk"] if chunked else 0))


def warmup_arm(model, arm, cfg, lens):
    """Compile every program the measured pass can touch: one prefill
    per distinct prompt length (or the chunk program for long ones),
    and one decode dispatch at EVERY ladder rung — a rung first visited
    mid-measurement would read as a steady-state retrace."""
    import numpy as np

    rng = np.random.default_rng(0)
    eng = make_engine(model, arm, cfg)
    for ln in sorted(set(lens)):
        eng.submit(rng.integers(0, cfg["vocab"], (ln,)).astype(np.int32),
                   4)
        eng.run(max_wall=300.0)
    for rung in eng.ladder:
        for _ in range(rung):
            eng.submit(rng.integers(0, cfg["vocab"], (8,))
                       .astype(np.int32), 4)
        eng.run(max_wall=300.0)


def trace_total(snap):
    fam = snap["metrics"].get("program_cache_traces")
    if fam is None:
        return 0.0
    return sum(s["value"] for s in fam["series"])


def hist_delta(before, after, name):
    """Measured-pass histogram view: bucket-wise delta of the two
    cumulative snapshots (min/max dropped — unknown for the window)."""
    fa = after["metrics"].get(name)
    if fa is None or not fa["series"]:
        return None
    sa = fa["series"][0]
    fb = before["metrics"].get(name)
    if fb is None or not fb["series"]:
        return dict(sa)
    sb = fb["series"][0]
    return {"labels": {}, "count": sa["count"] - sb["count"],
            "sum": sa["sum"] - sb["sum"], "buckets": sa["buckets"],
            "counts": [a - b for a, b in zip(sa["counts"], sb["counts"])],
            "min": None, "max": None}


def quantiles(before, after, name, qs=(0.5, 0.99)):
    from paddle_tpu.observability import series_quantile

    entry = hist_delta(before, after, name)
    if entry is None or not entry["count"]:
        return {f"p{int(q * 100)}": None for q in qs}
    return {f"p{int(q * 100)}": round(series_quantile(entry, q), 6)
            for q in qs}


# virtual steps per second: the arrival clock ticks once per scheduler
# round rather than per wall second, so WHICH step each request lands
# on — and therefore whether a long-prompt arrival overlaps live
# decodes — is a pure function of the seed, not of machine load.
# Latencies are still measured in real wall time.
STEPS_PER_SEC = 250


REPEATS = 3     # measured passes per arm: the banked max stall is the
# MIN over passes of each pass's max — the schedule is deterministic,
# so the structural worst stall recurs every pass while a one-off OS/GC
# spike does not (a single pass's max is spike-polluted on shared CPU)


def run_arm(model, arm, cfg, arrivals):
    """The measured passes for one arm: warmed programs, deterministic
    step-indexed pacing, streaming callbacks collecting every token,
    telemetry snapshot delta spanning all passes (so the zero-retrace
    bar covers every pass)."""
    import paddle_tpu.observability as obs

    warmup_arm(model, arm, cfg,
               [len(a["prompt"]) for a in arrivals])
    due = [int(a["t"] * STEPS_PER_SEC) for a in arrivals]

    def one_pass():
        eng = make_engine(model, arm, cfg)
        streamed = {}

        def on_token(rid, tok, done):
            if not done:
                streamed.setdefault(rid, []).append(tok)

        rids = []
        i = 0
        tick = 0
        t0 = time.perf_counter()
        while i < len(arrivals) or eng.has_work():
            while i < len(arrivals) and due[i] <= tick:
                a = arrivals[i]
                rids.append(eng.submit(a["prompt"], a["max_new"],
                                       deadline=a["deadline"],
                                       on_token=on_token))
                i += 1
            tick += 1
            if eng.has_work():
                eng.run_step()
        wall = time.perf_counter() - t0
        return eng, rids, streamed, wall

    before = obs.snapshot()
    walls, stalls = [], []
    for _ in range(REPEATS):
        eng, rids, streamed, wall = one_pass()
        walls.append(wall)
        stalls.append(round(eng.max_decode_stall, 6))
    after = obs.snapshot()

    out = eng.results()
    statuses = [eng.status(r) for r in rids]
    tokens_total = sum(len(out.get(r, [])) for r in rids)
    wall = walls[-1]
    metrics = {
        "requests": len(rids),
        "passes": REPEATS,
        "statuses": {s: statuses.count(s) for s in set(statuses)},
        "all_ok": all(s == "OK" for s in statuses),
        "tokens_total": tokens_total,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens_total / wall, 2) if wall else None,
        "ttft_s": quantiles(before, after, "serving_ttft_seconds"),
        "inter_token_s": quantiles(before, after,
                                   "serving_inter_token_seconds"),
        "prefill_chunk_s": quantiles(before, after,
                                     "serving_prefill_chunk_seconds"),
        "decode_stall_s": quantiles(before, after,
                                    "serving_decode_stall_seconds"),
        "max_decode_stall_s": min(stalls),
        "max_decode_stall_per_pass_s": stalls,
        "steady_retraces": trace_total(after) - trace_total(before),
        "bucket_migrations": eng.bucket_migrations,
        "chunk_dispatches": eng.chunk_dispatches,
        "streamed_matches_results": all(
            streamed.get(r, []) == out.get(r, []) for r in rids),
    }
    return metrics, {r: out.get(r, []) for r in rids}


def bench(per_tenant, seed, quick=False):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    tenants = QUICK_TENANTS if quick else TENANTS
    cfg = (dict(vocab=256, max_batch=8, page_size=8,
                max_seq_len=384, ladder=(2, 4, 8), chunk=16)
           if quick else
           dict(vocab=256, max_batch=8, page_size=8,
                max_seq_len=512, ladder=(2, 4, 8), chunk=32))
    paddle.seed(1234)
    mcfg = GPTConfig.tiny()
    # the long tenant's prompts need position room beyond tiny's 128
    mcfg.max_position_embeddings = cfg["max_seq_len"]
    model = GPTForCausalLM(mcfg)
    arrivals = make_arrivals(tenants, per_tenant, cfg["vocab"], seed)

    arms = {}
    outputs = {}
    for arm in ("chunked", "monolithic"):
        arms[arm], outputs[arm] = run_arm(model, arm, cfg, arrivals)

    parity = outputs["chunked"] == outputs["monolithic"]
    c_stall = arms["chunked"]["max_decode_stall_s"]
    m_stall = arms["monolithic"]["max_decode_stall_s"]
    stall = {
        "chunked_max_s": c_stall,
        "monolithic_max_s": m_stall,
        # the acceptance bar: the worst stall any decoding request saw
        # shrinks from a whole-prompt prefill to ~one chunk. Both
        # maxima are min-over-passes (the structural stall recurs every
        # pass; a one-off OS/GC spike does not), the long tenant's
        # prompts are 10-20x the chunk so the margin survives ordinary
        # shared-CPU noise, and overlap between a long arrival and live
        # decodes is deterministic (step-indexed pacing), not a race
        # against machine load.
        "ratio": round(c_stall / m_stall, 4) if m_stall else None,
        "bounded_by_chunk": bool(m_stall and c_stall < m_stall),
    }
    ok = (parity
          and stall["bounded_by_chunk"]
          and all(a["all_ok"] for a in arms.values())
          and all(a["steady_retraces"] == 0 for a in arms.values())
          and all(a["streamed_matches_results"] for a in arms.values()))
    import paddle_tpu.observability as obs
    return {
        "schema": SCHEMA, "bench": "serving_load",
        "backend": jax.default_backend(), "seed": seed,
        "config": {**{k: v for k, v in cfg.items()},
                   "ladder": list(cfg["ladder"]),
                   "tenants": [list(t[:2]) + [list(t[2])] + list(t[3:])
                               for t in tenants],
                   "requests_per_tenant": per_tenant,
                   "quick": bool(quick)},
        "arms": arms,
        "parity_bit_identical": parity,
        "stall": stall,
        "ok": bool(ok),
        "telemetry": obs.snapshot(),
        # memwatch: the chunk/ladder programs' compiled-memory rows ride
        # the banked artifact (telemetry_dump --memory renders them)
        "memory": obs.memory.section(),
    }


# ===================================================== fleet bench (r14)
FLEET_SCHEMA = 1


def replica_counter_deltas(before, after, names):
    """Per-replica counter/histogram-count deltas: the per-replica
    telemetry view the r14 `replica` label makes possible."""
    out = {}
    for name in names:
        fa = after["metrics"].get(name)
        if fa is None:
            continue
        prev = {}
        fb = before["metrics"].get(name)
        if fb is not None:
            prev = {tuple(sorted(s["labels"].items())): s
                    for s in fb["series"]}
        for s in fa["series"]:
            rep = s["labels"].get("replica", "")
            b = prev.get(tuple(sorted(s["labels"].items())))
            if "value" in s:
                d = s["value"] - (b["value"] if b else 0.0)
            else:
                d = s["count"] - (b["count"] if b else 0)
            if d:
                out.setdefault(rep, {})[name] = round(d, 6)
    return out


_FLEET_REPLICA_FAMILIES = (
    "serving_requests_submitted", "serving_prefills",
    "serving_shared_admissions", "serving_ttft_seconds",
    "prefix_cache_hits", "prefix_cache_misses",
    "prefix_cache_hit_pages", "prefix_cache_evicted_pages",
    "prefix_cache_spilled_pages", "prefix_cache_restored_pages",
    "serving_preemptions", "serving_requests_timeout",
    "fleet_requests_routed")


def _fleet_model(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(1234)
    mcfg = GPTConfig.tiny()
    mcfg.max_position_embeddings = cfg["max_seq_len"]
    return GPTForCausalLM(mcfg)


def make_org_arrivals(n_orgs, per_org, prefix_len, body_len, vocab, seed,
                      max_new, deadline=None, rate=20.0):
    """Per-org shared-prefix Poisson arrivals: each org's prompts open
    with the org's own ``prefix_len``-token system prompt."""
    import numpy as np

    arrivals = []
    for oi in range(n_orgs):
        rng = np.random.default_rng((seed, oi))
        prefix = rng.integers(0, vocab, (prefix_len,)).astype(np.int32)
        t = 0.0
        for _ in range(per_org):
            t += float(rng.exponential(1.0 / rate))
            body = rng.integers(0, vocab, (body_len,)).astype(np.int32)
            arrivals.append(dict(
                t=t, tenant=f"org{oi}",
                prompt=np.concatenate([prefix, body]),
                max_new=int(max_new), deadline=deadline))
    arrivals.sort(key=lambda a: (a["t"], a["tenant"]))
    return arrivals


def _drive_fleet(fleet, arrivals, max_wall=300.0):
    """Deterministic step-indexed pacing (the r12 discipline): WHICH
    router round each arrival lands on is a pure function of the
    schedule, not machine load. TTFT comes from HOST stamps (submit
    wall -> first streamed token wall) so the A/B compares exact
    values, not histogram-bucket interpolations."""
    import time as _time

    due = [int(a["t"] * STEPS_PER_SEC) for a in arrivals]
    submit_t, ttft = {}, {}

    def cb(rid, tok, done):
        if not done and rid not in ttft:
            ttft[rid] = _time.perf_counter() - submit_t[rid]

    rids, i, tick = [], 0, 0
    t0 = _time.perf_counter()
    while i < len(arrivals) or fleet.has_work():
        if _time.perf_counter() - t0 > max_wall:
            break
        while i < len(arrivals) and due[i] <= tick:
            a = arrivals[i]
            ts = _time.perf_counter()
            rid = fleet.submit(a["prompt"], a["max_new"],
                               deadline=a["deadline"], on_token=cb)
            submit_t[rid] = ts
            rids.append(rid)
            i += 1
        tick += 1
        if fleet.has_work():
            fleet.run_step()
    st = fleet.statuses()               # BEFORE the drain frees them
    out = fleet.take_results()
    return rids, out, {r: st.get(r, "PENDING") for r in rids}, ttft


def fleet_routing_section(cfg, seed):
    """Affinity vs round-robin A/B over identical arrivals. Pass 0 is
    the WARMUP (programs compile, caches fill — each org's first
    request is a cold miss under either policy); the measured passes
    run the same schedule against the warm fleet, where affinity keeps
    every org on its cache-resident replica while round-robin smears
    the orgs across all replicas and thrashes eviction."""
    import numpy as np

    import paddle_tpu.observability as obs
    from paddle_tpu.generation.fleet import FleetRouter

    model = _fleet_model(cfg)
    arrivals = make_org_arrivals(
        cfg["orgs"], cfg["per_org"], cfg["prefix"], cfg["body"],
        cfg["vocab"], seed, cfg["max_new"])

    arms, outputs = {}, {}
    for policy in ("prefix_affinity", "round_robin"):
        fleet = FleetRouter(
            model, replicas=cfg["replicas"], policy=policy,
            max_batch=cfg["max_batch"], page_size=cfg["page_size"],
            max_seq_len=cfg["max_seq_len"], num_pages=cfg["num_pages"])
        _drive_fleet(fleet, arrivals)           # warmup pass
        p99s, passes = [], []
        before = obs.snapshot()
        for _ in range(REPEATS):
            rids, out, st, ttft = _drive_fleet(fleet, arrivals)
            vals = [ttft[r] for r in rids if r in ttft]
            q = {"p50": round(float(np.quantile(vals, 0.5)), 6),
                 "p99": round(float(np.quantile(vals, 0.99)), 6)}
            p99s.append(q["p99"])
            passes.append(q)
        after = obs.snapshot()
        arms[policy] = {
            "requests": len(rids),
            "all_ok": all(s == "OK" for s in st.values()),
            # min over passes: the structural gap (prefill skipped vs
            # re-run) recurs every pass, a one-off OS spike does not
            "ttft_p99_s": min(p99s),
            "ttft_per_pass": passes,
            "per_replica": replica_counter_deltas(
                before, after, _FLEET_REPLICA_FAMILIES),
            "placements": {why: sum(1 for _, _, w in fleet.placements
                                    if w == why)
                           for why in ("affinity", "balance",
                                       "round_robin", "pinned")},
        }
        outputs[policy] = {r: out.get(r, []) for r in rids}
    parity = outputs["prefix_affinity"] == outputs["round_robin"]
    aff, rr = arms["prefix_affinity"], arms["round_robin"]
    ok = (parity and aff["all_ok"] and rr["all_ok"]
          and aff["ttft_p99_s"] < rr["ttft_p99_s"]
          and aff["placements"]["affinity"] > 0)
    return {"arms": arms, "parity_bit_identical": parity,
            "ttft_p99_ratio": round(
                aff["ttft_p99_s"] / rr["ttft_p99_s"], 4)
            if rr["ttft_p99_s"] else None,
            "ok": bool(ok)}


def fleet_preemption_section(cfg, seed):
    """Tight-deadline p99 under overload: FLAGS_serving_preempt A/B."""
    import numpy as np

    import paddle_tpu.observability as obs
    from paddle_tpu import flags
    from paddle_tpu.generation.fleet import FleetRouter

    model = _fleet_model(cfg)
    rng = np.random.default_rng((seed, 99))
    batch_prompts = [rng.integers(0, cfg["vocab"], (12,)).astype(np.int32)
                     for _ in range(cfg["replicas"] * cfg["max_batch"])]
    slo_prompts = [rng.integers(0, cfg["vocab"], (10,)).astype(np.int32)
                   for _ in range(cfg["slo_requests"])]

    # one warmup fleet compiles everything both arms touch: chunked
    # prefill (all prompts AND replay feeds exceed the chunk, so no
    # prompt length ever forces a fresh compile mid-measurement) plus
    # the decode rung
    warm = FleetRouter(model, replicas=1, max_batch=cfg["max_batch"],
                       page_size=cfg["page_size"],
                       max_seq_len=cfg["max_seq_len"],
                       prefill_chunk=cfg["page_size"])
    warm.submit(batch_prompts[0], 2)
    warm.submit(slo_prompts[0], 2)
    warm.run(max_wall=120.0)

    def run_arm(preempt_on):
        import time as _time

        prev = {k: flags.get_flag(k) for k in
                ("serving_preempt", "serving_preempt_horizon")}
        flags.set_flags({"serving_preempt": preempt_on,
                         "serving_preempt_horizon": 30.0})
        try:
            before = obs.snapshot()
            fleet = FleetRouter(
                model, replicas=cfg["replicas"],
                max_batch=cfg["max_batch"], page_size=cfg["page_size"],
                max_seq_len=cfg["max_seq_len"],
                prefill_chunk=cfg["page_size"])
            # saturate every slot with no-deadline long generations
            brids = [fleet.submit(p, cfg["batch_tokens"],
                                  replica=i % cfg["replicas"])
                     for i, p in enumerate(batch_prompts)]
            guard = 0
            while any(e._slots.count(None) for e in fleet.engines) \
                    and guard < 200:
                fleet.run_step()        # until every slot is decoding
                guard += 1
            # tight-deadline arrivals land mid-overload; TTFT from
            # host stamps, slo tenant only
            submit_t, ttft = {}, {}

            def cb(rid, tok, done):
                if not done and rid not in ttft:
                    ttft[rid] = _time.perf_counter() - submit_t[rid]

            srids = []
            for p in slo_prompts:
                ts = _time.perf_counter()
                rid = fleet.submit(p, cfg["slo_tokens"],
                                   deadline=cfg["slo_deadline"],
                                   on_token=cb)
                submit_t[rid] = ts
                srids.append(rid)
            t0 = _time.perf_counter()
            while fleet.has_work() and \
                    _time.perf_counter() - t0 < 300.0:
                fleet.run_step()
            st = fleet.statuses()
            out = fleet.take_results()
            after = obs.snapshot()
            import numpy as np
            vals = [ttft[r] for r in srids if r in ttft]
            preempts = sum(e.preemptions for e in fleet.engines)
            return {
                "batch": {r: out.get(r, []) for r in brids},
                "slo": {r: out.get(r, []) for r in srids},
                "statuses": {r: st.get(r, "PENDING")
                             for r in brids + srids},
                "slo_ttft_p99_s": round(
                    float(np.quantile(vals, 0.99)), 6) if vals else None,
                "slo_ttft_p50_s": round(
                    float(np.quantile(vals, 0.5)), 6) if vals else None,
                "preemptions": preempts,
                "per_replica": replica_counter_deltas(
                    before, after, _FLEET_REPLICA_FAMILIES),
            }
        finally:
            flags.set_flags(prev)

    on, off = run_arm(True), run_arm(False)
    # the victims' outputs must be bit-identical across arms (replay IS
    # preemption), and every request must end OK in the on-arm
    batch_parity = on["batch"] == off["batch"]
    slo_parity = on["slo"] == off["slo"]
    ok = (batch_parity and slo_parity
          and on["preemptions"] > 0 and off["preemptions"] == 0
          and all(s == "OK" for s in on["statuses"].values())
          and on["slo_ttft_p99_s"] is not None
          and off["slo_ttft_p99_s"] is not None
          and on["slo_ttft_p99_s"] < off["slo_ttft_p99_s"])
    return {
        "preempt_on": {k: v for k, v in on.items()
                       if k not in ("batch", "slo")},
        "preempt_off": {k: v for k, v in off.items()
                        if k not in ("batch", "slo")},
        "victims_bit_identical": batch_parity,
        "slo_bit_identical": slo_parity,
        "slo_ttft_p99_ratio": round(
            on["slo_ttft_p99_s"] / off["slo_ttft_p99_s"], 4)
        if off["slo_ttft_p99_s"] else None,
        "ok": bool(ok)}


def fleet_tiering_section(cfg, seed):
    """Prefix working set > device page budget, host tier absorbing
    the overflow, vs a big-pool no-tier reference."""
    import numpy as np

    import paddle_tpu.observability as obs
    from paddle_tpu.generation.serving import ServingEngine

    model = _fleet_model(cfg)
    rng = np.random.default_rng((seed, 7))
    ps = cfg["page_size"]
    prefixes = [rng.integers(0, cfg["vocab"],
                             (cfg["tier_prefix"],)).astype(np.int32)
                for _ in range(cfg["tier_orgs"])]
    rounds = []
    for rnd in range(cfg["tier_rounds"]):
        for pf in prefixes:
            body = rng.integers(0, cfg["vocab"], (ps,)).astype(np.int32)
            rounds.append(np.concatenate([pf, body]))

    def run_arm(tiered):
        eng = ServingEngine(
            model, max_batch=1, page_size=ps,
            max_seq_len=cfg["max_seq_len"], prefix_cache=True,
            num_pages=(cfg["tier_device_pages"] + 1 if tiered else 256),
            host_tier_pages=(cfg["tier_host_pages"] if tiered else 0),
            replica="tier" if tiered else "ref")
        outs = []
        for p in rounds:
            rid = eng.submit(p.copy(), cfg["max_new"])
            out = eng.run(max_wall=120.0)
            outs.append(out[rid])
        return eng, outs

    before = obs.snapshot()
    ref_eng, ref = run_arm(False)
    tier_eng, tier = run_arm(True)
    after = obs.snapshot()
    pr = replica_counter_deltas(before, after, _FLEET_REPLICA_FAMILIES)
    spills = pr.get("tier", {}).get("prefix_cache_spilled_pages", 0)
    restores = pr.get("tier", {}).get("prefix_cache_restored_pages", 0)
    working_set = (cfg["tier_orgs"]
                   * (-(-cfg["tier_prefix"] // ps) + 1))
    parity = tier == ref
    ok = (parity and spills > 0 and restores > 0
          and working_set > cfg["tier_device_pages"])
    return {
        "device_pages": cfg["tier_device_pages"],
        "host_tier_pages": cfg["tier_host_pages"],
        "prefix_working_set_pages": working_set,
        "spilled_pages": spills, "restored_pages": restores,
        "host_tier_peak_pages": tier_eng._host_tier_peak,
        "requests": len(rounds),
        "parity_bit_identical": parity,
        "ok": bool(ok)}


def bench_fleet(seed, quick=False):
    import jax

    import paddle_tpu.observability as obs

    # routing geometry: prompt = prefix + ONE body token, prefix a
    # page multiple — a warm-cache hit adopts every prefix page and
    # teacher-forces nothing, so TTFT(hit) is one decode step while
    # TTFT(miss) pays the whole monolithic prefill; per-replica pools
    # hold one org's working set comfortably but NOT all orgs', so
    # round-robin placement thrashes eviction at steady state
    cfg = (dict(vocab=256, replicas=3, max_batch=2, page_size=8,
                max_seq_len=128, num_pages=33, orgs=3, per_org=6,
                prefix=120, body=1, max_new=4,
                slo_requests=3, slo_tokens=3, slo_deadline=20.0,
                batch_tokens=48,
                tier_orgs=5, tier_prefix=24, tier_rounds=2,
                tier_device_pages=10, tier_host_pages=64)
           if quick else
           dict(vocab=256, replicas=3, max_batch=2, page_size=8,
                max_seq_len=256, num_pages=79, orgs=4, per_org=10,
                prefix=200, body=1, max_new=6,
                slo_requests=5, slo_tokens=4, slo_deadline=20.0,
                batch_tokens=72,
                tier_orgs=6, tier_prefix=32, tier_rounds=3,
                tier_device_pages=14, tier_host_pages=96))
    sections = {
        "routing": fleet_routing_section(cfg, seed),
        "preemption": fleet_preemption_section(cfg, seed),
        "tiering": fleet_tiering_section(cfg, seed),
    }
    ok = all(s["ok"] for s in sections.values())
    return {
        "schema": FLEET_SCHEMA, "bench": "fleet_load",
        "backend": jax.default_backend(), "seed": seed,
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in cfg.items()},
        "sections": sections,
        "ok": bool(ok),
        "telemetry": obs.snapshot(),
        "memory": obs.memory.section(),
    }


# ====================================================== spec bench (r16)
SPEC_SCHEMA = 1


def _spec_pair(seed, max_pos):
    """The draft-agreement rig: a 4-layer tiny Llama target whose
    layers >= 1 have o_proj/down_proj scaled by 3e-2 — near-identity
    residual contributions, so the residual stream leaving layer 3 is
    close to the stream leaving layer 0 — plus a 1-layer draft SHARING
    the target's embedding, layer-0, final-norm and head weights. The
    draft is a structural truncation of its target (the production
    speculative-serving shape), so rounds mostly accept but real
    rejections still occur, at a quarter of the target's layer cost."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    dims = dict(vocab_size=256, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=128,
                max_position_embeddings=max_pos)
    paddle.seed(seed)
    target = LlamaForCausalLM(LlamaConfig(num_hidden_layers=4, **dims))
    sd = dict(target.state_dict())
    for li in range(1, 4):
        for nm in (f"llama.layers.{li}.self_attn.o_proj.weight",
                   f"llama.layers.{li}.mlp.down_proj.weight"):
            sd[nm] = paddle.to_tensor(sd[nm].numpy() * 3e-2)
    target.set_state_dict(sd)
    paddle.seed(seed + 1)
    draft = LlamaForCausalLM(LlamaConfig(num_hidden_layers=1, **dims))
    dsd = dict(draft.state_dict())
    tsd = target.state_dict()
    for k in dsd:
        if k in tsd:                # embed, layer 0, final norm, head
            dsd[k] = tsd[k]
    draft.set_state_dict(dsd)
    return target, draft


def _spec_engine(target, draft, cfg):
    from paddle_tpu.generation.serving import ServingEngine

    return ServingEngine(target, max_batch=cfg["max_batch"],
                         page_size=cfg["page_size"],
                         max_seq_len=cfg["max_seq_len"],
                         draft_model=draft)


def spec_throughput_section(target, draft, cfg, seed):
    """Batch-1 plain vs speculative A/B: REPEATS measured passes per
    arm after a warmup pass (the warmup's γ ladder climbs through
    every rung, so every draft/verify/sync program the measured passes
    touch is already compiled). Tokens/s is min over passes for BOTH
    arms — the structural rate recurs every pass while a one-off OS
    spike only slows one — and the retrace ledger spans all measured
    passes of both arms."""
    import numpy as np

    import paddle_tpu.observability as obs

    rng = np.random.default_rng((seed, 0))
    prompt = rng.integers(0, cfg["vocab"],
                          (cfg["prompt_len"],)).astype(np.int32)

    def one_pass(use_draft):
        eng = _spec_engine(target, draft if use_draft else None, cfg)
        rid = eng.submit(prompt, cfg["max_new"])
        t0 = time.perf_counter()
        out = eng.run(max_wall=300.0)
        return eng, out[rid], time.perf_counter() - t0, eng.status(rid)

    def run_arm(use_draft):
        one_pass(use_draft)                             # warmup
        before = obs.snapshot()
        walls, statuses = [], []
        for _ in range(REPEATS):
            eng, out, wall, status = one_pass(use_draft)
            walls.append(wall)
            statuses.append(status)
        after = obs.snapshot()
        tps = [round(len(out) / w, 2) for w in walls]
        metrics = {
            "tokens": len(out),
            "passes": REPEATS,
            "wall_s_per_pass": [round(w, 4) for w in walls],
            "tokens_per_s_per_pass": tps,
            "tokens_per_s": min(tps),
            "steady_retraces": trace_total(after) - trace_total(before),
            "all_ok": all(s == "OK" for s in statuses),
        }
        if use_draft:
            acc, rej = eng.spec_tokens_accepted, eng.spec_tokens_rejected
            metrics.update(
                spec_rounds=eng.spec_rounds,
                spec_tokens_accepted=acc, spec_tokens_rejected=rej,
                spec_accept_rate=round(acc / max(1, acc + rej), 4))
        return metrics, out

    plain, plain_out = run_arm(False)
    spec, spec_out = run_arm(True)
    parity = spec_out == plain_out
    speedup = (round(spec["tokens_per_s"] / plain["tokens_per_s"], 4)
               if plain["tokens_per_s"] else None)
    ok = (parity and speedup is not None
          and speedup >= cfg["speedup_bar"]
          and plain["steady_retraces"] == 0
          and spec["steady_retraces"] == 0
          and plain["all_ok"] and spec["all_ok"])
    return {"arms": {"plain": plain, "spec": spec},
            "parity_bit_identical": parity,
            "tokens_per_s_speedup": speedup,
            "speedup_bar": cfg["speedup_bar"],
            "ok": bool(ok)}


def spec_occupancy_section(target, draft, cfg, seed):
    """The γ+1 slot bill: n concurrent rows each cost γ+1 decode slots
    per round, so the largest affordable rung falls as occupancy
    rises. For each row count the sweep records the largest γ any
    round ran at while ALL submitted rows were still live (tail rounds
    after early finishes run at lower occupancy and would pollute the
    reading), and checks the speculative outputs against a plain
    engine on the same prompts — pricing changes the SCHEDULE, never
    the tokens."""
    import numpy as np

    rng = np.random.default_rng((seed, 1))
    prompts = [rng.integers(0, cfg["vocab"],
                            (cfg["prompt_len"],)).astype(np.int32)
               for _ in range(max(cfg["occ_rows"]))]

    rows = []
    for n in cfg["occ_rows"]:
        plain_eng = _spec_engine(target, None, cfg)
        prids = [plain_eng.submit(p, cfg["occ_max_new"])
                 for p in prompts[:n]]
        pout = plain_eng.run(max_wall=300.0)

        eng = _spec_engine(target, draft, cfg)
        rids = [eng.submit(p, cfg["occ_max_new"]) for p in prompts[:n]]
        gamma_full, rounds_full = 0, 0
        while eng.has_work():
            occ = sum(1 for s in eng._slots if s is not None)
            before = eng.spec_rounds
            eng.step()
            if occ == n and eng.spec_rounds > before:
                gamma_full = max(gamma_full, eng.spec_last_gamma)
                rounds_full += 1
        out = eng.results()
        rows.append({
            "rows": n,
            "gamma_at_full_occupancy": gamma_full,
            "rounds_at_full_occupancy": rounds_full,
            "rounds_total": eng.spec_rounds,
            "tokens_accepted": eng.spec_tokens_accepted,
            "tokens_rejected": eng.spec_tokens_rejected,
            "parity_bit_identical":
                [out.get(r, []) for r in rids] ==
                [pout.get(r, []) for r in prids],
        })
    gammas = [r["gamma_at_full_occupancy"] for r in rows]
    top_rung = cfg["rungs"][-1]
    ok = (all(r["parity_bit_identical"] for r in rows)
          and all(a >= b for a, b in zip(gammas, gammas[1:]))
          and gammas[0] == top_rung      # a lone row affords the top
          and gammas[-1] == 0)           # a full batch prices it out
    return {"rows": rows, "gamma_ladder": gammas,
            "top_rung": top_rung, "ok": bool(ok)}


def bench_spec(seed, quick=False):
    import jax

    import paddle_tpu.observability as obs
    from paddle_tpu import flags

    cfg = dict(vocab=256, max_batch=8, page_size=8, max_seq_len=192,
               prompt_len=16, max_new=(48 if quick else 96),
               occ_rows=(1, 2, 4, 8), occ_max_new=(48 if quick else 64),
               spec_slots=16, speedup_bar=1.8)
    target, draft = _spec_pair(31, max_pos=256)
    prev = flags.get_flags(("serving_spec_max_slots",))
    # 16 decode slots make the whole rung ladder reachable: one row
    # affords γ=8 (9 slots), a full batch of 8 affords none
    flags.set_flags({"serving_spec_max_slots": cfg["spec_slots"]})
    try:
        cfg["rungs"] = sorted(
            int(x) for x in
            str(flags.get_flag("serving_spec_rungs")).split(","))
        sections = {
            "throughput": spec_throughput_section(target, draft, cfg,
                                                  seed),
            "occupancy": spec_occupancy_section(target, draft, cfg,
                                                seed),
        }
    finally:
        flags.set_flags(prev)
    ok = all(s["ok"] for s in sections.values())
    return {
        "schema": SPEC_SCHEMA, "bench": "spec_decode",
        "backend": jax.default_backend(), "seed": seed,
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in cfg.items()},
        "sections": sections,
        "ok": bool(ok),
        "telemetry": obs.snapshot(),
        "memory": obs.memory.section(),
    }


# ================================================= kv-quant bench (r18)
KV_QUANT_SCHEMA = 1


def _kv_quant_model(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(1234)
    mcfg = GPTConfig.tiny()
    mcfg.max_position_embeddings = cfg["max_seq_len"]
    return mcfg, GPTForCausalLM(mcfg)


def _kv_quant_engine(model, cfg, kv_dtype, usable_pages):
    from paddle_tpu.generation.serving import ServingEngine

    return ServingEngine(model, max_batch=cfg["max_batch"],
                         page_size=cfg["page_size"],
                         max_seq_len=cfg["max_seq_len"],
                         num_pages=usable_pages + 1,
                         kv_dtype=kv_dtype)


def _kv_quant_drain(eng, prompts, max_new):
    """Submit everything up front and step to drain: how many scheduler
    steps the backlog takes, and the queue-depth integral over them —
    the page-pressure queueing regime made visible as one number."""
    rids = [eng.submit(p, max_new) for p in prompts]
    steps = 0
    queue_steps = 0
    while eng.has_work():
        queue_steps += len(eng._queue)
        eng.step()
        steps += 1
    out = eng.results()
    return {"rids": rids,
            "outputs": [out.get(r, []) for r in rids],
            "statuses": [eng.status(r) for r in rids],
            "steps_to_drain": steps,
            "queue_depth_integral": queue_steps}


def bench_kv_quant(seed, quick=False):
    """The r18 quantized-KV A/B at FIXED pool memory: the bf16/native
    arm's byte budget, re-spent on int8 pages, must buy ~2x (on an f32
    CPU pool: more) the usable page budget — measured from the pool
    LEDGER, never the planner — and the page-pressure queueing regime
    must recede (smaller queue-depth integral, no more drain steps).
    The int8 arm re-runs bit-identically (amax quantization is
    deterministic and write-order independent), the analytic
    ``memwatch plan`` pool term agrees with the ledger within the 10%
    bar, and the retrace ledger stays at zero across the measured
    passes of both arms."""
    import numpy as np

    import jax

    import paddle_tpu.observability as obs
    from paddle_tpu.observability import memory as memwatch

    cfg = (dict(vocab=256, max_batch=8, page_size=8, max_seq_len=128,
                native_pages=9, prompt_len=24, max_new=8, requests=6)
           if quick else
           dict(vocab=256, max_batch=8, page_size=8, max_seq_len=128,
                native_pages=9, prompt_len=24, max_new=8, requests=10))
    mcfg, model = _kv_quant_model(cfg)
    rng = np.random.default_rng((seed, 7))
    prompts = [rng.integers(0, cfg["vocab"],
                            (cfg["prompt_len"],)).astype(np.int32)
               for _ in range(cfg["requests"])]

    # ---- fixed-memory page accounting, ledger-measured: the native
    # arm's pool bytes are the budget; the int8 arm spends the same
    # bytes on quantized pages (int8 payload + f32 per-token scales)
    native_eng = _kv_quant_engine(model, cfg, "native",
                                  cfg["native_pages"])
    nled = native_eng.pool.ledger()
    budget = nled["bytes_per_page"] * nled["usable_pages"]
    int8_probe = _kv_quant_engine(model, cfg, "int8", 1)
    int8_bpp = int8_probe.pool.ledger()["bytes_per_page"]
    int8_pages = budget // int8_bpp
    int8_eng = _kv_quant_engine(model, cfg, "int8", int8_pages)
    iled = int8_eng.pool.ledger()
    ratio = iled["usable_pages"] / nled["usable_pages"]
    pages = {
        "byte_budget": int(budget),
        "native": {"usable_pages": nled["usable_pages"],
                   "bytes_per_page": nled["bytes_per_page"]},
        "int8": {"usable_pages": iled["usable_pages"],
                 "bytes_per_page": iled["bytes_per_page"]},
        "usable_page_ratio": round(ratio, 4),
        # the bf16-pool reference ratio (2-byte payload): what the same
        # A/B yields on chip, where pools store bf16 rather than f32
        "bf16_reference_ratio": round(
            2 * (nled["bytes_per_page"] // 4) / int8_bpp, 4),
    }

    # ---- memwatch plan's analytic pool term vs the measured ledger
    dims = memwatch.ModelDims.of_config(mcfg)
    plan = memwatch.estimate_engine_memory(
        dims, page_size=cfg["page_size"],
        page_budget=iled["usable_pages"], max_batch=cfg["max_batch"],
        max_seq_len=cfg["max_seq_len"], kv_dtype="int8",
        param_count=dims.param_count or sum(
            int(np.prod(v.shape)) for v in model.raw_state()[0].values()))
    ledger_pool_bytes = iled["bytes_per_page"] * (iled["usable_pages"] + 1)
    plan_pool_bytes = plan["breakdown"]["kv_pool"]
    plan_rel_err = plan_pool_bytes / ledger_pool_bytes - 1.0
    planfit = {"plan_kv_pool_bytes": int(plan_pool_bytes),
               "ledger_kv_pool_bytes": int(ledger_pool_bytes),
               "rel_err": round(plan_rel_err, 4),
               "within_10pct": bool(abs(plan_rel_err) <= 0.10)}

    # ---- the queueing A/B: pass 1 warms every program (admission,
    # chunkless prefill, each rung the backlog visits), pass 2 is
    # measured under the zero-retrace bar
    arms = {}
    outputs = {}
    for arm, pages_arm in (("native", nled["usable_pages"]),
                           ("int8", iled["usable_pages"])):
        runs = []
        before = after = None
        for p in range(2):
            eng = _kv_quant_engine(model, cfg, arm, pages_arm)
            if p == 1:
                before = obs.snapshot()
            runs.append(_kv_quant_drain(eng, prompts, cfg["max_new"]))
            if p == 1:
                after = obs.snapshot()
        meas = runs[1]
        arms[arm] = {
            "requests": cfg["requests"],
            "steps_to_drain": meas["steps_to_drain"],
            "queue_depth_integral": meas["queue_depth_integral"],
            "statuses": {s: meas["statuses"].count(s)
                         for s in set(meas["statuses"])},
            "all_ok": all(s == "OK" for s in meas["statuses"]),
            "steady_retraces": trace_total(after) - trace_total(before),
            "rerun_bit_identical": runs[0]["outputs"] == meas["outputs"],
        }
        outputs[arm] = meas["outputs"]

    # cross-arm token agreement is informational: int8 attention is
    # tolerance-bounded, not bit-equal, so greedy argmax may flip —
    # the tolerance contract lives in the kernel parity tests
    agree = [sum(1 for a, b in zip(x, y) if a == b) / max(len(x), 1)
             for x, y in zip(outputs["native"], outputs["int8"])]
    receding = {
        "native_queue_depth_integral":
            arms["native"]["queue_depth_integral"],
        "int8_queue_depth_integral": arms["int8"]["queue_depth_integral"],
        "receded": bool(arms["int8"]["queue_depth_integral"]
                        < arms["native"]["queue_depth_integral"]
                        and arms["int8"]["steps_to_drain"]
                        <= arms["native"]["steps_to_drain"]),
    }
    ok = (ratio >= 1.8
          and planfit["within_10pct"]
          and receding["receded"]
          and all(a["all_ok"] for a in arms.values())
          and all(a["steady_retraces"] == 0 for a in arms.values())
          and arms["int8"]["rerun_bit_identical"]
          and arms["native"]["rerun_bit_identical"])
    return {
        "schema": KV_QUANT_SCHEMA, "bench": "kv_quant",
        "backend": jax.default_backend(), "seed": seed,
        "config": {**cfg, "quick": bool(quick)},
        "pages": pages,
        "plan_vs_ledger": planfit,
        "arms": arms,
        "page_pressure": receding,
        "token_agreement_per_request": [round(a, 4) for a in agree],
        "ok": bool(ok),
        "telemetry": obs.snapshot(),
        "memory": obs.memory.section(),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="bank the ledger JSON here "
                         "(repo convention: SERVING_LOAD_r12.json)")
    ap.add_argument("--per-tenant", type=int, default=16,
                    help="requests per tenant")
    ap.add_argument("--seed", type=int, default=712)
    ap.add_argument("--quick", action="store_true",
                    help="the small deterministic tier-1 slice")
    ap.add_argument("--fleet", action="store_true",
                    help="run the r14 fleet acceptance bench (routing "
                         "A/B + preemption + tiering) instead of the "
                         "single-engine chunked/monolithic A/B")
    ap.add_argument("--spec", action="store_true",
                    help="run the r16 speculative-decoding acceptance "
                         "bench (batch-1 plain-vs-spec throughput A/B "
                         "+ the γ-vs-occupancy ladder) instead of the "
                         "single-engine chunked/monolithic A/B")
    ap.add_argument("--kv-dtype", default=None, choices=("int8",),
                    help="run the r18 quantized-KV acceptance bench: "
                         "native-vs-int8 pool A/B at FIXED pool memory "
                         "(~2x the usable page budget, measured from "
                         "the ledger; page-pressure queueing recedes; "
                         "bit-identical re-runs; zero retraces)")
    args = ap.parse_args()

    doc = (bench_fleet(args.seed, quick=args.quick) if args.fleet
           else bench_spec(args.seed, quick=args.quick) if args.spec
           else bench_kv_quant(args.seed, quick=args.quick)
           if args.kv_dtype
           else bench(args.per_tenant, args.seed, quick=args.quick))
    brief = {k: v for k, v in doc.items() if k != "telemetry"}
    print(json.dumps(brief, indent=2, sort_keys=True))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
