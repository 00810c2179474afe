"""Render / exercise paddle_tpu runtime telemetry.

Three modes:

  1. **File mode** (default): read a metrics snapshot JSON — either a
     raw ``observability.snapshot()`` dump or any ``BENCH_*.json``-style
     artifact that embeds one under a ``"telemetry"`` key (top-level or
     inside a ``"results"`` row) — and render it as a human table,
     ``--json``, or ``--prom`` (Prometheus text exposition format).
     Histograms get derived p50/p90/p99 columns. ``--memory`` renders
     the memwatch view instead: the per-program CompiledMemoryStats
     table, the KV pool ledger gauges, and device/host watermarks.

         python tools/telemetry_dump.py FUSED_DECODE_BENCH_r06.json
         python tools/telemetry_dump.py snap.json --prom

  2. **Demo mode** (``--demo``): run a small in-process ServingEngine
     load (tiny Llama, CPU-safe), then print the live snapshot and
     optionally write the Chrome-trace timeline (``--trace out.json``;
     open in chrome://tracing or Perfetto). The zero->aha path for the
     telemetry subsystem.

  3. **Span mode** (``--spans``): SELF time by span name — a span's
     length less what its children (the records whose ``parent`` is its
     ``id``) cover — from the live ring with ``--demo``, or from a
     Chrome-trace JSON written by ``tracer().save``. Where a serving
     step's host time goes, phase by phase.

         python tools/telemetry_dump.py --demo --spans
         python tools/telemetry_dump.py trace.json --spans

No file argument and no --demo reads a snapshot JSON from stdin.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

QUANTILES = (0.5, 0.9, 0.99)


def extract_snapshot(doc: dict):
    """A snapshot dict from any of the accepted shapes."""
    if "metrics" in doc and isinstance(doc["metrics"], dict):
        return doc
    if isinstance(doc.get("telemetry"), dict):
        return doc["telemetry"]
    for row in doc.get("results", []):
        if isinstance(row, dict) and isinstance(row.get("telemetry"), dict):
            return row["telemetry"]
    raise SystemExit("no metrics snapshot found (expected a "
                     "snapshot dict or an artifact with a 'telemetry' key)")


def extract_memory(doc: dict):
    """An artifact's ``"memory"`` section from any of the accepted
    shapes (same contract as extract_snapshot: top-level or inside a
    ``"results"`` row), or None."""
    if not isinstance(doc, dict):
        return None
    if isinstance(doc.get("memory"), dict):
        return doc["memory"]
    for row in doc.get("results", []):
        if isinstance(row, dict) and isinstance(row.get("memory"), dict):
            return row["memory"]
    return None


def render_memory(snap: dict, doc: dict = None) -> str:
    """The --memory view: per-program compiled-memory table (pivoted
    from the program_memory_bytes gauges, or an artifact's explicit
    "memory" section) + the KV pool ledger + device/host watermarks."""
    lines = []
    mets = snap.get("metrics", {})
    mem = extract_memory(doc) if doc else None
    # ---- per-program table: prefer an artifact's banked rows, else
    # pivot the gauge series back into rows
    rows = []
    if mem:
        rows = mem.get("programs", [])
    if not rows:
        by_key = {}
        fam = mets.get("program_memory_bytes", {"series": []})
        for s in fam["series"]:
            lbl = s["labels"]
            key = (lbl.get("model", ""), lbl["kind"], lbl["bucket"],
                   lbl.get("extra", ""))
            row = by_key.setdefault(key, {
                "model": key[0], "kind": key[1], "bucket": key[2],
                "extra": key[3]})
            row[lbl["section"]] = int(s["value"])
        rows = [by_key[k] for k in sorted(by_key)]
    if rows:
        from paddle_tpu.observability.memory import format_program_table

        lines.append("# program memory (CompiledMemoryStats, bytes)")
        lines.append(format_program_table(rows))
    else:
        lines.append("# no program memory rows (FLAGS_memwatch off, or "
                     "nothing compiled)")
    # ---- pool ledger gauges
    led = []
    for name in ("kv_pool_pages", "kv_pool_bytes"):
        for s in mets.get(name, {"series": []})["series"]:
            lbl = ",".join(f"{k}={v}" for k, v in
                           sorted(s["labels"].items()))
            led.append(f"  {name}{{{lbl}}} = {s['value']:g}")
    for name in ("kv_pool_fragmentation", "serving_kv_pages_in_use",
                 "serving_prefix_pinned_pages",
                 "kv_host_tier_peak_pages"):
        for s in mets.get(name, {"series": []})["series"]:
            lbl = ",".join(f"{k}={v}" for k, v in
                           sorted(s["labels"].items()))
            suffix = f"{{{lbl}}}" if lbl else ""
            led.append(f"  {name}{suffix} = {s['value']:g}")
    if led:
        lines.append("# kv pool ledger")
        lines.extend(led)
    # ---- watermarks: live gauges when present; banked artifacts carry
    # them under memory.watermarks instead (benches snapshot telemetry
    # BEFORE obs.memory.section() publishes the gauges)
    wm = []
    for name in ("device_memory_bytes", "host_memory_bytes"):
        for s in mets.get(name, {"series": []})["series"]:
            lbl = ",".join(f"{k}={v}" for k, v in
                           sorted(s["labels"].items()))
            wm.append(f"  {name}{{{lbl}}} = {s['value']:g}")
    if not wm and mem and isinstance(mem.get("watermarks"), dict):
        banked_wm = mem["watermarks"]
        for dev, stats in sorted(banked_wm.get("devices", {}).items()):
            for k, v in sorted(stats.items()):
                wm.append(f"  device_memory_bytes{{device={dev},"
                          f"stat={k}}} = {v:g}")
        for k, v in sorted(banked_wm.get("host", {}).items()):
            wm.append(f"  host_memory_bytes{{stat={k}}} = {v:g}")
    if wm:
        lines.append("# watermarks")
        lines.extend(wm)
    return "\n".join(lines)


def render_programs() -> str:
    """The --programs view: a LIVE census of the in-process decode
    program cache — one row per cached key (kind, model-signature
    prefix, batch bucket, page budget, dtype, the extra tuple, trace
    count, banked compile seconds) plus the memwatch peak bytes when
    the program's memory row was captured. The cache is process state,
    not a snapshot artifact, so this only shows anything under --demo
    (or when imported by an in-process serving harness)."""
    from paddle_tpu.generation.program_cache import decode_program_cache
    from paddle_tpu.observability.memory import _extra_str, program_table

    cache = decode_program_cache()
    stats = cache.stats()
    keys = cache.keys()                  # admission order
    for k in stats["traces"]:            # traced keys survive a clear of
        if k not in keys:                # _programs only via stats; show
            keys.append(k)               # them too rather than lose them
    mem_peak = {(r["kind"], str(r["bucket"]), str(r["extra"])): r["peak"]
                for r in program_table() if "peak" in r}
    cols = ("kind", "model", "bucket", "pages", "dtype", "extra",
            "traces", "compile_s", "peak_bytes")
    lines = [f"# decode program cache: {stats['programs']} program(s), "
             f"{stats['hits']} hit(s), {stats['misses']} miss(es)"]
    lines.append("  ".join(f"{h:>18s}" for h in cols))
    for k in keys:
        row = (k.kind, k.model_sig[:8], str(k.batch_bucket),
               _extra_str(k.page_budget), k.dtype,
               _extra_str(k.extra) or "-",
               str(stats["traces"].get(k, 0)),
               f"{stats['compile_seconds'].get(k, 0.0):.3f}",
               str(mem_peak.get((k.kind, str(k.batch_bucket),
                                 _extra_str(k.extra)), "-")))
        lines.append("  ".join(f"{v:>18s}" for v in row))
    if not keys:
        lines.append("  (no cached programs in this process)")
    return "\n".join(lines)


def render_table(snap: dict) -> str:
    from paddle_tpu.observability import series_quantile

    lines = []
    for name in sorted(snap.get("metrics", {})):
        fam = snap["metrics"][name]
        for s in fam["series"]:
            lbl = ",".join(f"{k}={v}" for k, v in
                           sorted(s.get("labels", {}).items()))
            tag = f"{name}{{{lbl}}}" if lbl else name
            if fam["type"] == "histogram":
                qs = "  ".join(
                    f"p{int(q * 100)}={series_quantile(s, q):.6g}"
                    if s["count"] else f"p{int(q * 100)}=-"
                    for q in QUANTILES)
                lines.append(f"{tag:52s} {fam['type']:9s} "
                             f"count={s['count']} sum={s['sum']:.6g}  {qs}")
            else:
                lines.append(f"{tag:52s} {fam['type']:9s} "
                             f"value={s['value']:g}")
    return "\n".join(lines)


def self_times(events):
    """{span name: [count, total ms, self ms]} of the ring's complete
    events. A record's self time is its length less its children's; a
    record without ``id`` (an older trace) has no children to find."""
    spans = [e for e in events if e.get("ph") == "X"]
    covered = {}
    for e in spans:
        if e.get("parent"):
            covered[e["parent"]] = covered.get(e["parent"], 0.0) + e["dur"]
    out = {}
    for e in spans:
        row = out.setdefault(e["name"], [0, 0.0, 0.0])
        row[0] += 1
        row[1] += e["dur"] / 1e3
        row[2] += max(0.0, e["dur"] - covered.get(e.get("id"), 0.0)) / 1e3
    return out


def render_self_times(events) -> str:
    lines = [f"{'span':28s} {'count':>7s} {'total ms':>11s} "
             f"{'self ms':>11s} {'self ms each':>13s}"]
    for name, (n, total, own) in sorted(self_times(events).items()):
        lines.append(f"{name:28s} {n:7d} {total:11.3f} {own:11.3f} "
                     f"{own / n:13.4f}")
    lines.append("(request.queued, request.first_token and "
                 "request.complete are a request's life, not a step's "
                 "time: they overlap the engine's spans and have no parent)")
    return "\n".join(lines)


def run_demo(n_requests: int, tokens: int, trace_path,
             programs: bool = False, spans: bool = False):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags, observability as obs
    from paddle_tpu.generation.program_cache import (
        clear_decode_program_cache, decode_program_cache)
    from paddle_tpu.generation.serving import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (8 + (i % 3) * 4,))
               .astype(np.int32) for i in range(n_requests)]

    max_seq = 32 + tokens               # prompts are <= 16 tokens

    def mixed_load():
        """The snapshot/timeline workload: staggered lengths + prefix
        cache, memwatch on."""
        flags.set_flags({"memwatch": True})
        clear_decode_program_cache()     # rebind the cache's memwatch
        eng = ServingEngine(model, max_batch=4, page_size=8,
                            max_seq_len=max_seq, prefix_cache=True)
        for p in prompts:
            eng.submit(p, tokens)
        eng.run()
        return decode_program_cache().trace_count(eng.decode_key) - 1

    prior = flags.snapshot(("memwatch",)).as_tuple()
    try:
        retraces = mixed_load()
        snap = obs.registry().snapshot()
        if trace_path:
            obs.tracer().save(trace_path)
            print(f"chrome trace -> {trace_path} "
                  f"({len(obs.tracer())} events)", file=sys.stderr)
        result = {"steady_retraces": retraces}
        print(json.dumps(result), file=sys.stderr)
        # the census reads LIVE cache state, so render it before the
        # finally clears the cache (the snapshot survives, keys don't)
        prog_text = render_programs() if programs else None
        if spans:
            prog_text = render_self_times(obs.tracer().events())
    finally:
        flags.set_flags(dict(prior))
        clear_decode_program_cache()
    return snap, prog_text


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", help="snapshot or artifact JSON "
                    "(stdin when omitted)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the snapshot as JSON")
    ap.add_argument("--prom", action="store_true",
                    help="emit Prometheus text exposition format")
    ap.add_argument("--memory", action="store_true",
                    help="memwatch view: per-program compiled-memory "
                    "table + KV pool ledger + watermarks")
    ap.add_argument("--programs", action="store_true",
                    help="live decode-program-cache census: one row per "
                    "cached DecodeKey (kind/model/bucket/pages/dtype/"
                    "extra) with trace counts, compile seconds, and "
                    "memwatch peak bytes; pairs with --demo")
    ap.add_argument("--spans", action="store_true",
                    help="self time by span name (length less children) "
                    "from the live ring (--demo) or a saved Chrome trace")
    ap.add_argument("--demo", action="store_true",
                    help="run a tiny in-process ServingEngine load and "
                    "dump ITS telemetry")
    ap.add_argument("--trace", metavar="PATH",
                    help="with --demo: write the Chrome-trace timeline")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    doc = None
    prog_text = None
    if args.demo:
        snap, prog_text = run_demo(args.requests, args.tokens, args.trace,
                                   programs=args.programs,
                                   spans=args.spans)
    else:
        if args.programs:
            # live cache of THIS process — no demo means nothing was
            # admitted, but the empty census (with its explanatory
            # trailer line) is still the honest answer
            print(render_programs())
            return 0
        if args.path:
            with open(args.path) as fh:
                doc = json.load(fh)
        else:
            doc = json.load(sys.stdin)
        if args.spans:
            print(render_self_times(doc["traceEvents"]))
            return 0
        snap = extract_snapshot(doc)

    if args.prom:
        from paddle_tpu.observability import to_prometheus
        sys.stdout.write(to_prometheus(snap))
    elif args.as_json:
        json.dump(snap, sys.stdout, indent=1)
        sys.stdout.write("\n")
    elif args.memory:
        print(render_memory(snap, doc))
    elif args.programs or args.spans:
        print(prog_text)
    else:
        print(render_table(snap))
    return 0


if __name__ == "__main__":
    sys.exit(main())
