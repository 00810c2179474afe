"""memwatch CLI: what-if HBM planner + per-program memory regression gate.

Four subcommands over ``paddle_tpu/observability/memory.py`` (one
accounting code path with the live capture, the benches, and
``tools/memory_70b.py`` / ``tools/pipeline_memory.py``):

  **plan** — analytic serving-memory breakdown for a configuration that
  may be too big to compile locally, against a chip's HBM::

      python tools/memwatch.py plan --model llama2_7b --weight-dtype int8 \
          --kv-dtype int8 --page-budget 1024 --page-size 64 --rung 32 \
          --chunk 256 --max-seq 2048 --hbm-gb 16

  answers "does 7B int8 + page budget P + rung 32 + chunk 256 fit in
  16 GB?" with the transparent weights/pool/workspace/margin breakdown
  and the largest page budget that still fits.

  **bank** — run the tier-1-sized capture suite (tiny Llama fused +
  chunked serving, tiny GPT generic serving, tiny GPT train step) on
  this backend and bank every program's CompiledMemoryStats rows plus
  the estimator's predictions::

      python tools/memwatch.py bank --out MEMWATCH_r18.json

  **check** — re-run the same capture suite and flag any program whose
  temp/peak grew beyond tolerance vs the banked artifact (the memory
  analogue of the zero-retrace gate; exit code 1 on growth)::

      python tools/memwatch.py check --artifact MEMWATCH_r18.json

  **view** — render a banked artifact (or any bench row with a
  ``"memory"`` section) as a table.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toolenv  # noqa: E402

# repo-root path setup is unconditional — backend forcing (below) is not
sys.path.insert(0, toolenv.repo_root())

SCHEMA = 1
GB = 1 << 30

_MODELS = ("llama_tiny", "llama2_7b", "llama2_70b", "gpt_tiny")


def _dims(name: str):
    from paddle_tpu.observability.memory import ModelDims

    if name == "gpt_tiny":
        from paddle_tpu.models import GPTConfig
        cfg = GPTConfig.tiny()
    else:
        from paddle_tpu.models import LlamaConfig
        ctor = {"llama_tiny": LlamaConfig.tiny,
                "llama2_7b": LlamaConfig.llama2_7b,
                "llama2_70b": LlamaConfig.llama2_70b}.get(name)
        if ctor is None:
            raise SystemExit(f"unknown --model {name!r} (have {_MODELS})")
        cfg = ctor()
    return ModelDims.of_config(cfg)


# ------------------------------------------------------------------ plan
def cmd_plan(args) -> int:
    from paddle_tpu.observability import memory as memwatch

    dims = _dims(args.model)
    kw = dict(page_size=args.page_size, max_batch=args.rung,
              max_seq_len=args.max_seq, chunk=args.chunk,
              weight_dtype=args.weight_dtype, kv_dtype=args.kv_dtype,
              host_tier_pages=args.host_tier_pages,
              tp=getattr(args, "tp", 1))
    if args.draft:
        # r16 speculative serving: the draft's weights + worst-case KV
        # pool are resident, the (1, gamma+1) verify chunk is workspace
        kw.update(draft_dims=_dims(args.draft),
                  spec_gamma=args.spec_gamma,
                  draft_weight_dtype=args.draft_weight_dtype
                  or args.weight_dtype)
    try:
        plan = memwatch.estimate_engine_memory(
            dims, page_budget=args.page_budget, **kw)
    except ValueError as e:
        # the r19 tensor-parallel refusal: a degree the engine itself
        # would reject (kv-head/head/MLP indivisibility) never gets an
        # HBM number — silently rounding would under-bill every shard
        print(f"# memwatch plan: {args.model} tp={kw['tp']}")
        print(f"  -> REFUSED: {e}")
        return 1
    hbm = int(args.hbm_gb * GB)
    verdict = memwatch.fits(plan, hbm)

    def fmt(b):
        return f"{b / GB:8.3f} GB" if b >= 1 << 20 else f"{b:8d} B "

    spec_note = (f" draft={args.draft} gamma={args.spec_gamma}"
                 if args.draft else "")
    tp_note = (f" tp={kw['tp']} [PER-SHARD bill: sharded weights + "
               f"kv-head-partitioned pool + per-shard workspaces]"
               if kw["tp"] > 1 else "")
    print(f"# memwatch plan: {args.model} weights={args.weight_dtype} "
          f"kv={args.kv_dtype} rung={args.rung} chunk={args.chunk} "
          f"pages={plan['config']['usable_pages']}x{args.page_size} "
          f"max_seq={args.max_seq} host_tier={args.host_tier_pages}"
          f"{spec_note}{tp_note}")
    for k, v in plan["breakdown"].items():
        print(f"  {k:32s} {fmt(v)}")
    print(f"  {'TOTAL (device HBM)':32s} {fmt(plan['total'])}")
    print(f"  {'HBM':32s} {fmt(hbm)}")
    print(f"  -> {'FITS' if verdict['fits'] else 'DOES NOT FIT'} "
          f"(headroom {verdict['headroom_bytes'] / GB:+.3f} GB)")
    # host-RAM KV tier: priced jointly, billed to host not HBM — the
    # serving ledger's kv_pool_bytes{state="spilled"} /
    # kv_host_tier_peak_pages gauges report the live tier against this
    ht = plan["host_tier"]
    if ht["pages"]:
        eff = ht["pages"] + plan["config"]["usable_pages"]
        print(f"  {'host KV tier (host RAM)':32s} {fmt(ht['bytes'])}  "
              f"[{ht['pages']} pages -> effective prefix working set "
              f"{eff} pages]")
        if args.host_ram_gb:
            host = int(args.host_ram_gb * GB)
            print(f"  {'host RAM':32s} {fmt(host)}  "
                  f"(tier headroom {(host - ht['bytes']) / GB:+.3f} GB)")
    # the planner's most actionable number: the largest page budget
    # that still fits this config (binary search over the analytic
    # model — each probe is arithmetic, not a compile)
    lo, hi = 0, 1 << 24
    while lo < hi:
        mid = (lo + hi + 1) // 2
        p = memwatch.estimate_engine_memory(dims, page_budget=mid, **kw)
        if p["total"] <= hbm:
            lo = mid
        else:
            hi = mid - 1
    toks = lo * args.page_size
    print(f"  max usable page budget at this HBM: {lo} pages "
          f"({toks} KV tokens, ~{toks // max(args.max_seq, 1)} full-length "
          f"sequences)")
    # ---- r17: N-layer fused decode kernel VMEM pricing. HBM fit says
    # nothing about whether the grouped kernel's working set (weight
    # double-buffers, per-layer page blocks, activation scratch) fits
    # per-core VMEM — an unfittable N is REFUSED here, before anyone
    # ships FLAGS_fused_block_layers=N to a chip.
    vplan = None
    if args.fused_layers > 1:
        io = 4 if args.weight_dtype == "float32" else 2
        vplan = memwatch.plan_fused_layers(
            dims, fused_layers=args.fused_layers, batch=args.rung,
            page_size=args.page_size, io_dtype_bytes=io,
            vmem_limit=int(args.vmem_mb * (1 << 20)))
        print(f"# fused decode VMEM: N={args.fused_layers} "
              f"rung={args.rung} io={io}B")
        for k, v in vplan["breakdown"].items():
            print(f"  {k:32s} {v:10d} B")
        print(f"  {'TOTAL (per-core VMEM)':32s} {vplan['total']:10d} B")
        print(f"  {'VMEM limit':32s} {vplan['vmem_limit']:10d} B")
        if not vplan["fits"]:
            print(f"  -> REFUSED: --fused-layers {args.fused_layers} "
                  f"does not fit {args.vmem_mb:g} MiB VMEM "
                  f"(over by {-vplan['headroom_bytes']} B); "
                  f"lower N or the decode rung")
        else:
            print(f"  -> VMEM FITS (headroom "
                  f"{vplan['headroom_bytes']} B)")
    if args.json:
        print(json.dumps({"plan": plan, "verdict": verdict,
                          "max_page_budget": lo,
                          **({"fused_vmem": vplan} if vplan else {})}))
    if vplan is not None and not vplan["fits"]:
        return 1
    return 0 if verdict["fits"] else 1


# ------------------------------------------------- capture suite (bank)
def capture_suite() -> dict:
    """Build + run the tier-1-sized programs with memwatch armed and
    return {rows, estimates, backend}: tiny-Llama serving (fused decode,
    monolithic prefill, chunked prefill), tiny-GPT serving (generic
    decode), and a tiny-GPT TrainStep. Deterministic byte sizes — the
    regression gate diffs these rows."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import flags, observability as obs
    from paddle_tpu.generation.program_cache import (
        clear_decode_program_cache)
    from paddle_tpu.generation.serving import ServingEngine
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                                   LlamaForCausalLM)
    from paddle_tpu.observability import memory as memwatch

    prior = flags.snapshot(("memwatch",)).as_tuple()
    flags.set_flags({"memwatch": True})
    clear_decode_program_cache()
    memwatch.clear_program_table()
    rng = np.random.default_rng(13)
    estimates = []
    try:
        # --- tiny Llama: fused decode + monolithic prefill + chunk
        paddle.seed(13)
        lcfg = LlamaConfig.tiny()
        lmodel = LlamaForCausalLM(lcfg)
        eng = ServingEngine(lmodel, max_batch=2, page_size=8,
                            max_seq_len=48, prefill_chunk=8)
        for n in (6, 20):               # short (monolithic) + long (chunk)
            eng.submit(rng.integers(0, lcfg.vocab_size, (n,))
                       .astype(np.int32), 4)
        eng.run()
        estimates += _engine_estimates(eng, lcfg, chunk=8)
        # --- tiny Llama again, int8-quantized KV pool (r18): the fused
        # decode + prefill rows against a QuantizedPages pool — the gate
        # watches the quantized programs' sections (scale rows included)
        paddle.seed(13)
        qmodel = LlamaForCausalLM(lcfg)
        eng = ServingEngine(qmodel, max_batch=2, page_size=8,
                            max_seq_len=48, kv_dtype="int8")
        eng.submit(rng.integers(0, lcfg.vocab_size, (6,))
                   .astype(np.int32), 4)
        eng.run()
        estimates += _engine_estimates(eng, lcfg)
        # --- tiny Llama again, N-layer grouped decode (r17): banks the
        # decode_fused_nlayer rows so the gate watches the grouped
        # program's sections too
        paddle.seed(13)
        nprior = flags.snapshot(("fused_block_layers",)).as_tuple()
        flags.set_flags({"fused_block_layers": 2})
        try:
            nmodel = LlamaForCausalLM(lcfg)
            eng = ServingEngine(nmodel, max_batch=2, page_size=8,
                                max_seq_len=48)
            eng.submit(rng.integers(0, lcfg.vocab_size, (6,))
                       .astype(np.int32), 4)
            eng.run()
            estimates += _engine_estimates(eng, lcfg, fused_layers=2)
            # --- N-layer again with int4 weight tiles + int8 KV (r18):
            # the fully-quantized grouped program's rows
            paddle.seed(13)
            n4model = LlamaForCausalLM(lcfg)
            eng = ServingEngine(n4model, max_batch=2, page_size=8,
                                max_seq_len=48, kv_dtype="int8",
                                weight_dtype="int4")
            eng.submit(rng.integers(0, lcfg.vocab_size, (6,))
                       .astype(np.int32), 4)
            eng.run()
            estimates += _engine_estimates(eng, lcfg, fused_layers=2)
        finally:
            flags.set_flags(dict(nprior))
        # --- tiny GPT: generic decode path
        paddle.seed(13)
        gcfg = GPTConfig.tiny()
        gmodel = GPTForCausalLM(gcfg)
        eng = ServingEngine(gmodel, max_batch=2, page_size=8,
                            max_seq_len=48)
        eng.submit(rng.integers(0, gcfg.vocab_size, (6,))
                   .astype(np.int32), 4)
        eng.run()
        estimates += _engine_estimates(eng, gcfg)
        # --- tiny GPT train step
        _run_train_step(gcfg, gmodel, rng)
        rows = memwatch.program_table()
    finally:
        flags.set_flags(dict(prior))
        clear_decode_program_cache()
    return {"schema": SCHEMA, "bench": "memwatch",
            "backend": jax.default_backend(),
            "rows": rows, "estimates": estimates,
            "watermarks": memwatch.sample_device_memory(publish=False)}


def _engine_estimates(eng, cfg, chunk=None, fused_layers=1):
    """Estimator predictions for the engine's captured programs, with
    the compiled row alongside — the banked evidence that the analytic
    model tracks XLA's accounting."""
    import numpy as np

    from paddle_tpu.observability import memory as memwatch

    dims = memwatch.ModelDims.of_config(cfg)
    geom = memwatch.PoolGeometry.of_pool(eng.pool)
    pb = sum(memwatch.aval_bytes(v) for v in eng._params.values())
    pb += sum(memwatch.aval_bytes(v) for v in eng._buffers.values()
              if v is not None)
    out = []
    sig = eng._model_sig[:8]            # only THIS engine's programs
    # every DecodeKey.extra now carries the kv/weight dtype discriminant
    # (r18) — match it too, or a same-model engine pair (native + int8
    # pool) would cross-attribute each other's rows
    tag_kv = str(("kv", eng.kv_dtype))
    tag_wt = str(("wt", eng.weight_dtype))
    rows = {(r["kind"], r["bucket"], r["extra"]): r
            for r in memwatch.program_table()
            if r["model"] == sig and tag_kv in r["extra"]
            and tag_wt in r["extra"]}
    for (kind, bucket, extra), row in sorted(rows.items()):
        if kind == "decode_fused_nlayer":
            est = memwatch.estimate_decode_program(
                dims, geom, bucket, pb, fused_layers=fused_layers,
                int4_weights=eng.weight_dtype == "int4")
        elif kind.startswith("decode"):
            est = memwatch.estimate_decode_program(dims, geom, bucket, pb)
        elif kind == "prefill_chunk" and chunk:
            est = memwatch.estimate_prefill_program(dims, geom, chunk, pb,
                                                    chunked=True)
        elif kind == "prefill":
            # the captured prefill row is the LAST prompt length traced;
            # skip rows we cannot reconstruct the length for
            continue
        else:
            continue
        comp = row["temp"] + row["output"]
        pred = est["temp"] + est["output"]
        out.append({"model": sig, "kind": kind, "bucket": bucket,
                    "extra": extra, "estimate": est,
                    "compiled_temp_plus_output": comp,
                    "estimated_temp_plus_output": pred,
                    "rel_err": round(pred / comp - 1.0, 4) if comp else None})
    return out


def _run_train_step(cfg, model, rng):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.hapi import TrainStep

    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(logits, y):
        import paddle_tpu.nn.functional as F
        return F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), y.reshape([-1]))

    step = TrainStep(model, opt, loss_fn=loss_fn)
    ids = rng.integers(0, cfg.vocab_size, (2, 9))
    x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
    y = paddle.to_tensor(ids[:, 1:].astype(np.int32))
    step(x, y)
    step.sync()
    step.sync_to_model()


def cmd_bank(args) -> int:
    doc = capture_suite()
    text = json.dumps(doc, indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"banked {len(doc['rows'])} program rows -> {args.out}")
    else:
        print(text)
    return 0


def cmd_check(args) -> int:
    from paddle_tpu.observability import memory as memwatch

    with open(args.artifact) as f:
        banked = json.load(f)
    doc = capture_suite()
    findings = memwatch.compare_program_rows(
        banked["rows"], doc["rows"], tolerance=args.tol)
    grew = [f for f in findings if f["verdict"] == "grew"]
    info = [f for f in findings if f["verdict"] != "grew"]
    missing = [f for f in info if f["verdict"] == "missing"]
    matched = len(banked["rows"]) - len(missing)
    for f in grew:
        # growth is None when the banked value was 0 (0 -> anything is
        # flagged, but has no finite ratio)
        why = (f"{f['growth']:+.1%} > {args.tol:.0%} tolerance"
               if f["growth"] is not None else "banked 0 -> nonzero")
        print(f"GREW  {f['model']}:{f['kind']}/b{f['bucket']}"
              f"{('/' + f['extra']) if f['extra'] else ''} {f['section']}: "
              f"{f['banked']} -> {f['current']} ({why})")
    for f in info:
        print(f"note  {f['model']}:{f['kind']}/b{f['bucket']}"
              f"{('/' + f['extra']) if f['extra'] else ''}: {f['verdict']}")
    if not matched:
        # a gate that compares nothing must not pass: zero overlap means
        # the capture suite is no longer measuring what was banked
        # (capture failures, renamed kinds/model sigs, broken backend)
        print(f"memwatch gate FAILED: no banked program matched a "
              f"captured row ({len(banked['rows'])} banked, "
              f"{len(doc['rows'])} captured) — re-bank or fix capture")
        return 1
    if not grew:
        print(f"memwatch gate OK: {matched} programs within "
              f"{args.tol:.0%} of {args.artifact}")
    return 1 if grew else 0


def cmd_view(args) -> int:
    from paddle_tpu.observability import memory as memwatch

    with open(args.artifact) as f:
        doc = json.load(f)
    rows = doc.get("rows") or doc.get("memory", {}).get("programs") or []
    if not rows:
        raise SystemExit("no program rows in artifact")
    print(memwatch.format_program_table(rows))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("plan", help="what-if HBM fit planner")
    p.add_argument("--model", default="llama2_7b", choices=_MODELS)
    p.add_argument("--weight-dtype", default="bfloat16",
                   choices=("float32", "bfloat16", "int8", "int4"))
    p.add_argument("--kv-dtype", default="bfloat16",
                   choices=("bfloat16", "int8"))
    p.add_argument("--page-budget", type=int, default=None,
                   help="usable KV pages (default: worst-case formula)")
    p.add_argument("--page-size", type=int, default=64)
    p.add_argument("--rung", type=int, default=8,
                   help="decode batch bucket (ladder rung)")
    p.add_argument("--chunk", type=int, default=256)
    p.add_argument("--max-seq", type=int, default=2048)
    p.add_argument("--hbm-gb", type=float, default=16.0)
    p.add_argument("--host-tier-pages", type=int, default=0,
                   help="host-RAM KV tier pages "
                        "(FLAGS_serving_kv_host_tier_pages): priced "
                        "against host RAM, jointly with device HBM")
    p.add_argument("--host-ram-gb", type=float, default=0.0,
                   help="report host-tier headroom against this much "
                        "host RAM (0 = just report tier bytes)")
    p.add_argument("--draft", default=None, choices=_MODELS,
                   help="price speculative serving: this draft model's "
                        "weights + worst-case KV pool ride along, and "
                        "the (1, gamma+1) verify chunk joins the "
                        "workspace max")
    p.add_argument("--spec-gamma", type=int, default=4,
                   help="largest speculation rung to price the verify "
                        "chunk at (FLAGS_serving_spec_gamma)")
    p.add_argument("--draft-weight-dtype", default=None,
                   choices=("float32", "bfloat16", "int8", "int4"),
                   help="draft storage dtype (default: --weight-dtype)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree: price ONE SHARD of "
                        "the r19 sharded decode engine (sharded "
                        "stacked weights, kv-head-partitioned pool "
                        "incl. the int8 scale band, per-shard "
                        "workspaces); refuses indivisible degrees")
    p.add_argument("--fused-layers", type=int, default=1,
                   help="price the N-layer fused decode kernel's VMEM "
                        "working set (FLAGS_fused_block_layers=N); an "
                        "N that does not fit --vmem-mb is refused "
                        "(exit 1)")
    p.add_argument("--vmem-mb", type=float, default=16.0,
                   help="per-core VMEM budget for --fused-layers")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_plan)

    p = sub.add_parser("bank", help="capture + bank program memory rows")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=cmd_bank)

    p = sub.add_parser("check", help="regression gate vs banked artifact")
    p.add_argument("--artifact", default="MEMWATCH_r18.json")
    p.add_argument("--tol", type=float, default=0.10)
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("view", help="render a banked artifact")
    p.add_argument("artifact")
    p.set_defaults(fn=cmd_view)

    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS") != "tpu":
        # bank/check build against the local backend; default cpu (set
        # JAX_PLATFORMS=tpu to bank on-chip rows)
        toolenv.force_cpu()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
