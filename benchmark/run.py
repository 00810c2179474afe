#!/usr/bin/env python3
"""Run ONE cell of the benchmark ONCE.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds`` and prints, as the last line
of its standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``. No TPU, or fewer chips than the cell asks for: a
non-zero exit and no result, never a CPU number under a device metric's
name.

Nothing here names a cell, a configuration or a metric. ``--workload``
is looked up in ``BENCHMARK.json``; its configuration, traffic, plain
reference and metric readers are found by name under ``benchmark/``.
"""

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# with --trace 1 the window is this long at most: a trace of a whole
# run is hundreds of megabytes, and the per-layer numbers carry no bound
TRACE_SECONDS = 10.0


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def use_compile_cache():
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at a fixed path inside the checkout (the path is part of
    the cache's key). Before jax is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "xla"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def metric_file(kind_dir, name):
    """{reader, params} of one metric, or None where it has no file."""
    path = os.path.join(HERE, kind_dir, name + ".json")
    return load_json(path) if os.path.exists(path) else None


def declared(bench, section, cell_name):
    """The metrics of ``section`` that BENCHMARK.json declares for this
    cell, name -> entry."""
    return {m["name"]: m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])}


def load_cell(name):
    """(BENCHMARK.json, the cell's entry, its configuration, its
    traffic), all found by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    return (bench, cell,
            load_json(HERE, "configs", cell["config"] + ".json"),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def require_chips(chips):
    """JAX's devices, or a non-zero exit where they are not the TPU
    chips the cell asks for. Sets the compile cache up first."""
    use_compile_cache()
    sys.path.insert(0, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices


def build_system(cell, config, traffic, seed):
    """(the system under test, the loop that drives it), by the names
    the data files give."""
    from benchmark.lib import registry
    registry.load_all()
    build = registry.lookup(registry.BUILDERS,
                            config["builders"][traffic["loop"]], "builder")
    loop = registry.lookup(registry.LOOPS, traffic["loop"], "loop")
    return build(config, traffic, seed, cell["chips"]), loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload)
    traced = bool(args.trace)
    seconds = min(args.seconds, TRACE_SECONDS) if traced else args.seconds
    devices = require_chips(cell["chips"])

    from benchmark.lib import registry, stats, trace
    from benchmark.lib.peaks import peaks
    chip_peaks = peaks(devices[0].device_kind)     # unknown kind: an error
    system, loop = build_system(cell, config, traffic, args.seed)
    out = loop(system, args.seed, seconds, traced)

    used = system.devices
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in used)
    window = out.window
    busy_s = trace.busy_seconds(window.device_ops) if traced else None
    notes = dict(out.notes)
    ctx = dict(series=out.series,
               scalars=dict(out.scalars, setup_s=window.t_open - T_START,
                            memory_peak_bytes=float(peak_bytes)),
               device_ops=window.device_ops, host_spans=window.host_spans,
               busy_s=busy_s, sizes=system.sizes, peaks=chip_peaks,
               chips=cell["chips"], traffic=traffic, config=config,
               notes=notes)

    section, folder = (("per_layer", "layer_metrics") if traced
                       else ("end_to_end", "end_to_end"))
    metrics = {}
    for name, entry in declared(bench, section, cell["name"]).items():
        spec = metric_file(folder, name)
        if spec is None:
            continue
        read = registry.lookup(registry.READERS, spec["reader"], "reader")
        value = read(ctx, **spec.get("params", {}))
        if value is not None:
            metrics[name] = {"value": float(value), "unit": entry["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak_bytes)}
    line = {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = busy_s
        device["window_s"] = window.seconds
        line["breakdown"] = {
            "device_ops": trace.top(trace.op_seconds(window.device_ops)),
            "idle_gaps": trace.top(trace.idle_gaps(window.device_ops,
                                                   window.host_spans))}
    line["workload"] = cell["name"]
    line["seed"] = args.seed
    line["window_s"] = window.seconds
    line["counters"] = dict(out.scalars)
    # every series of the window in brief, for whoever reads the line by
    # hand (the driver ignores it): readings, median, 90th percentile
    line["series"] = {k: [len(v), stats.percentile(v, 50),
                          stats.percentile(v, 90)]
                      for k, v in out.series.items() if v}
    line["notes"] = notes
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
