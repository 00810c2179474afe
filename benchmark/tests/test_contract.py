"""BENCHMARK.json against the harness's own files, and run.py's refusal
to measure without a chip."""

import glob
import json
import os
import re
import subprocess
import sys

from benchmark.lib import registry

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _load(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = _load(ROOT, "BENCHMARK.json")["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()           # no result line, no CPU number


def test_every_name_resolves_to_files_and_registered_kinds():
    registry.load_all()
    bench = _load(ROOT, "BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        data = _load(ROOT, c["file"])
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert data["arch"] in registry.ARCHS
        assert os.path.exists(os.path.join(HERE, "refs", c["name"] + ".py"))
    for w in bench["workloads"]:
        assert w["config"] in configs and len(w["why"]) <= 200
        t = _load(HERE, "traffic", w["traffic"] + ".json")
        assert t["loop"] in registry.LOOPS
        builder = _load(ROOT, configs[w["config"]]["file"])["builders"]
        assert builder[t["loop"]] in registry.BUILDERS
    for section, folder in (("end_to_end", "end_to_end"),
                            ("per_layer", "layer_metrics")):
        for m in bench[section]:
            assert NAME.match(m["name"]) and " " not in m["unit"]
            spec = _load(HERE, folder, m["name"] + ".json")
            assert spec["reader"] in registry.READERS
            where = set(m.get("workloads", cells))
            assert where <= cells
            # which cells report a metric is BENCHMARK.json's to say: a
            # later PR adds a cell without editing the metric's file
            assert "cells" not in spec
            if section == "per_layer":
                moved = e2e[m["moves"]]
                assert where <= set(moved.get("workloads", cells))
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in bench["per_layer"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def test_benchmark_json_keeps_to_the_contracts_limits():
    bench = _load(ROOT, "BENCHMARK.json")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = re.compile(r"^[^\t\n]{1,200}$")
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert all(line.match(w) for w in bench["command"])
    assert bench["paths"] == ["benchmark"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line.match(c["source"])
        assert line.match(c["why"]) and c["file"].startswith("benchmark/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line.match(w["why"])
    sources = {"device_trace", "program_span", "program_counter",
               "host_clock"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in sources and line.match(m["layer"])
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as fh:
        assert len(fh.read()) <= 64 * 1024


def test_no_metric_file_without_an_entry():
    bench = _load(ROOT, "BENCHMARK.json")
    for section, folder in (("end_to_end", "end_to_end"),
                            ("per_layer", "layer_metrics")):
        names = {m["name"] for m in bench[section]}
        files = {os.path.splitext(os.path.basename(p))[0]
                 for p in glob.glob(os.path.join(HERE, folder, "*.json"))}
        assert files == names
