#!/usr/bin/env python3
"""Run-to-run spread of each metric over a set of result lines.

    python benchmark/spreads.py <file with result lines or one per file> ...

Reads the last JSON line of each file (what ``run.py`` printed), groups
by workload, and prints for every metric its values, median and spread:
the distance between the first and third quartile as a share of the
median, the figure a bound is set from (about five times the widest
spread over the cells, never under 1%). Not run by the driver.
"""

import json
import statistics
import sys

from lib.stats import spread


def main(paths) -> int:
    by_cell = {}
    for path in paths:
        with open(path) as fh:
            lines = [ln for ln in fh if ln.startswith("{")]
        if not lines:
            print(f"{path}: no result line", file=sys.stderr)
            continue
        line = json.loads(lines[-1])
        cell = by_cell.setdefault(line.get("workload", "?"), {})
        for name, m in line["metrics"].items():
            cell.setdefault(name, []).append(m["value"])
    for workload, metrics in sorted(by_cell.items()):
        for name, values in sorted(metrics.items()):
            row = dict(workload=workload, metric=name, n=len(values),
                       median=statistics.median(values),
                       min=min(values), max=max(values))
            if len(values) >= 3 and row["median"]:
                row["spread"] = spread(values)
            print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
