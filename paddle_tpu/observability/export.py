"""Exporter over the registry snapshot: :func:`to_prometheus` renders
the JSON-able snapshot dict (an embedded ``BENCH_*.json`` telemetry blob
or the live registry, identically) as Prometheus text exposition format
(cumulative ``_bucket{le=...}`` histogram encoding). The span ring
exports itself: ``tracer().chrome_trace()`` / ``tracer().save(path)``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from .metrics import registry

__all__ = ["to_prometheus"]


def _fmt_labels(labels: Dict[str, str], extra=()) -> str:
    pairs = [(k, str(v)) for k, v in sorted(labels.items())] + list(extra)
    if not pairs:
        return ""
    body = ",".join(f'{k}="{_escape(v)}"' for k, v in pairs)
    return "{" + body + "}"


def _escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_val(v: float) -> str:
    if v != v:                                  # NaN
        return "NaN"
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    return repr(float(v)) if v != int(v) else str(int(v))


def to_prometheus(snapshot: Optional[Dict[str, Any]] = None) -> str:
    """Render a registry snapshot (default: the live process registry)
    as Prometheus text exposition format."""
    if snapshot is None:
        snapshot = registry().snapshot()
    lines = []
    for name, fam in sorted(snapshot.get("metrics", {}).items()):
        if fam.get("help"):
            lines.append(f"# HELP {name} {fam['help']}")
        lines.append(f"# TYPE {name} {fam['type']}")
        for s in fam.get("series", []):
            labels = s.get("labels", {})
            if fam["type"] == "histogram":
                cum = 0
                for upper, c in zip(s["buckets"], s["counts"]):
                    cum += c
                    lines.append(
                        f"{name}_bucket"
                        f"{_fmt_labels(labels, [('le', _fmt_val(upper))])}"
                        f" {cum}")
                cum += s["counts"][len(s["buckets"])]
                lines.append(
                    f"{name}_bucket{_fmt_labels(labels, [('le', '+Inf')])}"
                    f" {cum}")
                lines.append(
                    f"{name}_sum{_fmt_labels(labels)} {_fmt_val(s['sum'])}")
                lines.append(
                    f"{name}_count{_fmt_labels(labels)} {s['count']}")
            else:
                lines.append(
                    f"{name}{_fmt_labels(labels)} {_fmt_val(s['value'])}")
    return "\n".join(lines) + ("\n" if lines else "")
