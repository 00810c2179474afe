"""From a profiler trace (``*.xplane.pb``) to numbers.

What a TPU trace holds (looked at by hand, PR 23): a plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` carries one event per
executed HLO instruction, one after another on the core, named by the
instruction's full HLO text (``%copy.1 = bf16[16,513,64,64]{...}
copy(...)``); and a plane ``/host:CPU`` whose thread lines carry the
``jax.profiler.TraceAnnotation`` spans by their plain names. Device and
host events are on one clock to within about half a millisecond.

The reduction works on plain tuples, so that the tests can feed it
hand-made events as well as a recorded trace:

    device_ops  {device_name: [(hlo_text, start_ns, dur_ns), ...]}
    host_spans  [(name, start_ns, dur_ns), ...]
"""

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
# host spans the gaps are attributed to: the benchmark's own, and the
# program's where they reach the trace
SPAN_PREFIXES = ("bench.", "request.", "engine.", "train.")
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all", "collective-broadcast")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


# ------------------------------------------------------------- reading
def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str):
    """(device_ops, host_spans) of one recorded trace, by JAX's own
    reader."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, list] = {}
    host_spans: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        host_spans.append((e.name, float(e.start_ns),
                                           float(e.duration_ns)))
    return device_ops, host_spans


# ------------------------------------------------------------ HLO names
def parse_hlo(text: str) -> Tuple[str, str, str]:
    """(instruction name, output shape, opcode) of an event name that is
    an HLO instruction's text; anything else comes back as its own
    name with no shape and no opcode."""
    if not text.startswith("%") or " = " not in text:
        return text, "", ""
    inst, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):                 # a tuple of shapes
        depth = 0
        end = len(rest) - 1
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i
                break
        shape, tail = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    return inst, shape, tail.split("(", 1)[0]


_LAYOUT = re.compile(r"\{[^}]*\}")
_ARRAY = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def first_array(shape: str) -> str:
    """``bf16[16,513,64,64]`` out of a shape with layouts or a tuple."""
    m = _ARRAY.search(_LAYOUT.sub("", shape))
    return m.group(0) if m else ""


@functools.lru_cache(maxsize=65536)     # a step's few thousand texts repeat
def op_label(text: str) -> str:
    """A name that survives renumbering: the instruction's name without
    its ``.N`` suffix, and its (first) output array."""
    inst, shape, op = parse_hlo(text)
    base = re.sub(r"[.\d]+$", "", inst) or inst
    label = f"{base} {first_array(shape)}".strip()
    return label[:120]


def operand_arrays(text: str) -> List[str]:
    """The operand arrays of an instruction, in order."""
    _, _, op = parse_hlo(text)
    if not op:
        return []
    args = text.split(f" {op}(", 1)[-1]
    depth = 1
    for i, ch in enumerate(args):          # up to the call's own ")"
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            args = args[:i]
            break
    return _ARRAY.findall(_LAYOUT.sub("", args))


def array_dims(array: str) -> List[int]:
    inner = array[array.index("[") + 1:-1]
    return [int(d) for d in inner.split(",") if d]


def is_collective(text: str) -> bool:
    return parse_hlo(text)[2].startswith(COLLECTIVE_OPS)


def is_pallas(text: str) -> bool:
    return PALLAS_TARGET in text


# ------------------------------------------------------------ intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _spans_of(ops) -> List[Interval]:
    return [(s, s + d) for _, s, d in ops]


# ------------------------------------------------------------ reduction
def busy_seconds(device_ops) -> Optional[float]:
    """Seconds in which an operation ran on the device: the union of the
    op intervals, averaged over the devices the trace holds."""
    if not device_ops:
        return None
    per = [length(union(_spans_of(ops))) for ops in device_ops.values()]
    return sum(per) / len(per) / 1e9


def op_seconds(device_ops) -> Dict[str, float]:
    """Device seconds by op label, averaged over the devices."""
    total: Dict[str, float] = {}
    for ops in device_ops.values():
        for text, _, dur in ops:
            label = op_label(text)
            total[label] = total.get(label, 0.0) + dur
    n = max(len(device_ops), 1)
    return {k: v / n / 1e9 for k, v in total.items()}


def idle_gaps(device_ops, host_spans, min_gap_ns: float = 2e4
              ) -> Dict[str, float]:
    """Idle seconds of the first device between its first and last op,
    by what the host was doing: each gap goes to the shortest host span
    that holds its middle, or to ``unlabelled``. Gaps under
    ``min_gap_ns`` (bubbles between back-to-back ops, which no host
    span explains) are lumped under ``short_gaps``."""
    if not device_ops:
        return {}
    ops = device_ops[sorted(device_ops)[0]]
    busy = union(_spans_of(ops))
    out: Dict[str, float] = {}
    spans = sorted(host_spans, key=lambda s: s[2])       # shortest first
    for (_, end), (start, _) in zip(busy, busy[1:]):
        gap = start - end
        if gap < min_gap_ns:
            name = "short_gaps"
        else:
            mid = end + gap / 2.0
            name = next((n for n, s, d in spans if s <= mid <= s + d),
                        "unlabelled")
        out[name] = out.get(name, 0.0) + gap / 1e9
    return out


def collective_exposed_seconds(device_ops) -> Optional[float]:
    """Collective time during which no other op runs on that device,
    averaged over the devices."""
    if not device_ops:
        return None
    per = []
    for ops in device_ops.values():
        coll = union(_spans_of([o for o in ops if is_collective(o[0])]))
        rest = union(_spans_of([o for o in ops if not is_collective(o[0])]))
        per.append(length(subtract(coll, rest)))
    return sum(per) / len(per) / 1e9


def pallas_seconds(device_ops, want) -> float:
    """Device seconds of the Pallas custom calls whose operand arrays
    ``want(list_of_dims)`` accepts, averaged over the devices."""
    total = 0.0
    for ops in device_ops.values():
        for text, _, dur in ops:
            if is_pallas(text) and want(
                    [array_dims(a) for a in operand_arrays(text)]):
                total += dur
    return total / max(len(device_ops), 1) / 1e9


def top(table: Dict[str, float], n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]
