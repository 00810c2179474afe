"""The one general traffic generator.

A traffic mix is a data file of parameters. The work it offers is a
FIXED TABLE: the number of requests and a quantile grid of lengths are
set by the file (and, for an open loop, by the length of the window),
never drawn. ``--seed`` only permutes the table (the pairing of prompt
and answer lengths, and the order) and places the arrival times. So
every seed offers the same requests, the same prompt tokens and the same
output tokens; only their order and spacing differ. PR 22's draws made
the load differ from seed to seed by more than any change to the program
could, and that is what the driver refused.

A file that gives ``schedule_seed`` goes one step further: pairing, order
and due times come from that number and not from ``--seed``, so the cell
replays ONE recorded trace and ``--seed`` draws only the token ids (and
the weights). An open loop under the knee needs it: where the arrivals
cluster decides which rung of the engine's ladder the requests meet, and
a tail latency then moves with the seed by more than with the program
(PR 23: the driver's seeds spread ``itl_p90_ms`` by 2.7% where one seed
repeats to 0.3%).
"""

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int              # position in this seed's order
    prompt_len: int
    output_len: int
    due_s: float            # open loop: seconds after the window opens


def quantile_grid(spec: dict, n: int) -> List[int]:
    """``n`` lengths on the quantile grid of a clipped log-normal:
    ``levels`` distinct values (the quantiles at (i + 0.5) / levels,
    clipped to [lo, hi] and rounded to a multiple of ``multiple``), each
    taken by an equal share of the ``n`` requests. Few distinct lengths
    keep the set of compiled prefill shapes, and so the set-up, small."""
    levels = int(spec["levels"])
    lo, hi = int(spec["lo"]), int(spec["hi"])
    mult = int(spec.get("multiple", 1))
    norm = NormalDist()
    values = []
    for i in range(levels):
        z = norm.inv_cdf((i + 0.5) / levels)
        x = float(spec["median"]) * math.exp(float(spec["sigma"]) * z)
        x = min(max(x, lo), hi)
        values.append(int(min(max(round(x / mult) * mult, lo), hi)))
    return [values[j * levels // n] for j in range(n)]


def request_count(traffic: dict, seconds: float) -> int:
    if "requests" in traffic:
        return int(traffic["requests"])
    return max(1, int(round(float(traffic["rate_per_s"]) * seconds)))


def table(traffic: dict, seconds: float) -> List[tuple]:
    """The seed-independent table: (prompt_len, output_len) pairs in
    canonical order."""
    n = request_count(traffic, seconds)
    prompts = quantile_grid(traffic["prompt_len"], n)
    outputs = quantile_grid(traffic["output_len"], n)
    return list(zip(prompts, outputs))


def schedule(traffic: dict, seed: int, seconds: float) -> List[Request]:
    """This seed's requests: the table with its prompt lengths and its
    output lengths each permuted (so pairing and order change, totals do
    not), and, for an open loop, due times that are the sorted uniforms
    of a Poisson process given its count. The file's ``schedule_seed``,
    if it gives one, takes the seed's place here: one trace for every
    seed."""
    rows = table(traffic, seconds)
    rng = np.random.default_rng(int(traffic.get("schedule_seed", seed)))
    n = len(rows)
    p_order = rng.permutation(n)
    o_order = rng.permutation(n)
    prompts = [rows[i][0] for i in p_order]
    outputs = [rows[i][1] for i in o_order]
    if traffic["loop"] == "open":
        due = np.sort(rng.uniform(0.0, float(seconds), n)).tolist()
    else:
        due = [0.0] * n
    return [Request(i, int(p), int(o), float(d))
            for i, (p, o, d) in enumerate(zip(prompts, outputs, due))]


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> np.ndarray:
    """Token ids of one prompt, from the seed and its place in the
    order."""
    rng = np.random.default_rng([int(seed), 7, int(index)])
    return rng.integers(0, vocab, (length,), dtype=np.int64).astype(np.int32)
