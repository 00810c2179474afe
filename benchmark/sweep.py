#!/usr/bin/env python3
"""Find an open-loop cell's knee ONCE, on the chip, in one process.

    python benchmark/sweep.py --workload <open-loop cell> --rates 2,2.5,3,3.5,4 --seconds 30

Not run by the driver. It builds and warms the cell's system once, then
offers the cell's table at each rate in turn (draining the engine in
between) and prints one JSON line per rate: whether the backlog grew
(its mean over the window's last quarter against its second quarter),
the time to first token by thirds of the window, and the tail of the
token gaps. The knee is the highest rate at which the backlog does not
grow; the cell's traffic file then fixes its rate at four fifths of it,
as a number.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402


def mean(xs):
    return sum(xs) / len(xs) if xs else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    _, cell, config, traffic = bench_run.load_cell(args.workload)
    if traffic["loop"] != "open":
        raise SystemExit("only an open loop has a knee to find")
    bench_run.require_chips(cell["chips"])
    from benchmark.lib import stats
    system, loop = bench_run.build_system(cell, config, traffic, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        system.traffic = dict(traffic, rate_per_s=rate, sample_share=1.0)
        out = loop(system, args.seed, args.seconds, False)
        system.engine.run()             # drain before the next rate
        system.engine.take_results()
        ttft = out.series["ttft_ms"]
        third = max(1, len(ttft) // 3)
        backlog = out.series["backlog"]
        q = max(1, len(backlog) // 4)
        print(json.dumps(dict(
            rate_per_s=rate, offered=out.notes["requests_offered"],
            first_token_in_window=len(ttft), failed=out.failed,
            backlog_q2=mean(backlog[q:2 * q]), backlog_q4=mean(backlog[3 * q:]),
            backlog_end=backlog[-1] if backlog else None,
            ttft_p50_by_third=[stats.percentile(ttft[i * third:(i + 1) * third],
                                                50) for i in range(3)],
            ttft_p50_ms=stats.percentile(ttft, 50),
            ttft_p90_ms=stats.percentile(ttft, 90),
            itl_p50_ms=stats.percentile(out.series["itl_ms"], 50),
            itl_p90_ms=stats.percentile(out.series["itl_ms"], 90),
            run_step_p50_ms=stats.percentile(out.series["run_step_ms"], 50),
            tok_s=(out.scalars["prompt_tokens_done"]
                   + out.scalars["output_tokens"]) / out.scalars["window_s"],
            correct=out.correct)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
