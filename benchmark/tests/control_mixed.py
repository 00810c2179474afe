#!/usr/bin/env python3
"""The controls of ``trinity-mini.serve-mixed``'s ``correct``, on the
chip at the cell's sizes and the cell's own sample:

    python3 benchmark/tests/control_mixed.py --seed <n>

Builds the cell's system as ``benchmark/run.py`` does, runs its set-up
(``afmoe.warm_up``) and prints one JSON line with three readings, all
through the cell's own comparison (``afmoe.compare`` under the
reference's ``TIE_ATOL`` / ``TIE_RTOL``): the ENGINE's greedy tokens of
the four checked prompts, which must read ``correct: true``; the plain
reference's own choice with both operands of every weight matmul rounded
to ``float8_e4m3fn``, the nearest precision below the configuration's
bfloat16; and its choice with the window left off every layer. Both
controls must read ``correct: false``: the first says the limit tells a
precision from the one below it, the second that the comparison sees
the window at all. Exit code 1 where any of the three fails. PERF.md
(sections 4 and 6) has the readings the limit was set between.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))       # benchmark/: run.py
import run  # noqa: E402

CELL = "trinity-mini.serve-mixed"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    _, cell, config, traffic = run.load_cell(CELL)
    run.require_chips(cell["chips"])

    import jax.numpy as jnp
    from benchmark.lib import afmoe
    from benchmark.lib import traffic as traffic_lib
    system, _ = run.build_system(cell, config, traffic, args.seed)
    requests = traffic_lib.schedule(traffic, args.seed, 50.0)
    line = dict(seed=args.seed, **afmoe.control_readings(
        system, requests, jnp.float8_e4m3fn))
    print(json.dumps(line), flush=True)
    return int(not line["engine"]["correct"]
               or line["low_precision"]["correct"]
               or line["window_off"]["correct"])


if __name__ == "__main__":
    sys.exit(main())
