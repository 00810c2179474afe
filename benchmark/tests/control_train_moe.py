#!/usr/bin/env python3
"""The controls of ``mellum2-12b-a2.5b-instruct.train-8k``'s ``correct``,
on the chip at the cell's sizes and on the cell's own first batch:

    python3 benchmark/tests/control_train_moe.py --seed <n> [--seed <m> ...]

For each seed, the cell's weights and first batch as its builder makes
them (``benchmark/lib/mellum.py``), and two readings through the cell's
own limits (``verdict`` under the reference's ``LOSS_ATOL`` /
``GRAD_RTOL``), each with a variant of the plain reference in the
program's place: both operands of every weight matmul rounded to
``float8_e4m3fn`` under a per-tensor amax scale, the nearest precision
below the configuration's bfloat16; and every layer full attention (the
window left off). Both must read ``correct: false``: the first says the
limits tell a precision from the one below it, the second that the
comparison sees the window. The update's limit (``UPDATE_RTOL``) has no
control to run: a variant in the program's place has no optimizer, and
a state left unchanged reads 1 by its definition. One JSON line a seed;
exit code 1 where a control passes. The PROGRAM's reading is the cell's
own run (its line's ``notes``). PERF.md section 6 (PR 38) has the
readings the limits were set between.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))       # benchmark/: run.py
import run  # noqa: E402

CELL = "mellum2-12b-a2.5b-instruct.train-8k"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    _, cell, config, traffic = run.load_cell(CELL)
    run.require_chips(cell["chips"])

    import jax.numpy as jnp
    import numpy as np
    from benchmark.lib import mellum, system

    ref = system.load_reference(config["name"])
    passed = 0
    for seed in args.seed:
        _, model = system.lazy_model(config)
        weights = system.make_weights(model, seed)
        # the builder's first batch: its iterator's first draw
        rng = np.random.default_rng([seed, 11])
        ids = rng.integers(0, config["model"]["vocab_size"],
                           (traffic["batch"], traffic["seq_len"] + 1),
                           dtype=np.int64).astype(np.int32)
        line = dict(seed=seed)
        ref_loss, ref_grads = mellum.reference_gradients(
            ref, weights, ids, config["model"])
        for name, kw in (("low_precision",
                          dict(matmul_dtype=jnp.float8_e4m3fn)),
                         ("window_off", dict(all_full=True))):
            loss, grads = mellum.reference_gradients(
                ref, weights, ids, config["model"], **kw)
            errors = mellum.gradient_errors(ref, grads, ref_grads)
            del grads
            line[name] = dict(loss=loss, reference_loss=ref_loss,
                              grad_errors=errors, **mellum.verdict(
                                  ref, loss, ref_loss, errors))
            passed += line[name]["correct"]
        del weights
        print(json.dumps(line), flush=True)
    return int(passed > 0)


if __name__ == "__main__":
    sys.exit(main())
