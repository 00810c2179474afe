"""Every traffic file offers the same work for every seed."""

import glob
import json
import os

import numpy as np
import pytest

from benchmark.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FILES = sorted(glob.glob(os.path.join(HERE, "traffic", "*.json")))
SEEDS = [(0, 1), (7, 2**31 + 11), (123456789, 3000000000)]


def _load(path):
    with open(path) as fh:
        return json.load(fh)


def _serving(path):
    return _load(path)["loop"] in ("open", "closed")


@pytest.mark.parametrize("path", [p for p in FILES if _serving(p)],
                         ids=os.path.basename)
@pytest.mark.parametrize("seeds", SEEDS, ids=str)
def test_same_requests_for_every_seed(path, seeds):
    t = _load(path)
    seconds = 50.0
    a, b = (traffic.schedule(t, s, seconds) for s in seeds)
    assert len(a) == len(b) == traffic.request_count(t, seconds)
    assert sum(r.prompt_len for r in a) == sum(r.prompt_len for r in b)
    assert sum(r.output_len for r in a) == sum(r.output_len for r in b)
    # the same multiset of prompt lengths and of output lengths
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.output_len for r in a) == sorted(r.output_len for r in b)
    if t["loop"] == "open":
        for sched in (a, b):
            due = [r.due_s for r in sched]
            assert due == sorted(due) and 0.0 <= due[0] and due[-1] < seconds
    if "schedule_seed" in t:
        # one trace for every seed: only the token ids differ
        assert a == b
        assert (traffic.prompt_tokens(seeds[0], 0, 64, 50304)
                != traffic.prompt_tokens(seeds[1], 0, 64, 50304)).any()
        return
    # only order (and pairing) and due times differ
    assert [(r.prompt_len, r.output_len) for r in a] != \
        [(r.prompt_len, r.output_len) for r in b]
    if t["loop"] == "open":
        assert [r.due_s for r in a] != [r.due_s for r in b]


@pytest.mark.parametrize("path", [p for p in FILES if _serving(p)],
                         ids=os.path.basename)
def test_same_seed_same_schedule_and_prompts(path):
    t = _load(path)
    big = 2**31 + 12345
    assert traffic.schedule(t, big, 20.0) == traffic.schedule(t, big, 20.0)
    p = traffic.prompt_tokens(big, 3, 64, 50304)
    assert p.dtype == np.int32 and p.shape == (64,)
    assert (p == traffic.prompt_tokens(big, 3, 64, 50304)).all()
    assert (p != traffic.prompt_tokens(big, 4, 64, 50304)).any()
    assert 0 <= p.min() and p.max() < 50304


def test_schedule_seed_takes_the_seeds_place():
    """A file's schedule_seed gives the schedule that --seed of the same
    number gave without it, for any --seed; without it the seed decides."""
    t = dict(loop="open", rate_per_s=2.0,
             prompt_len=dict(median=128, sigma=0.8, lo=16, hi=768,
                             levels=12, multiple=8),
             output_len=dict(median=48, sigma=0.7, lo=8, hi=256, levels=12))
    fixed = dict(t, schedule_seed=303)
    assert traffic.schedule(fixed, 5, 30.0) == traffic.schedule(t, 303, 30.0)
    assert traffic.schedule(fixed, 2**31 + 9, 30.0) == \
        traffic.schedule(fixed, 5, 30.0)
    assert traffic.schedule(t, 5, 30.0) != traffic.schedule(t, 303, 30.0)


def test_quantile_grid_is_fixed_and_clipped():
    spec = dict(median=128, sigma=0.8, lo=16, hi=768, levels=12, multiple=8)
    grid = traffic.quantile_grid(spec, 120)
    assert len(grid) == 120 and grid == sorted(grid)
    assert len(set(grid)) <= 12 and min(grid) >= 16 and max(grid) <= 768
    assert all(g % 8 == 0 for g in grid)
    # every level is taken by an equal share of the requests
    assert {grid.count(v) for v in set(grid)} == {10}
    # the middle of the grid is the median
    assert grid[59] <= 128 <= grid[60] + 8


def test_every_cell_fits_its_engine_and_its_model():
    """No operation may fail: every request of every serving cell fits
    the engine's max_seq_len, and every train cell its position table."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for cell in bench["workloads"]:
        t = _load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
        c = _load(os.path.join(HERE, "configs", cell["config"] + ".json"))
        if t["loop"] == "train":
            assert t["seq_len"] <= c["model"]["max_position_embeddings"]
            if "mesh" in t:
                assert int(np.prod(list(t["mesh"].values()))) == cell["chips"]
            continue
        rows = traffic.table(t, bench["run_seconds"])
        assert max(p + o for p, o in rows) <= c["serve"]["max_seq_len"]
        assert t["loop"] in c["builders"]
