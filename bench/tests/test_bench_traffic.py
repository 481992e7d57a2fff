"""The traffic generator: deterministic per seed, on the cell's grid, and the
same lengths and gaps for every seed in an order of the seed's own."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402

MIXES = sorted((ROOT / "bench" / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


def _mix(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_inputs_are_deterministic_per_seed(path):
    mix = _mix(path)
    a = traffic.schedule(mix, 30.0, BIG_SEED)
    assert a == traffic.schedule(mix, 30.0, BIG_SEED)
    assert a != traffic.schedule(mix, 30.0, BIG_SEED + 1)
    pa = traffic.prompts(a, BIG_SEED, 1000)
    pb = traffic.prompts(a, BIG_SEED, 1000)
    assert all((x == y).all() for x, y in zip(pa, pb))
    pc = traffic.prompts(a, BIG_SEED + 1, 1000)
    assert any((x != y).any() for x, y in zip(pa, pc))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_schedule_stays_on_grid_and_fits_max_seq(path):
    mix = _mix(path)
    items = traffic.schedule(mix, 30.0, BIG_SEED)
    grid = set(mix["prompt"]["grid"])
    assert {it.prompt_len for it in items} <= grid
    o = mix["output"]
    assert all(o["min"] <= it.max_new <= o["max"] for it in items)
    assert all(it.prompt_len + it.max_new <= mix["max_seq"] for it in items)
    arrivals = [it.arrival for it in items]
    assert arrivals == sorted(arrivals)
    assert all(0.0 <= a < 30.0 for a in arrivals)
    assert all(len(p) == it.prompt_len
               for p, it in zip(traffic.prompts(items, 7, 50), items))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_schedule_holds_the_quantiles_in_a_shuffled_order(path):
    mix = _mix(path)
    a = traffic.schedule(mix, 30.0, BIG_SEED)
    b = traffic.schedule(mix, 30.0, 7)
    n = len(a)
    assert a != b  # each seed has an order of its own ...
    for key in ("prompt_len", "max_new"):  # ... of the same lengths
        assert Counter(getattr(it, key) for it in a) == Counter(
            getattr(it, key) for it in b)
    if mix["loop"] == "open":  # ... and the same gaps between arrivals
        gaps = [np.diff([0.0] + [it.arrival for it in x]) for x in (a, b)]
        np.testing.assert_allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert Counter(it.prompt_len for it in a) == Counter(
        traffic.prompt_lengths(mix, n).tolist())
    assert Counter(it.max_new for it in a) == Counter(
        traffic.output_lengths(mix, n).tolist())
    assert [it.max_new for it in a] != sorted(it.max_new for it in a)
    if mix["loop"] == "open":
        assert n == round(mix["rate_rps"] * 30.0)
        gaps = [y.arrival - x.arrival for x, y in zip(a, a[1:])]
        assert gaps != sorted(gaps)


def test_lengths_follow_the_stated_lognormal():
    mix = {"prompt": {"median": 320, "sigma": 1.0,
                      "grid": [128, 256, 512, 1024, 2048]},
           "output": {"median": 192, "sigma": 0.8, "min": 16, "max": 1024}}
    p = traffic.prompt_lengths(mix, 1000)
    o = traffic.output_lengths(mix, 1000)
    assert sorted(p)[500] == 512  # the median 320 rounds up to 512
    assert 185 <= sorted(o)[500] <= 200
    assert min(o) == 16 and max(o) == 1024
    # P(length > 1024) = P(Z > ln(3.2)) = 12.2% of prompts take 2048
    assert 110 <= sum(p == 2048) <= 135


def test_round_to_grid():
    import numpy as np

    got = traffic.round_to_grid(np.array([1, 128, 129, 5000.5]), [128, 256])
    assert got.tolist() == [128, 128, 256, 256]
