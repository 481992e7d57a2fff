"""The one traffic generator: a mix file of parameters in, a request schedule out.

A mix (``bench/traffic/<name>.json``) states:

- ``loop``: ``"open"`` (Poisson arrivals at ``rate_rps`` over the window) or
  ``"closed"`` (``clients`` clients that each send the next request as soon
  as the previous one is answered, until the window ends);
- ``prompt``: a lognormal length (``median``, ``sigma``) rounded up to the
  next length of ``grid``; lengths past the grid's last entry take that entry;
- ``output``: a lognormal answer length (``median``, ``sigma``), rounded and
  clipped to ``[min, max]``;
- ``max_seq``: the engine's longest prompt plus answer.

Every seed gets the same set of lengths and inter-arrival gaps (the
quantiles of the stated distributions) in an order of its own, drawn from
the seed, and its own token ids, uniform over the vocabulary (and, in
``bench/weights.py``, its own weights).  So every run does the same amount
of work, and no one arrival pattern is the one measured.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

@dataclass(frozen=True)
class Item:
    arrival: float  # seconds from window start
    prompt_len: int
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    """Mid-point probabilities (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / n


def _lognormal(n: int, median: float, sigma: float) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    return np.exp(math.log(median) + sigma * z)


def round_to_grid(lengths: np.ndarray, grid) -> np.ndarray:
    grid = np.asarray(sorted(grid))
    idx = np.minimum(np.searchsorted(grid, np.ceil(lengths), side="left"),
                     len(grid) - 1)
    return grid[idx]


def prompt_lengths(mix: dict, n: int) -> np.ndarray:
    p = mix["prompt"]
    return round_to_grid(_lognormal(n, p["median"], p["sigma"]), p["grid"])


def output_lengths(mix: dict, n: int) -> np.ndarray:
    o = mix["output"]
    raw = np.rint(_lognormal(n, o["median"], o["sigma"]))
    return np.clip(raw, o["min"], o["max"]).astype(int)


def request_count(mix: dict, seconds: float) -> int:
    """Open loop: the expected arrivals in the window.  Closed loop: enough
    that no client runs dry before the window ends (each request takes at
    least ``min_request_s``)."""
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_rps"] * seconds)))
    per_client = math.ceil(seconds / mix["min_request_s"]) + 1
    return mix["clients"] * per_client


def schedule(mix: dict, seconds: float, seed: int) -> list[Item]:
    """The window's requests in arrival order, in the seed's order."""
    n = request_count(mix, seconds)
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 3])
    plen = rng.permutation(prompt_lengths(mix, n))
    outs = rng.permutation(output_lengths(mix, n))
    if mix["loop"] == "open":
        # Exponential quantiles, scaled so the n arrivals fill the window.
        gaps = -np.log1p(-_quantiles(n))
        gaps = rng.permutation(gaps) * (seconds * n / (n + 1) / gaps.sum())
        arrivals = np.cumsum(gaps)
    elif mix["loop"] == "closed":
        arrivals = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return [Item(float(a), int(p), int(o))
            for a, p, o in zip(arrivals, plen, outs)]


def prompts(items: list[Item], seed: int, vocab: int) -> list[np.ndarray]:
    """Uniform token ids for each request, from the seed."""
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 1])
    return [rng.integers(0, vocab, size=it.prompt_len, dtype=np.int32)
            for it in items]


def grid_used(items: list[Item]) -> list[int]:
    """The prompt lengths the window will admit: one admit program each."""
    return sorted({it.prompt_len for it in items})
