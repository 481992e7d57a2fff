"""End-to-end metrics of one window, from the engine's request records.

Times are the engine's host clock, in seconds from the start of the serve
call (the window start).  A request counts if it arrived in the window; in a
closed loop, requests still queued when the window ended were never sent and
do not count.  A counted request that did not finish is ``failed`` and
misses every latency (it enters each percentile as infinitely late).
"""
from __future__ import annotations

import math

import numpy as np

MISSED = 1e9  # what a percentile reads when it falls on a failed request


def counted(report, mix: dict) -> list[int]:
    out = []
    for i, r in enumerate(report.records):
        never_sent = (mix["loop"] == "closed" and r.status == "shed"
                      and r.reason == "deadline")
        if not never_sent:
            out.append(i)
    return out


def finished(report, items, i: int) -> bool:
    r = report.records[i]
    return r.status == "done" and len(r.tokens) == items[i].max_new


def percentile(values: list[float], q: float) -> float:
    """Linear between closest ranks (numpy's default); ``MISSED`` where it
    touches a failed request."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        return MISSED
    pos = q / 100.0 * (len(v) - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(v[hi]):
        return MISSED
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def measure(report, items, mix: dict) -> dict:
    """``attempted``, ``failed`` and every end-to-end metric but setup_s."""
    idx = counted(report, mix)
    ok = {i for i in idx if finished(report, items, i)}
    recs = report.records
    inf = float("inf")
    ttft = [(recs[i].t_first - items[i].arrival) * 1e3 if i in ok else inf
            for i in idx]
    tpot = [(recs[i].t_done - recs[i].t_first) * 1e3 / (len(recs[i].tokens) - 1)
            if i in ok else inf
            for i in idx if items[i].max_new >= 2]
    tokens = sum(len(recs[i].tokens) for i in idx)
    end = max((recs[i].t_done for i in ok), default=0.0)
    out = {
        "attempted": len(idx),
        "failed": len(idx) - len(ok),
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p90_ms": percentile(tpot, 90),
        "output_tok_s": tokens / end if end > 0 else 0.0,
    }
    return out
