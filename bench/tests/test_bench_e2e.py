"""End-to-end metrics from request records: what counts, what fails, and
how a failed request enters a tail."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import e2e  # noqa: E402
from bench.traffic import Item  # noqa: E402


def rec(status="done", n=5, t_first=1.0, t_done=2.0, reason=""):
    return NS(status=status, reason=reason, tokens=[0] * n, t_first=t_first,
              t_done=t_done)


def test_latencies_and_rate_of_a_clean_window():
    items = [Item(0.0, 8, 5), Item(1.0, 8, 5), Item(2.0, 8, 1)]
    report = NS(records=[rec(t_first=0.5, t_done=1.3),
                         rec(t_first=1.25, t_done=2.25),
                         rec(n=1, t_first=2.5, t_done=2.5)])
    m = e2e.measure(report, items, {"loop": "open"})
    assert m["attempted"] == 3 and m["failed"] == 0
    assert m["ttft_p50_ms"] == pytest.approx(500.0)
    # one-token answers have no gap between tokens
    assert m["tpot_p90_ms"] == pytest.approx(200 + 0.9 * (250 - 200))
    assert m["output_tok_s"] == pytest.approx(11 / 2.5)


def test_a_failed_request_misses_every_latency():
    items = [Item(0.0, 8, 5)] * 10
    recs = [rec(t_first=0.1 * (i + 1), t_done=1.0) for i in range(9)]
    recs.append(rec(status="shed", reason="oom", n=0, t_first=None,
                    t_done=None))
    m = e2e.measure(NS(records=recs), items, {"loop": "open"})
    assert m["failed"] == 1
    assert m["ttft_p50_ms"] == pytest.approx(550.0)
    assert m["ttft_p90_ms"] == e2e.MISSED
    # a request cut short (fewer tokens than asked) is not finished either
    recs[0] = rec(n=3)
    assert e2e.measure(NS(records=recs), items, {"loop": "open"})["failed"] == 2


def test_closed_loop_leaves_out_requests_never_sent():
    items = [Item(0.0, 8, 5)] * 4
    recs = [rec(), rec(), rec(status="shed", reason="deadline", n=0),
            rec(status="shed", reason="deadline", n=0)]
    m = e2e.measure(NS(records=recs), items, {"loop": "closed"})
    assert m["attempted"] == 2 and m["failed"] == 0
    m = e2e.measure(NS(records=recs), items, {"loop": "open"})
    assert m["attempted"] == 4 and m["failed"] == 2


def test_percentile_matches_numpy_between_ranks():
    import numpy as np

    v = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for q in (50, 90, 95):
        assert e2e.percentile(v, q) == pytest.approx(np.percentile(v, q))
    assert e2e.percentile([], 50) == e2e.MISSED
