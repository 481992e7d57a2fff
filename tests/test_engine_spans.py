"""The serve loop's host spans (``ServeReport.spans``): one span per phase
of each scheduling round, on the engine clock, and the same spans on the
profiler's host plane when a trace is taken.

A tiny paged engine serves three requests under the resilience policy: two
at once, and a third that arrives after they finish, so the engine idles
for some rounds between them.  The clock ticks on every read, so every span
has extent and no two share a start."""
from pathlib import Path

import jax
import numpy as np
import pytest

from helpers import build_engine, setup_family
from repro.serving import Request, ResiliencePolicy

PHASES = ["engine.schedule", "engine.dispatch", "engine.wait", "engine.emit"]


class TickClock:
    """Advances one millisecond on every read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1e-3
        return self.t


@pytest.fixture(scope="module")
def model():
    cfg, params, prompt, _ = setup_family("qwen2-1.5b")
    return cfg, params, np.asarray(prompt, np.int32)


def _serve(model, speculate):
    cfg, params, prompt = model
    eng = build_engine("continuous", cfg, params, max_seq=24, slots=2,
                       page_size=4, chunk=3, num_pages=16,
                       speculate=speculate, clock=TickClock())
    reqs = [Request(prompt=prompt[0], max_new=5),
            Request(prompt=prompt[1, :6], max_new=4),
            Request(prompt=prompt[1], max_new=4, arrival=0.6)]
    rep = eng.serve_detailed(reqs, policy=ResiliencePolicy(round_time=0.05),
                             heartbeat=lambda: None)
    assert [r.status for r in rep.records] == ["done"] * 3
    # every decode round took the dispatch branch under test
    assert {c["eff_k"] for c in rep.counters} == {speculate or 0}
    return rep


def _by_round(spans):
    out = {}
    for s in sorted(spans, key=lambda s: s["ts"]):
        out.setdefault(s["round"], []).append(s)
    return out


@pytest.mark.parametrize("speculate", [None, 2], ids=["plain", "ngram"])
def test_every_round_records_its_phases_in_order(model, speculate):
    rep = _serve(model, speculate)
    rounds = _by_round(rep.spans)
    assert sorted(rounds) == list(range(rep.rounds))
    decode_rounds = {e["round"] for r in rep.records for e in r.events
                     if e["name"] == "decode"}
    idle = [rnd for rnd, ss in rounds.items()
            if any(s["name"] == "engine.idle" for s in ss)]
    assert idle, "the third request's wait must idle the engine"
    for rnd, ss in rounds.items():
        assert ss[0]["name"] == "engine.heartbeat"
        top = [s for s in ss if s["name"] not in ("engine.heartbeat",
                                                  "engine.admit")]
        names = [s["name"] for s in top]
        if rnd in decode_rounds:
            assert names == PHASES, (rnd, names)
        else:
            assert names == ["engine.schedule", "engine.idle"], (rnd, names)
        for a, b in zip(ss, ss[1:]):
            if b["name"] != "engine.admit":
                assert a["ts"] + a["dur"] <= b["ts"], (rnd, a, b)
    emitted = sum(e["tokens"] for r in rep.records for e in r.events
                  if e["name"] == "decode")
    assert sum(s["tokens"] for s in rep.spans
               if s["name"] == "engine.emit") == emitted


@pytest.mark.parametrize("speculate", [None, 2], ids=["plain", "ngram"])
def test_admits_nest_in_their_schedule(model, speculate):
    rep = _serve(model, speculate)
    events = {(e["round"], e["slot"]): e for r in rep.records
              for e in r.events if e["name"] == "admit"}
    admits = [s for s in rep.spans if s["name"] == "engine.admit"]
    assert len(admits) == len(events) == 3
    for a in admits:
        (sched,) = [s for s in rep.spans if s["name"] == "engine.schedule"
                    and s["round"] == a["round"]]
        assert sched["ts"] <= a["ts"]
        assert a["ts"] + a["dur"] <= sched["ts"] + sched["dur"]
        ev = events[(a["round"], a["slot"])]
        assert a["prefilled_tokens"] == ev["prefilled_tokens"]
        assert a["prompt_tokens"] >= a["prefilled_tokens"] > 0


@pytest.mark.parametrize("speculate", [None, 2], ids=["plain", "ngram"])
def test_profiler_host_plane_holds_the_same_spans(model, speculate,
                                                  tmp_path):
    from jax.profiler import ProfileData

    _serve(model, speculate)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = _serve(model, speculate)
    finally:
        jax.profiler.stop_trace()
    path = max(Path(tmp_path).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    host = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    host.append((e.start_ns, e.name, dict(e.stats)))
    host.sort(key=lambda h: h[0])
    spans = sorted(rep.spans, key=lambda s: s["ts"])
    assert [h[1] for h in host] == [s["name"] for s in spans]
    assert [h[2]["round"] for h in host] == [s["round"] for s in spans]
    for (_, name, stats), s in zip(host, spans):
        if name == "engine.admit":
            assert stats["prefilled_tokens"] == s["prefilled_tokens"]
        if name == "engine.emit":
            assert stats["tokens"] == s["tokens"]


@pytest.mark.parametrize("speculate", [None, 2], ids=["plain", "ngram"])
def test_counters_hold_the_pages_each_chunk_read(model, speculate,
                                                 monkeypatch):
    """Every decode round's counters carry ``kv_pages_read``, the pages of
    each slot's first ``pos + 1`` positions at the chunk's last step, and
    ``kv_pages_table``, every slot's whole block-table row.  The positions
    are the chunk program's own outputs: a plain step's live slots advanced
    past the position they attended to; a speculative round counts from
    the frontier it reached."""
    from repro.serving import engine as eng
    from repro.serving import speculative as spec_mod

    seen = []
    if speculate is None:
        orig = eng._decode_chunk

        def spy(*a, **kw):
            out = orig(*a, **kw)
            pos, lives = np.asarray(out[2]), np.asarray(out[6])
            seen.append(pos - lives[-1])
            return out

        monkeypatch.setattr(eng, "_decode_chunk", spy)
    else:
        orig = spec_mod._spec_chunk

        def spy(*a, **kw):
            out = orig(*a, **kw)
            seen.append(np.asarray(out[3]))
            return out

        monkeypatch.setattr(spec_mod, "_spec_chunk", spy)
    rep = _serve(model, speculate)
    decode_rounds = {e["round"] for r in rep.records for e in r.events
                     if e["name"] == "decode"}
    assert sorted(c["round"] for c in rep.counters) == sorted(decode_rounds)
    assert len(seen) == len(rep.counters)
    page_size, slots, width = 4, 2, 24 // 4
    for c, last in zip(rep.counters, seen):
        assert c["kv_pages_read"] == sum(-(-(int(p) + 1) // page_size)
                                         for p in last)
        assert c["kv_pages_table"] == slots * width
        assert 0 < c["kv_pages_read"] <= c["kv_pages_table"]
