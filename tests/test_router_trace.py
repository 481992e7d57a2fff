"""Replica routing + chrome-trace telemetry + bench schema validation.

``ReplicaRouter`` must serve every request TOKEN-IDENTICALLY to a solo
engine (greedy and sampled — the rid-pinning contract), concentrate
shared system prompts onto one replica (prefix affinity) while spreading
load, and the exported chrome-trace JSON must be deterministic,
Perfetto-structurally valid, and round-trippable.  The last tests pin the
``BENCH_*.json`` schema contract the CI validator enforces.
"""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from helpers import (
    assert_tokens_identical,
    build_engine,
    setup_family,
)
from repro.serving import ReplicaRouter, Request, ResiliencePolicy, VirtualClock

ROOT = Path(__file__).resolve().parents[1]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_export = _load_tool("trace_export")
validate_bench = _load_tool("validate_bench")

PS = 4


def _fleet_requests(prompt, vocab, n_per_group=3, n_new=5):
    """Two system-prompt groups: each group shares its row's first page
    (and beyond) with per-request perturbed tails — the trace shape that
    makes prefix affinity matter."""
    rows = np.asarray(prompt, np.int32)
    reqs = []
    for g in range(2):
        for j in range(n_per_group):
            tail = rows[g].copy()
            if j:
                tail[-2:] = (tail[-2:] + j) % vocab
            reqs.append(Request(prompt=tail, max_new=n_new))
    return reqs


def _mk(cfg, params, **kw):
    base = dict(max_seq=24, page_size=PS, chunk=3, num_pages=20,
                prefix_cache=True)
    base.update(kw)
    return build_engine("continuous", cfg, params, **base)


def test_router_token_identical_to_solo_greedy_and_sampled():
    cfg, params, prompt, _ = setup_family("qwen2-1.5b")
    reqs = _fleet_requests(prompt, cfg.vocab)
    key = jax.random.PRNGKey(3)
    for skw in (dict(), dict(greedy=False, temperature=0.8, top_k=8,
                             key=key)):
        want = _mk(cfg, params).serve(reqs, **skw)
        router = ReplicaRouter([_mk(cfg, params) for _ in range(2)])
        rep = router.serve_detailed(reqs, **skw)
        for i in range(len(reqs)):
            assert rep.records[i].status == "done"
            assert_tokens_identical(
                want[i], rep.records[i].tokens,
                msg=f"req {i} diverged routed ({'sampled' if skw else 'greedy'})")


def test_router_prefix_affinity_concentrates_and_spreads():
    """Both system-prompt groups land wholly on one replica each (affinity),
    the two groups land on DIFFERENT replicas (load tiebreak), and every
    non-first group member is an affinity hit."""
    cfg, params, prompt, _ = setup_family("qwen2-1.5b")
    reqs = _fleet_requests(prompt, cfg.vocab)
    router = ReplicaRouter([_mk(cfg, params) for _ in range(2)])
    assign = router.route(reqs)
    g0, g1 = set(assign[:3]), set(assign[3:])
    assert len(g0) == 1 and len(g1) == 1, \
        f"groups must concentrate on one replica each, got {assign}"
    assert g0 != g1, f"least-load tiebreak must spread groups, got {assign}"
    rep = router.serve_detailed(reqs)
    assert rep.assignments == assign
    assert rep.affinity_hits == 4  # requests 1,2 and 4,5
    assert rep.prefix_hits >= 4    # the replicas' REAL tries hit too
    assert len(rep.done()) == len(reqs)


def test_trace_export_deterministic_and_perfetto_valid(tmp_path):
    """Same trace + policy + VirtualClock => byte-identical exported JSON,
    passing the structural validator, for both solo and router reports."""
    cfg, params, prompt, _ = setup_family("qwen2-1.5b")
    reqs = _fleet_requests(prompt, cfg.vocab)
    pol = ResiliencePolicy(round_time=0.5)

    def solo_trace():
        eng = _mk(cfg, params, clock=VirtualClock())
        return trace_export.report_to_trace(
            eng.serve_detailed(reqs, policy=pol))

    t1, t2 = solo_trace(), solo_trace()
    s1 = json.dumps(trace_export._jsonable(t1), sort_keys=True)
    s2 = json.dumps(trace_export._jsonable(t2), sort_keys=True)
    assert s1 == s2, "trace export must be deterministic under VirtualClock"
    n = trace_export.validate_trace(json.loads(s1))
    assert n > len(reqs)  # at least admit+finish per request plus metas
    names = {e["name"].split()[0] for e in t1["traceEvents"]}
    assert {"admit", "decode", "finish", "free_pages"} <= names
    engine = [e for e in t1["traceEvents"] if e.get("cat") == "engine"]
    assert {e["name"] for e in engine} >= {
        "engine.schedule", "engine.admit", "engine.dispatch", "engine.wait",
        "engine.emit"}
    assert len({e["tid"] for e in engine}) == 1
    assert all(e["ph"] == "X" and "round" in e["args"] for e in engine)

    router = ReplicaRouter(
        [_mk(cfg, params, clock=VirtualClock()) for _ in range(2)])
    rrep = router.serve_detailed(reqs, policy=pol)
    rtrace = trace_export.router_report_to_trace(rrep)
    path = tmp_path / "router.trace.json"
    n = trace_export.write_trace(rtrace, str(path))
    assert n == len(rtrace["traceEvents"])
    reloaded = json.loads(path.read_text())
    assert trace_export.validate_trace(reloaded) == n
    assert {e["pid"] for e in reloaded["traceEvents"]} == {0, 1}
    tracks = {(e["pid"], e["args"]["name"]) for e in reloaded["traceEvents"]
              if e["name"] == "thread_name"}
    assert {(0, "engine"), (1, "engine")} <= tracks
    assert reloaded["otherData"]["assignments"] == rrep.assignments


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        trace_export.validate_trace({"events": []})
    with pytest.raises(ValueError, match="phase"):
        trace_export.validate_trace(
            {"traceEvents": [{"name": "x", "ph": "Z", "pid": 0, "ts": 0}]})
    with pytest.raises(ValueError, match="dur"):
        trace_export.validate_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "pid": 0, "ts": 0}]})
    with pytest.raises(ValueError, match="counter"):
        trace_export.validate_trace(
            {"traceEvents": [{"name": "c", "ph": "C", "pid": 0, "ts": 0,
                              "args": {"v": "high"}}]})


def test_committed_bench_artifacts_match_schema():
    """The repo's committed BENCH_*.json must satisfy the CI validator —
    a bench refactor that renames/drops a field fails here, not in a
    downstream consumer PR."""
    for name in ("BENCH_serving.json", "BENCH_decode.json"):
        path = ROOT / name
        if not path.exists():
            pytest.skip(f"{name} not committed")
        errors = validate_bench.validate_bench(json.loads(path.read_text()))
        assert not errors, f"{name}: {errors}"


def test_validate_bench_catches_drift():
    obj = json.loads((ROOT / "BENCH_serving.json").read_text())
    ok = validate_bench.validate_bench(obj)
    assert not ok
    del obj["continuous"]
    obj["page_size"] = "four"
    errors = validate_bench.validate_bench(obj)
    assert any("continuous" in e for e in errors)
    assert any("page_size" in e for e in errors)
