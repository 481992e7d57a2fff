"""Export serving telemetry as chrome-tracing (Perfetto-loadable) JSON.

``serve_detailed`` stamps span events on each ``RequestRecord`` (admit /
decode / preempt / shed / finish; see resilience.RequestRecord), one
counter sample per dispatched round on ``ServeReport.counters``, and the
host spans of each round's phases on ``ServeReport.spans``.  This module
renders them in the Trace Event Format that chrome://tracing and
https://ui.perfetto.dev load directly:

* one process (pid) per engine replica, one thread track (tid) per batch
  slot — ``ph:"X"`` complete events for decode rounds (they have extent),
  ``ph:"i"`` instants for admit/preempt/shed/finish;
* one ``engine`` thread track per replica of ``ph:"X"`` complete events,
  one per host span (``engine.schedule`` with its nested
  ``engine.admit``, ``engine.dispatch``, ``engine.wait``, ``engine.emit``,
  ``engine.idle``, ``engine.heartbeat``);
* counter tracks (``ph:"C"``) for free/retained pages, pages in use,
  cumulative prefix-hit tokens, effective speculation k, queue depth and
  retries.

Timestamps are the engine clock (VirtualClock under the benches) in
seconds, scaled to the format's microseconds — so traces are
deterministic: same trace + policy + seed => byte-identical JSON.

CLI: ``python tools/trace_export.py --validate trace.json`` exits
non-zero unless the file parses and passes ``validate_trace`` (used by CI
before uploading the bench smoke's trace artifact).
"""
from __future__ import annotations

import argparse
import json
import sys

_US = 1e6   # engine-clock seconds -> trace microseconds

_COUNTER_KEYS = ("free_pages", "retained_pages", "pages_in_use",
                 "prefix_hit_tokens", "eff_k", "queued", "retries")
_INSTANT = ("admit", "preempt", "shed", "finish")
_ENGINE_TID = 1 << 16  # the engine track, clear of the slot tracks


def _meta(pid: int, name: str, tid: int = 0, kind: str = "process_name"):
    return {"name": kind, "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name}}


def report_to_trace(report, pid: int = 0, process_name: str = "engine",
                    request_offset: int = 0) -> dict:
    """Render one ``ServeReport`` as a trace dict (``{"traceEvents": []}``).

    ``request_offset`` shifts the request indices baked into event names
    (the router passes each replica's global indices through per-record
    ``rid`` events instead, so it leaves this at 0 and relies on pids)."""
    ev: list[dict] = [_meta(pid, process_name)]
    tids = set()
    for i, rec in enumerate(report.records):
        label = f"req{request_offset + i}"
        for e in rec.events:
            tid = int(e.get("slot", rec.slot if rec.slot is not None else 0)
                      or 0)
            tids.add(tid)
            args = {k: v for k, v in e.items()
                    if k not in ("name", "ts", "dur", "slot")}
            args["request"] = request_offset + i
            if e["name"] == "decode":
                ev.append({"name": f"decode {label}", "ph": "X",
                           "pid": pid, "tid": tid,
                           "ts": e["ts"] * _US,
                           "dur": max(e.get("dur", 0.0), 0.0) * _US,
                           "cat": "decode", "args": args})
            elif e["name"] in _INSTANT:
                ev.append({"name": f"{e['name']} {label}", "ph": "i",
                           "pid": pid, "tid": tid, "ts": e["ts"] * _US,
                           "s": "t", "cat": e["name"], "args": args})
    for tid in sorted(tids):
        ev.append(_meta(pid, f"slot {tid}", tid, "thread_name"))
    if report.spans:
        ev.append(_meta(pid, "engine", _ENGINE_TID, "thread_name"))
    for s in report.spans:
        ev.append({"name": s["name"], "ph": "X", "pid": pid,
                   "tid": _ENGINE_TID, "ts": s["ts"] * _US,
                   "dur": max(s["dur"], 0.0) * _US, "cat": "engine",
                   "args": {k: v for k, v in s.items()
                            if k not in ("name", "ts", "dur")}})
    for c in report.counters:
        ts = c.get("ts", 0.0) * _US
        for k in _COUNTER_KEYS:
            if k in c:
                ev.append({"name": k, "ph": "C", "pid": pid, "tid": 0,
                           "ts": ts, "args": {k: c[k]}})
    return {"traceEvents": ev,
            "displayTimeUnit": "ms",
            "otherData": {"rounds": report.rounds,
                          "prefix_hits": report.prefix_hits,
                          "prefix_hit_tokens": report.prefix_hit_tokens,
                          "prefill_tokens": report.prefill_tokens,
                          "cow_forks": report.cow_forks,
                          "evictions": report.evictions}}


def router_report_to_trace(router_report) -> dict:
    """Render a ``RouterReport``: one pid per replica, merged into a
    single trace so Perfetto shows the fleet side by side."""
    events: list[dict] = []
    other = {}
    for r, rep in enumerate(router_report.replica_reports):
        sub = report_to_trace(rep, pid=r, process_name=f"replica {r}")
        events.extend(sub["traceEvents"])
        other[f"replica{r}"] = sub["otherData"]
    other["assignments"] = list(map(int, router_report.assignments))
    other["affinity_hits"] = int(router_report.affinity_hits)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


# ------------------------------------------------------------------ checks --
_PH_KNOWN = {"X", "B", "E", "i", "I", "C", "M", "b", "e", "n", "s", "t", "f"}


def validate_trace(obj) -> int:
    """Structural check that ``obj`` is Perfetto-loadable Trace Event JSON.
    Returns the event count; raises ``ValueError`` with a pointed message
    otherwise (CI gates the bench-smoke artifact upload on this)."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("trace must be a JSON object with 'traceEvents'")
    evs = obj["traceEvents"]
    if not isinstance(evs, list) or not evs:
        raise ValueError("'traceEvents' must be a non-empty array")
    for n, e in enumerate(evs):
        where = f"traceEvents[{n}]"
        if not isinstance(e, dict):
            raise ValueError(f"{where}: not an object")
        ph = e.get("ph")
        if ph not in _PH_KNOWN:
            raise ValueError(f"{where}: unknown phase {ph!r}")
        if not isinstance(e.get("name"), str) or not e["name"]:
            raise ValueError(f"{where}: missing event name")
        if not isinstance(e.get("pid"), int):
            raise ValueError(f"{where}: pid must be an integer")
        if ph != "M":
            ts = e.get("ts")
            if not isinstance(ts, (int, float)) or ts < 0:
                raise ValueError(f"{where}: ts must be a number >= 0")
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(f"{where}: complete event needs dur >= 0")
        if ph == "C":
            args = e.get("args")
            if (not isinstance(args, dict) or not args or
                    not all(isinstance(v, (int, float))
                            for v in args.values())):
                raise ValueError(
                    f"{where}: counter args must be numeric values")
    json.dumps(obj)   # must round-trip: no numpy scalars etc. left inside
    return len(evs)


def _jsonable(obj):
    """Coerce numpy scalars so ``json.dump`` (and Perfetto) accept them."""
    import numpy as np
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    return obj


def write_trace(trace: dict, path: str) -> int:
    """Validate then write ``trace`` to ``path``; returns event count."""
    trace = _jsonable(trace)
    n = validate_trace(trace)
    with open(path, "w") as f:
        json.dump(trace, f, separators=(",", ":"))
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--validate", metavar="TRACE_JSON", required=True,
                    help="validate an exported chrome-trace JSON file")
    args = ap.parse_args(argv)
    try:
        with open(args.validate) as f:
            obj = json.load(f)
        n = validate_trace(obj)
    except (OSError, json.JSONDecodeError, ValueError) as e:
        print(f"INVALID {args.validate}: {e}", file=sys.stderr)
        return 1
    print(f"OK {args.validate}: {n} events")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
