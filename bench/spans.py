"""The program's own spans and scopes in a traced window, for the readers
of the scheduler's phases and of the decode step's layers.

The engine records each phase of a scheduling round as a host span
(``ServeReport.spans``, engine clock) and, over the same interval, as a
``jax.profiler.TraceAnnotation``, which the profiler writes on its host
plane.  The model names its layers with ``jax.named_scope`` (``SCOPES``),
which XLA keeps in each instruction's ``op_name`` metadata.  The TPU's op
events carry no metadata, but the trace's ``/host:metadata`` plane holds
the compiled HLO of every program, under the name its module events have:
an op's scope is the innermost of ``SCOPES`` in its instruction's
``op_name``, or ``(none)``.

``of(ctx)`` reads both from the ``.xplane.pb`` that ``bench.trace.load``
read, on ``ctx.trace``'s clock, once a run, and prints what is not a
metric: device time by scope per program family, the engine.* span under
each of the longest idle gaps, and the error of the wall-clock tie that
puts the engine's events on the trace's clock.  A program that records no
spans or scopes reads as having none, and the readers return None.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from . import readers
from . import trace as tr

SCOPES = ("attention", "kv_write", "linear", "lm_head", "sample", "nonfinite")
NONE = "(none)"
ROUND = ("engine.schedule", "engine.dispatch", "engine.wait", "engine.emit")
_NAME = re.compile(r"%([\w.\-]+) = ")


@dataclass
class Scoped:
    host: list    # [name, start, end, args] of engine.* spans, by start
    scopes: list  # per chip: the scope of each of ctx.trace's ops, in order

    def to_json(self, trace: tr.Trace, a: float, b: float) -> dict:
        """The spans and op scopes in [a, b], the ops in the order of
        ``trace.to_json(a, b)``."""
        def kept(sc, ops):
            inside = (ops.start >= a) & (ops.end <= b)
            return [s for s, keep in zip(sc, inside) if keep]

        return {"host": [h for h in self.host if a <= h[1] and h[2] <= b],
                "scopes": [kept(sc, c["ops"])
                           for sc, c in zip(self.scopes, trace.chips)]}

    @classmethod
    def from_json(cls, d: dict) -> "Scoped":
        return cls(d["host"], d["scopes"])


def of(ctx) -> Scoped | None:
    """The run's spans and scopes (``ctx.scoped`` where a test set it)."""
    if not hasattr(ctx, "scoped"):
        from .harness import TRACE_DIR

        ctx.scoped = load(TRACE_DIR, ctx.trace)
        if ctx.scoped is not None:
            for line in diagnostics(ctx):
                print(line, flush=True)
    return ctx.scoped


# --- reading the .xplane.pb ---------------------------------------------
def load(logdir: Path, trace: tr.Trace) -> Scoped | None:
    from jax.profiler import ProfileData

    paths = list(Path(logdir).rglob("*.xplane.pb"))
    if not paths or not trace.chips:
        return None
    path = max(paths, key=lambda p: p.stat().st_mtime)
    raw = path.read_bytes()
    pd = ProfileData.from_serialized_xspace(raw)
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)  # chip 0 first, as trace.load
    first = min((e.start_ns for p in tpus[:1] for line in p.lines
                 if line.name == "XLA Modules" for e in line.events),
                default=None)
    mods = trace.chips[0]["modules"]
    if not len(mods) or first is None:
        return None
    shift = float(mods.start[0]) - first / 1e9  # onto the trace's clock
    host = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    host.append([e.name, shift + e.start_ns / 1e9,
                                 shift + (e.start_ns + e.duration_ns) / 1e9,
                                 {k: _number(v) for k, v in e.stats}])
    host.sort(key=lambda h: h[1])
    hlo = hlo_scopes(raw)
    return Scoped(host, [op_scopes(c, hlo) for c in trace.chips])


def _number(v):
    for kind in (int, float):
        try:
            return kind(v)
        except (TypeError, ValueError):
            pass
    return v


def op_scopes(chip: dict, hlo: dict) -> list:
    """The scope of each op of one chip: looked up in the HLO of the program
    run that holds it; None where the trace has no HLO for that program."""
    mods, ops = chip["modules"], chip["ops"]
    run = np.searchsorted(mods.start, ops.start, side="right") - 1
    out = []
    for name, i in zip(ops.names, run):
        table = hlo.get(mods.names[i]) if i >= 0 else None
        m = _NAME.match(name)
        out.append(None if table is None or not m
                   else table.get(m.group(1), NONE))
    return out


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` name stack."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return NONE


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf message;
    a varint as an int, a length-delimited field as a memoryview."""
    buf, i, n = memoryview(buf), 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _varint(buf, i: int):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _group(buf) -> dict:
    out = {}
    for f, v in _fields(buf):
        out.setdefault(f, []).append(v)
    return out


def hlo_scopes(raw: bytes) -> dict:
    """program name -> {instruction name: scope}, from the ``Hlo Proto``
    stats of the ``/host:metadata`` plane (XSpace.planes = 1; XPlane name =
    2, event_metadata = 4, stat_metadata = 5; XEventMetadata name = 2,
    stats = 5; XStat metadata_id = 1, bytes_value = 6; HloProto hlo_module
    = 1)."""
    out = {}
    for f, plane in _fields(raw):
        if f != 1:
            continue
        p = _group(plane)
        if bytes(p.get(2, [b""])[0]) != b"/host:metadata":
            continue
        stat_names = {}
        for entry in p.get(5, []):
            e = _group(entry)
            stat_names[e[1][0]] = bytes(_group(e[2][0]).get(2, [b""])[0])
        for entry in p.get(4, []):
            meta = _group(_group(entry)[2][0])
            for stat in meta.get(5, []):
                s = _group(stat)
                if stat_names.get(s[1][0]) == b"Hlo Proto" and 6 in s:
                    module = _group(s[6][0])[1][0]
                    out[bytes(meta[2][0]).decode()] = _module_scopes(module)
    return out


def _module_scopes(module) -> dict:
    """instruction name -> scope of an HloModuleProto (computations = 3;
    HloComputationProto instructions = 2; HloInstructionProto name = 1,
    metadata = 7; OpMetadata op_name = 2)."""
    out = {}
    for comp in _group(module).get(3, []):
        for inst in _group(comp).get(2, []):
            g = _group(inst)
            meta = _group(g[7][0]) if 7 in g else {}
            op_name = bytes(meta.get(2, [b""])[0]).decode()
            out[bytes(g[1][0]).decode()] = scope_of(op_name)
    return out


# --- what the readers share ---------------------------------------------
def decode_rounds(ctx) -> dict:
    """round -> {span name: seconds} (engine clock) of each decode round
    whose spans all lie inside the traced window; a round's admits are
    summed under ``engine.admit``."""
    rounds = {}
    for s in getattr(ctx.report, "spans", []):
        rounds.setdefault(s["round"], []).append(s)
    out = {}
    for rnd, ss in rounds.items():
        names = {s["name"] for s in ss}
        if not set(ROUND) <= names:
            continue
        if min(s["ts"] for s in ss) < ctx.trace.on or max(
                s["ts"] + s["dur"] for s in ss) > ctx.trace.off:
            continue
        secs = dict.fromkeys(ROUND + ("engine.admit",), 0.0)
        for s in ss:
            secs[s["name"]] = secs.get(s["name"], 0.0) + s["dur"]
        out[rnd] = secs
    return out


def round_phase_ms(ctx, phase: str) -> float | None:
    rounds = decode_rounds(ctx)
    if not rounds:
        return None
    if phase == "engine.schedule":
        vals = [r[phase] - r["engine.admit"] for r in rounds.values()]
    else:
        vals = [r[phase] for r in rounds.values()]
    return 1e3 * float(np.mean(vals))


def scope_seconds(ctx, runs, chip: int = 0) -> dict | None:
    """scope -> device seconds of the leaf ops inside the given program runs
    [(start, end, ...)]; None where an op's program has no HLO in the
    trace."""
    sc = of(ctx)
    if sc is None or chip >= len(sc.scopes):
        return None
    ops = ctx.trace.chips[chip]["ops"]
    scopes = sc.scopes[chip]
    out = {}
    for run in runs:
        a, b = run[0], run[1]
        lo, hi = np.searchsorted(ops.start, [a, b])
        for i in range(lo, hi):
            if ops.end[i] > b or tr.op_kind(ops.names[i]) in tr.CONTAINERS:
                continue
            if scopes[i] is None:
                return None
            out[scopes[i]] = out.get(scopes[i], 0.0) + float(
                ops.end[i] - ops.start[i])
    return out


def decode_scope_ms(ctx, names) -> float | None:
    """Device time under the given scopes in the decode-chunk programs, per
    decode step (``chunk`` steps a run, as ``decode_step_ms`` counts)."""
    runs = readers.decode_runs(ctx)
    secs = scope_seconds(ctx, runs) if runs else None
    if not secs or set(secs) == {NONE}:
        return None
    steps = len(runs) * int(ctx.serving["chunk"])
    return 1e3 * sum(secs.get(n, 0.0) for n in names) / steps


def scope_table(ctx, programs, chip: int = 0) -> dict | None:
    """Device milliseconds per program run by scope, ``(none)`` included,
    over the window's runs of ``programs``.  Printed, not a metric."""
    mods = ctx.trace.modules(programs, chip)
    runs = list(zip(mods.start, mods.end))
    secs = scope_seconds(ctx, runs, chip) if runs else None
    if secs is None:
        return None
    return {"runs": len(runs),
            "ms_per_run": {k: 1e3 * v / len(runs) for k, v in
                           sorted(secs.items(), key=lambda kv: -kv[1])}}


def span_at(spans: list, t: float) -> str | None:
    """The name of the innermost of ``spans`` ([name, start, end, args], by
    start) that covers ``t``."""
    best = None
    for name, a, b, _ in spans:
        if a > t:
            break
        if t <= b and (best is None or a >= best[0]):
            best = (a, name)
    return None if best is None else best[1]


def idle_gaps(ctx, top: int = 10) -> list:
    """The longest device idle gaps of the window, each labelled by the
    engine.* span under its midpoint: on the host plane, else in
    ``ServeReport.spans`` (a span opened before the profiler started is
    only there), else ``trace.host_label``."""
    sc = of(ctx)
    engine = sorted(([s["name"], s["ts"], s["ts"] + s["dur"], s]
                     for s in getattr(ctx.report, "spans", [])),
                    key=lambda h: h[1])
    ops = ctx.trace.chips[0]["ops"]
    _, holes = tr.union_seconds(ops.start, ops.end, ctx.trace.on,
                                ctx.trace.off, gaps=True)
    holes = sorted(holes, key=lambda h: h[0] - h[1])[:top]
    out = []
    for a, b in holes:
        mid = 0.5 * (a + b)
        label = ((span_at(sc.host, mid) if sc else None)
                 or span_at(engine, mid))
        out.append([label or tr.host_label(ctx.report, a, b), b - a])
    return out


def idle_by_span(ctx) -> dict | None:
    """Device idle seconds of the window under each engine.* span name on
    the host plane, per decode round that dispatched in it (an admit's idle
    counted under ``engine.admit``, not its schedule), and under none."""
    sc = of(ctx)
    if sc is None:
        return None
    on, off = ctx.trace.on, ctx.trace.off
    n = sum(1 for h in sc.host if h[0] == "engine.dispatch"
            and on <= h[1] <= off)
    if not n:
        return None
    ops = ctx.trace.chips[0]["ops"]
    _, holes = tr.union_seconds(ops.start, ops.end, on, off, gaps=True)
    out = {}
    for name, a, b, _ in sc.host:
        over = sum(max(0.0, min(b, y) - max(a, x)) for x, y in holes)
        out[name] = out.get(name, 0.0) + over
    out["engine.schedule"] = (out.get("engine.schedule", 0.0)
                              - out.get("engine.admit", 0.0))
    out[NONE] = sum(y - x for x, y in holes) - sum(out.values())
    return {k: v / n for k, v in out.items()}


def tie_offsets(ctx) -> np.ndarray:
    """Host-plane start minus engine-clock ``ts`` of each engine span that
    both record inside the window: the error of the wall-clock tie
    (``harness._trace_hooks``) that puts engine events on the trace's
    clock."""
    sc = of(ctx)
    if sc is None:
        return np.zeros(0)

    def key(name, args):
        return name, args.get("round"), args.get("slot")

    engine = {key(s["name"], s): s["ts"] for s in
              getattr(ctx.report, "spans", [])}
    return np.asarray([a - engine[key(n, args)] for n, a, _, args in sc.host
                       if key(n, args) in engine])


def diagnostics(ctx) -> list[str]:
    out = []
    for family, programs in (("admit", tr.ADMIT), ("decode", tr.DECODE)):
        out.append(f"scopes {family} "
                   f"{json.dumps(scope_table(ctx, programs))}")
    off = tie_offsets(ctx)
    tie = ({"spans": len(off), "median_ms": 1e3 * float(np.median(off)),
            "widest_ms": 1e3 * float(off[np.argmax(np.abs(off))])}
           if len(off) else {"spans": 0})
    phases = {p: round_phase_ms(ctx, p) for p in ROUND}
    by = idle_by_span(ctx) or {}
    waiting = ("engine.idle", "engine.heartbeat")  # no decode to feed
    out.append("host spans " + json.dumps({
        "tie_offset": tie, "round_ms": phases,
        "device_idle_ms_per_decode_round": 1e3 * sum(
            v for k, v in by.items() if k not in waiting) if by else None,
        "idle_ms_per_decode_round_by_span": {k: 1e3 * v
                                             for k, v in by.items()}}))
    out.append(f"idle gaps {json.dumps(idle_gaps(ctx))}")
    return out
