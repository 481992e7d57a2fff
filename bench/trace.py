"""The profiler trace of a traced window, reduced to what the per-layer
metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, keeps the TPU
planes' ``XLA Modules`` line (one event per program run, named
``jit_<function>(<fingerprint>)``) and ``XLA Ops`` line (one event per HLO
instruction run, named by the instruction's text, shapes included), and puts
every event on the engine's clock: seconds from the start of the serve call,
the clock that stamps the engine's request events.  Programs and kernels are
found by name: a Pallas kernel's instruction carries the kernel's name
(``%pim_matvec.93 = f32[8,256] custom-call(bf16[8,1536] ..., s8[1536,256]
...)``).
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np

DECODE = ("jit__decode_chunk_body", "jit__decode_chunk_sharded")
ADMIT = ("jit__admit_body", "jit__admit_prefill_sharded")
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"%([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def op_kind(name: str) -> str:
    """``fusion`` for ``%fusion.12 = ...``; a kernel's own name for its
    custom call."""
    m = _OP.match(name)
    return m.group(1) if m else name.split(" ")[0]


def op_shapes(name: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, shape) of the result and then each operand, in order."""
    head, _, rest = name.partition(" = ")
    body = rest.split(", custom_call_target")[0].split("), ")[0]
    return [(t, tuple(int(d) for d in s.split(",") if d))
            for t, s in _SHAPE.findall(body)]


@dataclass
class Events:
    names: list
    start: np.ndarray  # engine seconds
    end: np.ndarray

    def select(self, keep) -> "Events":
        idx = [i for i, n in enumerate(self.names) if keep(n)]
        return Events([self.names[i] for i in idx], self.start[idx],
                      self.end[idx])

    def within(self, a: float, b: float) -> "Events":
        m = (self.start >= a) & (self.end <= b)
        idx = np.flatnonzero(m)
        return Events([self.names[i] for i in idx], self.start[idx],
                      self.end[idx])

    @property
    def seconds(self) -> float:
        return float(np.sum(self.end - self.start))

    def __len__(self):
        return len(self.names)


def _events(rows) -> Events:
    rows = sorted(rows, key=lambda r: r[1])
    return Events([r[0] for r in rows],
                  np.asarray([r[1] for r in rows], float),
                  np.asarray([r[2] for r in rows], float))


def union_seconds(start: np.ndarray, end: np.ndarray, a: float, b: float,
                  gaps: bool = False):
    """Length of the union of [start, end) clipped to [a, b]; with
    ``gaps``, also the uncovered intervals."""
    s, e = np.clip(start, a, b), np.clip(end, a, b)
    order = np.argsort(s)
    busy, cur_s, cur_e, holes = 0.0, None, a, []
    for i in order:
        if e[i] <= s[i]:
            continue
        if cur_s is None or s[i] > cur_e:
            if cur_s is not None:
                busy += cur_e - cur_s
            holes.append((cur_e, s[i]))
            cur_s, cur_e = s[i], e[i]
        else:
            cur_e = max(cur_e, e[i])
    if cur_s is not None:
        busy += cur_e - cur_s
    holes.append((cur_e, b))
    holes = [(x, y) for x, y in holes if y > x]
    return (busy, holes) if gaps else busy


@dataclass
class Trace:
    on: float        # traced window, engine seconds
    off: float
    chips: list      # per chip: {"modules": Events, "ops": Events}

    @property
    def window_s(self) -> float:
        return self.off - self.on

    def modules(self, prefixes, chip: int = 0) -> Events:
        if chip >= len(self.chips):
            return _events([])
        return self.chips[chip]["modules"].within(self.on, self.off).select(
            lambda n: n.split("(")[0] in prefixes)

    def ops_in(self, mods: Events, chip: int = 0) -> Events:
        """Ops that ran inside the given program runs."""
        ops = self.chips[chip]["ops"]
        keep = np.zeros(len(ops), bool)
        for a, b in zip(mods.start, mods.end):
            keep |= (ops.start >= a) & (ops.end <= b)
        idx = np.flatnonzero(keep)
        return Events([ops.names[i] for i in idx], ops.start[idx],
                      ops.end[idx])

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        return float(np.mean([union_seconds(c["ops"].start, c["ops"].end,
                                            self.on, self.off)
                              for c in self.chips] or [0.0]))

    def breakdown(self, report, top: int = 10) -> dict:
        if not self.chips:
            return {"device_ops": [], "idle_gaps": []}
        ops = self.chips[0]["ops"].within(self.on, self.off)
        mods = self.chips[0]["modules"].within(self.on, self.off)
        agg = {}
        for name, s, e in zip(ops.names, ops.start, ops.end):
            kind = op_kind(name)
            if kind in CONTAINERS:
                continue
            i = np.searchsorted(mods.start, s, side="right") - 1
            prog = mods.names[i].split("(")[0] if i >= 0 else "?"
            key = f"{prog}:{kind}"
            agg[key] = agg.get(key, 0.0) + float(e - s)
        dev = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
        _, holes = union_seconds(self.chips[0]["ops"].start,
                                 self.chips[0]["ops"].end, self.on, self.off,
                                 gaps=True)
        holes = sorted(holes, key=lambda h: h[0] - h[1])[:top]
        return {"device_ops": [[k, v] for k, v in dev],
                "idle_gaps": [[host_label(report, a, b), b - a]
                              for a, b in holes]}

    # --- a small trace, kept for the tests --------------------------------
    def to_json(self, a: float, b: float, keep=()) -> dict:
        """Events in [a, b]; op names cut to their left-hand side except
        for the kinds in ``keep``, whose shapes the readers parse."""
        def short(n):
            lhs, eq, _ = n.partition(" = ")
            return n if not eq or op_kind(n) in keep else lhs + eq

        def ev(e):
            w = e.within(a, b)
            return [[short(n), float(s), float(t)]
                    for n, s, t in zip(w.names, w.start, w.end)]

        return {"on": a, "off": b,
                "chips": [{"modules": ev(c["modules"]), "ops": ev(c["ops"])}
                          for c in self.chips]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["on"], d["off"],
                   [{"modules": _events(c["modules"]), "ops": _events(c["ops"])}
                    for c in d["chips"]])


def host_label(report, a: float, b: float) -> str:
    """What the engine was doing in the idle interval [a, b]: a decode
    round's span, an admit that ended after it, or the gap between
    rounds."""
    mid = 0.5 * (a + b)
    rounds, admits = {}, []
    for i, r in enumerate(report.records):
        for e in r.events:
            if e["name"] == "decode":
                rounds[e["round"]] = (e["ts"], e["ts"] + e["dur"])
            elif e["name"] == "admit":
                admits.append((e["ts"], i))
    for rnd, (s, e) in sorted(rounds.items()):
        if s <= mid <= e:
            return f"decode round {rnd}"
    nxt = min((s for s, _ in rounds.values() if s >= a), default=np.inf)
    for ts, i in sorted(admits):
        if a <= ts <= nxt:
            return f"admit of request {i}"
    return "between rounds"


def load(logdir: Path, info: dict, devices) -> Trace:
    """The newest trace under ``logdir``; ``info`` ties the profiler's clock
    to the engine's (``harness._trace_hooks``)."""
    from jax.profiler import ProfileData

    path = max(Path(logdir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    pd = ProfileData.from_file(str(path))
    start_ns = None
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start_ns = int(stats["profile_start_time"])
    if start_ns is None:
        raise ValueError(f"{path}: no profile_start_time")
    shift = (start_ns - info["wall_ns"]) / 1e9 + info["on"]
    wanted = {f"/device:TPU:{d.id}" for d in devices}
    chips = {}
    for plane in pd.planes:
        if plane.name not in wanted:
            continue
        lines = {}
        for line in plane.lines:
            if line.name in ("XLA Modules", "XLA Ops"):
                lines[line.name] = _events(
                    (e.name, shift + e.start_ns / 1e9,
                     shift + (e.start_ns + e.duration_ns) / 1e9)
                    for e in line.events)
        empty = _events([])
        chips[plane.name] = {"modules": lines.get("XLA Modules", empty),
                             "ops": lines.get("XLA Ops", empty)}
    return Trace(info["on"], info["off"],
                 [chips[k] for k in sorted(chips)])


def load_small(path: Path) -> Trace:
    """A slice kept for the tests: ``Trace.to_json`` of a chip trace,
    gzipped (the kernels' names kept whole)."""
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))
