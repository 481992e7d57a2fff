"""What the per-layer readers (``bench/metrics/*.py``) share: matching the
engine's request events to program runs in the trace, and the work of each
kernel call and each step.

A reader gets ``ctx`` with ``trace`` (``bench.trace.Trace``), ``report`` (the
engine's ``ServeReport``), ``items`` (the schedule), ``hp`` (the reference's
sizes), ``serving`` (the configuration's serving block), ``peaks`` (its
device's row of ``bench/peaks.json``), ``chips`` and ``work``.  It returns
None where the traced window holds nothing it reads.
"""
from __future__ import annotations

import numpy as np

from . import trace as tr
from . import work


def decode_rounds(ctx) -> dict:
    """round -> (start, end, [(request, tokens emitted)]) of the decode
    rounds that lie inside the traced window."""
    out = {}
    for i, r in enumerate(ctx.report.records):
        for e in r.events:
            if e["name"] != "decode":
                continue
            s, t = e["ts"], e["ts"] + e["dur"]
            if s >= ctx.trace.on and t <= ctx.trace.off:
                out.setdefault(e["round"], (s, t, []))[2].append(
                    (i, e["tokens"]))
    return out


def decode_runs(ctx, chip: int = 0):
    """The decode-chunk program runs of the window, each with the round
    whose host span holds it: [(start, end, round)]."""
    rounds = decode_rounds(ctx)
    mods = ctx.trace.modules(tr.DECODE, chip)
    out = []
    for s, t in zip(mods.start, mods.end):
        for rnd, (a, b, _) in rounds.items():
            if a <= s and t <= b:
                out.append((s, t, rnd))
                break
    return out


def decode_step_contexts(ctx, rnd: int) -> list[int]:
    """Keys attended by each live (slot, step) of round ``rnd``."""
    out = []
    for i, k in decode_rounds(ctx)[rnd][2]:
        before = 1  # the admit's token
        for e in ctx.report.records[i].events:
            if e["name"] == "decode" and e["round"] < rnd:
                before += e["tokens"]
        plen = ctx.items[i].prompt_len
        out += [plen + j for j in range(before, before + k)]
    return out


def admit_runs(ctx, chip: int = 0):
    """Admit program runs of the window matched to the admit event the host
    stamped after each: [(start, end, true prompt tokens)]."""
    events = sorted((e["ts"], e["prefilled_tokens"])
                    for r in ctx.report.records for e in r.events
                    if e["name"] == "admit")
    mods = ctx.trace.modules(tr.ADMIT, chip)
    out, j = [], 0
    for s, t in zip(mods.start, mods.end):
        while j < len(events) and events[j][0] < t:
            j += 1
        if j < len(events):
            out.append((s, t, events[j][1]))
            j += 1
    return out


def _kernel_calls(ctx, kernel: str, programs):
    """(chip, name, start, end, least seconds) of each ``kernel`` call that
    ran inside ``programs`` runs."""
    linears = _linear_shapes(ctx)
    for chip in range(len(ctx.trace.chips)):
        mods = ctx.trace.modules(programs, chip)
        ops = ctx.trace.ops_in(mods, chip).select(
            lambda n: tr.op_kind(n) == kernel)
        for name, s, t in zip(ops.names, ops.start, ops.end):
            shapes = tr.op_shapes(name)
            m, kp = shapes[1][1]
            np_ = shapes[0][1][1]
            k, n = _true_kn(linears, kp, np_)
            bias = len(shapes) > 4
            f, b = work.quant_linear(m, k, n, bias=bias)
            yield chip, name, s, t, work.least_seconds(f, b, ctx.peaks)


def kernel_share(ctx, kernel: str, programs) -> float | None:
    """Least time over measured time, in %, of the ``kernel`` calls that ran
    inside ``programs`` runs, summed over the chips."""
    least = spent = 0.0
    for _, _, s, t, low in _kernel_calls(ctx, kernel, programs):
        least += low
        spent += t - s
    return 100.0 * least / spent if spent > 0 else None


def kernel_table(ctx, kernel: str, programs) -> list[dict]:
    """The ``kernel`` calls grouped by operand shapes: calls, mean
    microseconds, roofline share, and the share of their time in which
    another operation ran on the same chip (a copy or collective that
    shares the memory bandwidth).  Printed by a traced run, not a metric."""
    groups, leaves = {}, {}
    for chip, name, s, t, low in _kernel_calls(ctx, kernel, programs):
        if chip not in leaves:
            ops = ctx.trace.chips[chip]["ops"].select(
                lambda n: tr.op_kind(n) not in tr.CONTAINERS)
            leaves[chip] = (ops, float(np.max(ops.end - ops.start)))
        ops, longest = leaves[chip]
        lo = np.searchsorted(ops.start, s - longest)
        hi = np.searchsorted(ops.start, t)
        other = sum(max(0.0, min(t, ops.end[i]) - max(s, ops.start[i]))
                    for i in range(lo, hi)
                    if not (ops.start[i] == s and ops.names[i] == name))
        g = groups.setdefault(str(tr.op_shapes(name)[1:3]), [0, 0.0, 0.0, 0.0])
        g[0] += 1
        g[1] += t - s
        g[2] += low
        g[3] += other
    return [{"shapes": k, "calls": n, "mean_us": 1e6 * spent / n,
             "roofline_pct": 100.0 * low / spent,
             "overlapped_pct": 100.0 * other / spent}
            for k, (n, spent, low, other) in groups.items() if spent > 0]


def _linear_shapes(ctx) -> list[tuple[int, int]]:
    tp = int(ctx.serving.get("tensor_parallel", 1))
    kn = [(k, n) for _, k, n, _ in work.layer_linears(ctx.hp)]
    if not ctx.hp["tied"]:
        kn.append((ctx.hp["d_model"], ctx.hp["vocab"]))
    return kn + [(k, n // tp) for k, n in kn if tp > 1]


def _true_kn(linears, kp: int, np_: int) -> tuple[int, int]:
    """The model's (K, N) that a call padded to (kp, np_) computes: the
    tightest linear that fits (a kernel may pad K and N to its tiles)."""
    fits = [(kp - k + np_ - n, k, n) for k, n in linears
            if k <= kp and n <= np_]
    if not fits:
        return kp, np_
    _, k, n = min(fits)
    return k, n


def mfu(flops: float, seconds: float, ctx) -> float | None:
    if seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops_s"] * ctx.chips)


def mean(xs):
    return float(np.mean(xs)) if len(xs) else None
