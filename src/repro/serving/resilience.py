"""Serving-tier resilience: SLO policy, degradation ladder, crash-replay
snapshots, and the restart supervisor.

This module is the host-side half of fault-tolerant serving; the engine
half lives in ``ContinuousBatchingEngine.serve_detailed`` (serving.engine),
which consults a ``ResiliencePolicy`` at every scheduling boundary and
emits a ``ServeReport``.  Nothing here touches compiled code: resilience
is pure scheduling, which is what makes recovery provable — the compiled
decode path stays bit-deterministic, and the fold_in draw-key discipline
(serving.sampling) makes a replayed request's token stream identical to
the undisturbed run.

Failure semantics (what is retried / shed / degraded / replayed):

* **Retried** — transient chunk faults (``chaos.ChunkFault`` or anything
  the injector raises before the compiled step runs): the engine backs
  off (clock skew, no real sleep under a virtual clock) and re-invokes
  the SAME chunk up to ``max_retries`` times; past that the round is
  treated as an engine crash.  Retries never touch emitted tokens: the
  failed attempt never ran.
* **Shed** — load the engine refuses: queued requests whose deadline has
  already passed (``shed_expired``), queue overflow beyond ``max_queue``
  (lowest SLO class first, youngest arrival breaking ties), requests that
  can never fit the page pool ("oom"), and — at the top ladder rung —
  queued requests below ``protect_slo``.  Shed requests get status
  ``"shed"`` and whatever tokens they had already emitted; running
  requests are never shed mid-flight (their pages recycle naturally at
  retire).
* **Rejected** — invalid payloads (corrupted token ids, empty prompts,
  budgets that exceed ``max_seq``): admission validation refuses them
  with status ``"rejected"`` instead of feeding garbage to the compiled
  program.  Without a policy the engine raises, exactly as before.
* **Degraded** — the ladder (below) trades throughput machinery for
  stability one rung at a time; under greedy decode every rung is
  token-preserving (greedy speculation at any ``k`` — including off —
  emits identical tokens), so degradation never changes what a greedy
  request sees, only how fast it sees it.
* **Replayed** — after a crash, the supervisor restores the last
  ``ServeSnapshot`` (in-flight = prompt + emitted tokens + draw counters)
  and the engine re-admits each in-flight request by prefilling
  ``prompt + emitted[:-1]`` straight into fresh pages, resuming the token
  draw counter at ``len(emitted)`` — the (rid, counter) fold_in keys then
  continue the SAME random stream, so replayed requests finish
  token-identically to a run that never crashed
  (tests/test_chaos.py::test_crash_replay*).

Degradation ladder (rung 0 = healthy), driven by the engine's own
signals — retries this round, free-page fraction, deadline sheds,
injected/measured stragglers:

  0. full service (configured speculation window, full chunk)
  1. shrink the speculative verify window ``k`` to ``k // 2``
     (speculation overhead is the first thing to go; greedy tokens are
     invariant to ``k``)
  2. disable speculation entirely (one token per weight stream, but no
     verify-window overdraw on the page pool)
  3. halve the decode chunk (host regains control 2x as often: faster
     retire/admit turnaround and smaller retry units)
  4. shed queued requests below ``protect_slo``

Each bad round escalates one rung; ``cooldown`` consecutive clean rounds
de-escalate one.  The trace of transitions lands in
``ServeReport.ladder_trace``.

``ServingSupervisor`` reuses the training-tier ``runtime.fault``
machinery for liveness: the engine heartbeats host 0 of a
``HeartbeatMonitor`` every scheduling round; a crash (or a hang, under a
virtual clock) is detected by ``sweep()``, logged as a ``FailureEvent``,
and recovered by ``revive`` + snapshot restore — the serving analogue of
``TrainingSupervisor.run``'s restore-replan-continue loop.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Optional, Sequence

import numpy as np

from repro.runtime.fault import FailureEvent, HeartbeatMonitor
from repro.serving.chaos import EngineCrash, FaultInjector, VirtualClock


# ------------------------------------------------------------------ policy --
@dataclasses.dataclass(frozen=True)
class LadderConfig:
    """Degradation-ladder tuning; see the module docstring for the rungs."""

    enabled: bool = True
    cooldown: int = 3           # clean rounds before de-escalating one rung
    free_frac: float = 0.125    # free-page fraction that counts as pressure
    protect_slo: int = 1        # rung 4 sheds queued requests below this


@dataclasses.dataclass(frozen=True)
class ResiliencePolicy:
    """Request-level robustness knobs for ``serve_detailed``.  The default
    instance is safe everywhere: validation on, modest retry budget,
    ladder on, unbounded queue, snapshot every round."""

    max_retries: int = 3          # per-chunk transient-fault retries
    backoff_s: float = 0.01       # base backoff (doubles per attempt)
    max_queue: Optional[int] = None  # bounded admission queue (None = off)
    shed_expired: bool = True     # shed queued requests past their deadline
    validate: bool = True         # admission payload validation
    ladder: LadderConfig = LadderConfig()
    snapshot_every: int = 1       # rounds between snapshots (0 = off)
    snapshot_sink: Optional[Callable] = None  # called with each ServeSnapshot
    resume_mode: str = "prefill"  # "prefill" (replay by re-prefill) or
    #                               "recompute" (requeue from scratch)
    round_time: float = 0.0       # virtual seconds per round (deterministic
    #                               deadline tests under a VirtualClock)

    def __post_init__(self):
        if self.resume_mode not in ("prefill", "recompute"):
            raise ValueError(f"resume_mode {self.resume_mode!r}")


# ------------------------------------------------------------------ report --
@dataclasses.dataclass
class RequestRecord:
    """Outcome of one request: ``status`` is ``"done"`` (full budget or
    stop token), ``"shed"`` (load-shedding; ``tokens`` holds whatever was
    emitted before the shed), or ``"rejected"`` (admission validation).
    Times are engine-clock seconds from serve start (straggler skew
    included); ``met_deadline`` is None when the request had none.

    ``slot`` is the batch slot the request last occupied; ``events`` is
    its span-event stream — dicts of ``{"name", "ts", ...}`` (``"dur"``
    for spans with extent, plus per-event args: ``cached_tokens``/
    ``prefilled_tokens``/``cow`` on admit, ``tokens``/``round`` on
    decode, ``reason`` on shed).  Event names: ``admit``, ``decode``
    (one per scheduling round the request was live in), ``preempt``,
    ``shed``, ``finish``.  The engine's own phases of each round are host
    spans on ``ServeReport.spans``, not per request.
    ``tools/trace_export.py`` renders these as chrome-tracing/Perfetto
    tracks; under a VirtualClock the stream is deterministic."""

    status: str = "pending"
    reason: str = ""
    tokens: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int32))
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    met_deadline: Optional[bool] = None
    slot: Optional[int] = None
    events: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ServeReport:
    """Everything ``serve_detailed`` observed: per-request outcomes plus
    the resilience counters the benches aggregate into goodput/SLO
    attainment (benchmarks/serving_bench.py ``--fault-rate``)."""

    records: list = dataclasses.field(default_factory=list)
    rounds: int = 0
    retries: int = 0
    straggle_s: float = 0.0
    squeezed_pages: int = 0
    sheds: int = 0
    rejects: int = 0
    restarts: int = 0           # filled by the supervisor
    failures: list = dataclasses.field(default_factory=list)
    ladder_trace: list = dataclasses.field(default_factory=list)
    # (round, rung, reason) transitions
    max_ladder_level: int = 0
    # One sample per scheduling round that dispatched a chunk: free /
    # retained page counts, cumulative prefix-hit tokens, effective k,
    # queue depth — the counter tracks of tools/trace_export.py.
    counters: list = dataclasses.field(default_factory=list)
    # Host spans of the serve loop, engine clock: ``{"name", "ts", "dur",
    # "round", ...args}``, each also a profiler ``TraceAnnotation`` over
    # the same interval.  Per round: ``engine.heartbeat``, then
    # ``engine.schedule`` (holding one ``engine.admit`` per admit, with
    # ``slot``/``prompt_tokens``/``prefilled_tokens``), then either
    # ``engine.idle`` or ``engine.dispatch``, ``engine.wait`` and
    # ``engine.emit`` (with ``tokens``).  Rendered as the ``engine`` track
    # by tools/trace_export.py; read by the benchmark's scheduler metrics.
    spans: list = dataclasses.field(default_factory=list)
    prefix_hits: int = 0        # admits served (partly) from the prefix trie
    prefix_hit_tokens: int = 0  # prompt tokens aliased instead of prefilled
    prefill_tokens: int = 0     # prompt tokens actually computed
    cow_forks: int = 0          # copy-on-write page forks
    evictions: int = 0          # retained cache pages evicted under pressure

    @property
    def outputs(self) -> list[np.ndarray]:
        return [r.tokens for r in self.records]

    def done(self) -> list[int]:
        return [i for i, r in enumerate(self.records) if r.status == "done"]

    def latencies(self) -> list[float]:
        """Completion latency (serve-start to last token) per done request.

        Granularity: completion times are interpolated WITHIN a scheduling
        round to the chunk iteration the request's slot last emitted in
        (the engine only observes device results at round boundaries), so
        the residual quantization is one chunk iteration — ``round_time /
        eff_chunk`` under a VirtualClock-driven policy — rather than the
        whole round.  Two requests finishing in the same iteration of the
        same round still share a timestamp."""
        return [r.t_done for r in self.records
                if r.status == "done" and r.t_done is not None]

    def slo_attainment(self) -> float:
        """Fraction of requests that finished AND met their deadline;
        requests without deadlines count as met.  Shed/rejected = missed."""
        if not self.records:
            return 1.0
        met = sum(1 for r in self.records
                  if r.status == "done" and r.met_deadline in (True, None))
        return met / len(self.records)

    def goodput_tokens(self) -> int:
        """Tokens of requests that completed within their deadline — the
        numerator of goodput (useful work per second under SLO)."""
        return sum(len(r.tokens) for r in self.records
                   if r.status == "done" and r.met_deadline in (True, None))


# ---------------------------------------------------------------- snapshot --
@dataclasses.dataclass
class InflightState:
    """Replay state for one in-flight request: everything needed to
    re-admit it token-identically — its emissions so far (the prompt lives
    in the request list) and its verify-window draw counter.  The token
    draw counter IS ``len(emitted)`` (draw n samples the n-th emission;
    see serving.sampling).  ``acc_ema`` is the adaptive controller's
    learned per-request acceptance estimate (speculative.SpecConfig
    ``adaptive``) so a crash replay resumes the controller where it left
    off instead of re-paying the warm-up; the default keeps snapshots
    taken before this field existed loadable."""

    emitted: list
    wctr: int = 0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    acc_ema: float = 0.5


@dataclasses.dataclass
class ServeSnapshot:
    """Lightweight engine snapshot taken at scheduling-round boundaries:
    host-side request state only — no device buffers, no KV pages (those
    are recomputed by the resume prefill).  JSON-serializable so a
    supervisor can persist it across real process death
    (``save_snapshot``/``load_snapshot``)."""

    finished: dict      # ridx -> [tokens] of completed requests
    inflight: dict      # ridx -> InflightState, admit order preserved
    queued: list        # ridx, FIFO order
    closed: dict        # ridx -> (status, reason) for shed/rejected
    round: int = 0

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["finished"] = {str(k): [int(t) for t in v]
                         for k, v in d["finished"].items()}
        d["inflight"] = {str(k): v for k, v in d["inflight"].items()}
        d["closed"] = {str(k): list(v) for k, v in d["closed"].items()}
        return json.dumps(d)

    @classmethod
    def from_json(cls, s: str) -> "ServeSnapshot":
        d = json.loads(s)
        return cls(
            finished={int(k): [int(t) for t in v]
                      for k, v in d["finished"].items()},
            inflight={int(k): InflightState(**v)
                      for k, v in d["inflight"].items()},
            queued=[int(r) for r in d["queued"]],
            closed={int(k): tuple(v) for k, v in d["closed"].items()},
            round=int(d["round"]),
        )


def save_snapshot(path: str, snap: ServeSnapshot) -> None:
    """Atomically persist a snapshot (tmp + fsync + ``os.replace`` — the
    same publish discipline as ``checkpoint.CheckpointManager.save``), so
    a crash mid-write never corrupts the recovery point."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(snap.to_json())
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def load_snapshot(path: str) -> Optional[ServeSnapshot]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return ServeSnapshot.from_json(f.read())


# ------------------------------------------------------------------ ladder --
class DegradationLadder:
    """Escalate on bad rounds, de-escalate after ``cooldown`` clean ones.
    ``params(chunk, k)`` maps the current rung onto effective scheduling
    parameters; rungs that don't apply (no speculation configured) are
    skipped so rung semantics stay stable."""

    def __init__(self, cfg: LadderConfig, *, has_spec: bool):
        self.cfg = cfg
        self.level = 0
        self._clean = 0
        # The actions available to this engine, in escalation order.
        self.actions = ((["halve_k", "no_spec"] if has_spec else [])
                        + ["halve_chunk", "shed_low_slo"])
        self.trace: list = []

    @property
    def max_level(self) -> int:
        return len(self.actions)

    def active(self) -> list:
        return self.actions[: self.level]

    def update(self, rnd: int, bad: bool, reason: str = "") -> None:
        if not self.cfg.enabled:
            return
        if bad:
            self._clean = 0
            if self.level < self.max_level:
                self.level += 1
                self.trace.append((rnd, self.level, reason))
        else:
            self._clean += 1
            if self.level > 0 and self._clean >= self.cfg.cooldown:
                self.level -= 1
                self._clean = 0
                self.trace.append((rnd, self.level, "recovered"))

    def params(self, chunk: int, k: Optional[int]):
        """(effective_chunk, effective_k) — ``None`` k disables
        speculation for the round."""
        active = self.active()
        if k is not None:
            if "no_spec" in active:
                k = None
            elif "halve_k" in active:
                k = max(1, k // 2)
        if "halve_chunk" in active:
            chunk = max(1, chunk // 2)
        return chunk, k

    def shedding(self) -> bool:
        return "shed_low_slo" in self.active()


# -------------------------------------------------------------- supervisor --
class ServingSupervisor:
    """Restart loop for a crashing ``ContinuousBatchingEngine``: run
    ``serve_detailed``; on ``EngineCrash``, detect the death through the
    ``runtime.fault.HeartbeatMonitor`` (the engine heartbeats every
    scheduling round; the supervisor advances the shared clock past the
    timeout, exactly how a missed-heartbeat death manifests), record the
    ``FailureEvent``, ``revive`` the host, restore the engine's last
    snapshot, and replay.  Token streams of replayed requests are
    identical to an undisturbed run (see module docstring).

    ``snapshot_path`` additionally persists every snapshot to disk
    (atomic write), and ``run`` starts from it when present — recovery
    works even when the crash takes the ENGINE OBJECT with it (a fresh
    engine + the file resumes the trace; tests/test_chaos.py exercises
    this with a new engine instance)."""

    def __init__(self, engine, *, policy: Optional[ResiliencePolicy] = None,
                 chaos: Optional[FaultInjector] = None,
                 monitor: Optional[HeartbeatMonitor] = None,
                 clock: Optional[VirtualClock] = None,
                 max_restarts: int = 8,
                 snapshot_path: Optional[str] = None):
        self.engine = engine
        self.policy = policy or ResiliencePolicy()
        self.chaos = chaos
        self.clock = clock or VirtualClock()
        self.monitor = monitor or HeartbeatMonitor(
            1, timeout_s=30.0, clock=self.clock)
        self.max_restarts = max_restarts
        self.snapshot_path = snapshot_path
        self.restarts = 0
        self.failures: list[FailureEvent] = []

    def _beat(self) -> None:
        self.monitor.beat(0)

    def run(self, requests, **serve_kw) -> ServeReport:
        policy = self.policy
        if self.snapshot_path is not None and policy.snapshot_sink is None:
            policy = dataclasses.replace(
                policy, snapshot_sink=lambda s: save_snapshot(
                    self.snapshot_path, s))
        snap = (load_snapshot(self.snapshot_path)
                if self.snapshot_path is not None else None)
        while True:
            try:
                report = self.engine.serve_detailed(
                    requests, policy=policy, chaos=self.chaos, resume=snap,
                    heartbeat=self._beat, **serve_kw)
                report.restarts = self.restarts
                report.failures = list(self.failures)
                return report
            except EngineCrash as e:
                self.restarts += 1
                # The engine stopped beating: advance the shared clock past
                # the heartbeat timeout so the monitor's sweep genuinely
                # detects the death (not just the exception we caught).
                self.clock.advance(self.monitor.timeout_s + 1.0)
                dead = self.monitor.sweep()
                assert 0 in dead or 0 in self.monitor.dead
                self.failures.append(FailureEvent(
                    0, getattr(self.engine, "last_round", -1), f"crash:{e}"))
                if self.restarts > self.max_restarts:
                    raise
                self.monitor.revive(0)
                snap = self.engine.last_snapshot
                if self.snapshot_path is not None:
                    disk = load_snapshot(self.snapshot_path)
                    if disk is not None:
                        snap = disk
