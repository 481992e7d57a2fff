"""The system under test, as the benchmark drives it: the configuration's
engine over the benchmark's weights, a warm-up of exactly the cell's shapes,
and one ``serve_detailed`` call over the window's schedule."""
from __future__ import annotations

import sys
import time

from .cell import ROOT

sys.path.insert(0, str(ROOT / "src"))


def model_config(conf: dict, hp: dict):
    """The program's ``ModelConfig`` for the configuration file: the
    registry's entry with every size the file states."""
    from repro.configs.registry import get_config

    base = get_config(conf["serving"]["registry_id"])
    if base.family != "dense":
        raise ValueError("the benchmark drives dense decoders")
    return base.replace(
        n_layers=hp["layers"], d_model=hp["d_model"], n_heads=hp["heads"],
        n_kv_heads=hp["kv_heads"], head_dim=hp["head_dim"], d_ff=hp["d_ff"],
        vocab=hp["vocab"], qkv_bias=hp["qkv_bias"],
        rope_theta=hp["rope_theta"], norm_eps=hp["norm_eps"],
        tie_embeddings=hp["tied"], param_dtype=conf["torch_dtype"], pim_bits=8,
        kv_cache_bits=16)


def mesh_for(conf: dict):
    from repro.serving import make_decode_mesh

    tp = int(conf["serving"]["tensor_parallel"])
    return None if tp == 1 else make_decode_mesh(tp)


class Clock:
    """The engine's clock (``time.monotonic``), which also remembers when
    the serve call started and can run hooks at set engine times (the traced
    run starts and stops the profiler from it)."""

    def __init__(self):
        self.t0 = None
        self.hooks = []  # (engine seconds, callable), in order

    def __call__(self) -> float:
        t = time.monotonic()
        if self.t0 is None:
            self.t0 = t
        while self.hooks and t - self.t0 >= self.hooks[0][0]:
            self.hooks.pop(0)[1]()
        return t


def engine(cfg, params, conf: dict, mix: dict, mesh, clock):
    from repro.serving import ContinuousBatchingEngine

    s = conf["serving"]
    return ContinuousBatchingEngine(
        cfg, params, slots=s["slots"], max_seq=mix["max_seq"],
        page_size=s["page_size"], chunk=s["chunk"], mesh=mesh, clock=clock)


def policy():
    """Gates arrivals and nothing else (no backoff, snapshots, ladder or
    validation); queued deadlines are the closed loop's stop."""
    from repro.serving import LadderConfig, ResiliencePolicy

    return ResiliencePolicy(backoff_s=0.0, round_time=0.0, snapshot_every=0,
                            ladder=LadderConfig(enabled=False),
                            validate=False, shed_expired=True)


def requests(items, prompts, mix: dict, seconds: float):
    from repro.serving import Request

    stop = seconds if mix["loop"] == "closed" else None
    return [Request(prompt=p, max_new=it.max_new, arrival=it.arrival,
                    deadline=stop) for it, p in zip(items, prompts)]


def warm_up(eng, prompts_by_len: dict, key, chunk: int):
    """Compile and run once every program the window uses: the admit of each
    prompt length of the schedule, both into the fresh cache a serve call
    starts with and into one a program has written (on a mesh the two are
    placed differently, so each compiles its own admit), and the decode
    chunk."""
    from repro.serving import Request

    for _, p in sorted(prompts_by_len.items()):
        eng.serve_detailed([Request(prompt=p, max_new=chunk + 2)] * 2,
                           key=key, policy=policy())


def idle_wait(eng, reqs):
    """The serve call's heartbeat, run at the start of every round: while
    no request is live and the queue's head has not arrived, sleep until it
    arrives, as a server waits for its next request.  Without it the engine
    spins through idle rounds, and a head that arrives between a round's
    admission check and its idle check is shed as ``"oom"`` with the pool
    empty, a race whose count differs from run to run."""

    def beat():
        while eng._queue and not any(r >= 0 for r in eng._slot_req):
            wait = reqs[eng._queue[0]].arrival - eng._now()
            if wait <= 0:
                return
            time.sleep(wait)

    return beat


def serve(eng, reqs, key):
    return eng.serve_detailed(reqs, key=key, policy=policy(),
                              heartbeat=idle_wait(eng, reqs))
