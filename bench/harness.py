"""One run of one cell: set-up, the measured window, the check, the traced
reduction.  ``run.py`` is its command line; the tests drive ``run_cell`` on
the CPU with ``require=False``."""
from __future__ import annotations

import gc
import json
import os
import shutil
import time
from types import SimpleNamespace

from . import check, e2e, readers, serve, traffic, work
from . import trace as tr
from .cell import BENCH, ROOT, Cell, metric_reader

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 6.0   # length of the traced part of a --trace 1 window
TRACE_AT = 0.3        # ... which starts this far into the window
SAMPLE = 4            # requests the reference checks after each window


class NoChip(RuntimeError):
    pass


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def require_chip(chips: int):
    """The devices of a TPU with at least ``chips`` chips, and its peaks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs, peaks_for(devs[0].device_kind)


def device_fields(devices) -> dict:
    """The platform, kind and count of the devices a run measured on."""
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept, so only a checkout's first run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    enable_compile_cache()


class CompileCounter:
    """Counts programs lowered (each new jit signature, compiled or read
    from the persistent cache) while armed."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


def set_up(cell: Cell, seed: int, seconds: float, devices):
    """Weights, engine and warm-up; returns the state the window uses."""
    import jax

    from . import weights

    marks = [("start", time.monotonic())]
    ref = check.reference(cell.config["reference"])
    hp = ref.hyper(cell.config)
    cfg = serve.model_config(cell.config, hp)
    mesh = serve.mesh_for(cell.config)
    params = weights.make(cfg, seed, mesh=mesh,
                          roles=getattr(ref, "LEAF_ROLES", None))
    marks.append(("weights", time.monotonic()))
    clock = serve.Clock()
    eng = serve.engine(cfg, params, cell.config, cell.mix, mesh, clock)
    items = traffic.schedule(cell.mix, seconds, seed)
    prompts = traffic.prompts(items, seed, hp["vocab"])
    by_len = {}
    for it, p in zip(items, prompts):
        by_len.setdefault(it.prompt_len, p)
    key = weights.base_key(seed)
    serve.warm_up(eng, by_len, key, cell.config["serving"]["chunk"])
    jax.block_until_ready(eng._cache)
    marks.append(("warm_up", time.monotonic()))
    return SimpleNamespace(marks=marks, ref=ref, hp=hp, cfg=cfg, mesh=mesh, params=params,
                           clock=clock, engine=eng, items=items,
                           prompts=prompts, key=key, devices=devices,
                           mix=cell.mix, serving=cell.config["serving"])


def _trace_hooks(st, seconds: float):
    import jax

    info = {}
    start = TRACE_AT * seconds
    length = min(TRACE_SECONDS, seconds - start)
    if TRACE_DIR.exists():
        shutil.rmtree(TRACE_DIR)

    def on():
        jax.profiler.start_trace(str(TRACE_DIR))
        info["wall_ns"], info["mono"] = time.time_ns(), time.monotonic()
        info["on"] = info["mono"] - st.clock.t0

    def off():
        info["off"] = time.monotonic() - st.clock.t0
        jax.profiler.stop_trace()

    st.clock.hooks = [(start, on), (start + length, off)]
    return info


def window(st, seconds: float, trace: bool, counter: CompileCounter):
    import jax

    reqs = serve.requests(st.items, st.prompts, st.mix, seconds)
    st.clock.t0 = None
    info = _trace_hooks(st, seconds) if trace else None
    counter.armed = True
    report = serve.serve(st.engine, reqs, st.key)
    jax.block_until_ready(st.engine._cache)
    counter.armed = False
    if trace and "on" in info and "off" not in info:
        info["off"] = time.monotonic() - st.clock.t0
        jax.profiler.stop_trace()
    return report, info


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free_engine(st):
    """Drop the program's state (engine, cache) before the reference runs;
    the benchmark's weights stay for it."""
    st.engine = None
    gc.collect()


def correctness(st, report, seed: int, limit: float) -> dict:
    """The widest gap of a sample of served tokens (``bench/check.py``)."""
    picks = check.sample(report.records, st.items, seed, SAMPLE)
    worst, n_tok = 0.0, 0
    for i in picks:
        g = check.gaps(st.ref, st.params, st.hp, st.prompts[i],
                       report.records[i].tokens)
        worst = max(worst, float(g.max()))
        n_tok += len(g)
    return {"max_gap": worst, "limit": limit, "requests": len(picks),
            "tokens": n_tok, "ok": bool(picks) and worst <= limit}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require: bool = True):
    """Everything one run does; returns (result line, stdout lines,
    stderr check lines).  ``require=False`` skips the look for a chip and
    the persistent cache (the tests' rehearsal on the CPU)."""
    import jax

    if require:
        devices, peaks = require_chip(cell.chips)
        enable_cache()
    else:
        devices, peaks = jax.devices(), peaks_for("TPU v5 lite")
    devices = devices[:cell.chips]
    counter = CompileCounter()
    st = set_up(cell, seed, seconds, devices)
    setup_s = time.monotonic() - t_start
    report, info = window(st, seconds, trace, counter)
    if st.mix["loop"] == "closed" and not any(
            r.status == "shed" for r in report.records):
        raise RuntimeError("the closed loop ran out of requests before the "
                           "window ended: lower min_request_s in the mix")
    measured = e2e.measure(report, st.items, st.mix)
    mem = memory_peak(devices)
    free_engine(st)
    chk = correctness(st, report, seed, cell.limit)
    device = dict(device_fields(devices), memory_peak_bytes=mem)
    metrics, breakdown, diag = {}, None, []
    if trace:
        t = tr.load(TRACE_DIR, info, devices)
        ctx = SimpleNamespace(trace=t, report=report, items=st.items,
                              hp=st.hp, serving=st.serving, peaks=peaks,
                              chips=len(devices), work=work)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        for kernel, programs in (("pim_matvec", tr.DECODE),
                                 ("pim_matmul", tr.ADMIT)):
            for row in readers.kernel_table(ctx, kernel, programs):
                diag.append(f"kernel {kernel} {json.dumps(row)}")
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.window_s
        breakdown = t.breakdown(report)
    else:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else measured[m["name"]]
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": chk["ok"], "attempted": measured["attempted"],
              "failed": measured["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {"max_gap": {"value": chk["max_gap"],
                                    "limit": chk["limit"]}}
    shed = sorted({r.reason for r in report.records if r.status == "shed"})
    (_, t0), *rest = st.marks
    parts = [f"{name} {t - t_prev}" for (name, t), (_, t_prev)
             in zip(rest, st.marks)]
    out = [f"compiles_in_window {counter.count} {counter.names}",
           f"setup_s {setup_s}: start-up {t0 - t_start}, " + ", ".join(parts),
           f"window requests {len(st.items)} attempted "
           f"{measured['attempted']} failed {measured['failed']} "
           f"shed reasons {shed}",
           f"checked {chk['requests']} requests, {chk['tokens']} tokens",
           *diag]
    err = [f"check max_gap {chk['max_gap']} limit {chk['limit']}"]
    return result, out, err
