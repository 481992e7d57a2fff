"""Record on the chip the small trace that ``bench/tests/test_bench_spans.py``
reads.

The cell's engine, over the benchmark's weights, serves two requests that
arrive together, so that one round admits both and two decode rounds
follow, under the profiler.  The slice from the first round's schedule to
the last round's emit is written, gzipped, with the program's op scopes and
host spans (``bench/spans.py``) and the engine's request events and spans:

    python3 bench/record_slice.py --workload qwen2-1.5b.chat --seed 7 \\
        --out bench/tests/data/trace_qwen2_spans.json.gz
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import cell as cells  # noqa: E402
from bench import check, harness, serve, spans, traffic, weights  # noqa: E402
from bench import trace as tr  # noqa: E402

LENGTHS = (256, 1024)  # the two admits' prompt lengths
MARGIN = 1e-3          # seconds kept before the first span and after the last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax

    from repro.serving import Request

    cell = cells.load(args.workload)
    devices, _ = harness.require_chip(cell.chips)
    devices = devices[:cell.chips]
    harness.enable_cache()
    ref = check.reference(cell.config["reference"])
    hp = ref.hyper(cell.config)
    cfg = serve.model_config(cell.config, hp)
    mesh = serve.mesh_for(cell.config)
    params = weights.make(cfg, args.seed, mesh=mesh,
                          roles=getattr(ref, "LEAF_ROLES", None))
    clock = serve.Clock()
    eng = serve.engine(cfg, params, cell.config, cell.mix, mesh, clock)
    chunk = int(cell.config["serving"]["chunk"])
    items = [traffic.Item(0.0, n, 2 * chunk) for n in LENGTHS]
    prompts = traffic.prompts(items, args.seed, hp["vocab"])
    key = weights.base_key(args.seed)
    serve.warm_up(eng, dict(zip(LENGTHS, prompts)), key, chunk)
    reqs = [Request(prompt=p, max_new=it.max_new)
            for it, p in zip(items, prompts)]

    if harness.TRACE_DIR.exists():
        shutil.rmtree(harness.TRACE_DIR)
    info = {}

    def on():
        jax.profiler.start_trace(str(harness.TRACE_DIR))
        info["wall_ns"] = time.time_ns()
        info["on"] = time.monotonic() - clock.t0

    clock.t0, clock.hooks = None, [(0.0, on)]
    report = eng.serve_detailed(reqs, key=key, policy=serve.policy())
    jax.block_until_ready(eng._cache)
    info["off"] = time.monotonic() - clock.t0
    jax.profiler.stop_trace()

    t = tr.load(harness.TRACE_DIR, info, devices)
    sc = spans.load(harness.TRACE_DIR, t)
    a = min(h[1] for h in sc.host if h[0] == "engine.schedule") - MARGIN
    b = max(h[2] for h in sc.host if h[0] == "engine.emit") + MARGIN
    keep = ("pim_matvec", "pim_matmul")
    out = {"trace": t.to_json(a, b, keep=keep), "scoped": sc.to_json(t, a, b),
           "records": [{"prompt_len": it.prompt_len, "max_new": it.max_new,
                        "events": r.events}
                       for it, r in zip(items, report.records)],
           "spans": report.spans}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(out, f)
    print(json.dumps({"slice": [a, b], "rounds": report.rounds,
                      "host_spans": len(out["scoped"]["host"]),
                      "ops": [len(c["ops"]) for c in out["trace"]["chips"]],
                      "out": args.out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
