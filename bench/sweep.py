"""Find the knee of a configuration under a traffic mix, once, by a sweep.

Builds the engine once and serves the mix at each offered rate for
``--seconds``, in one process.  Prints one JSON line per rate: the
end-to-end metrics, how far the last request finished past the window
(``drain_s``) and the median time to first token of the first and last
thirds of the arrivals.  A rate the system sustains drains in about one
answer's time and keeps the last third's median near the first's; past the
knee the queue grows all through the window.  The rates run in the order
given, and the sweep stops after the first rate that is not sustained:
its last third waits more than ``STOP_RATIO`` times as long as its first
third.  The chosen rate (0.8 x the highest sustained) is written into the
mix file as a number.

    python bench/sweep.py --config qwen2-1.5b --traffic chat \\
        --rates 2,3,4,5,6 --seconds 20 --seed 11
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp

import numpy as np  # noqa: E402

from bench import e2e, harness, serve, traffic  # noqa: E402
from bench.cell import BENCH, Cell  # noqa: E402

STOP_RATIO = 5.0


def _load(name: str, folder: str) -> dict:
    p = Path(name)
    if not name.endswith(".json"):
        p = BENCH / folder / f"{name}.json"
    return json.loads(p.read_text())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    conf, mix = _load(args.config, "configs"), _load(args.traffic, "traffic")
    rates = [float(r) for r in args.rates.split(",")]
    devices, _ = harness.require_chip(int(conf["serving"]["tensor_parallel"]))
    harness.enable_cache()
    dev = harness.device_fields(devices)
    # Warm every length of the grid: the rates' schedules may differ in
    # which lengths they draw.
    full = dict(mix, loop="open", rate_rps=max(rates))
    cell = Cell("sweep", 1, conf, full, [], [])
    st = harness.set_up(cell, args.seed, args.seconds, devices)
    grid = set(mix["prompt"]["grid"]) - {it.prompt_len for it in st.items}
    if grid:
        rng = np.random.default_rng(0)
        serve.warm_up(st.engine, {n: rng.integers(0, st.hp["vocab"], n,
                                                  dtype=np.int32)
                                  for n in grid}, st.key, st.serving["chunk"])
    print(json.dumps({"setup_s": time.monotonic() - T_START, "device": dev}),
          flush=True)
    for k, rate in enumerate(rates):
        m = dict(mix, rate_rps=rate)
        items = traffic.schedule(m, args.seconds, args.seed + k)
        prompts = traffic.prompts(items, args.seed + k, st.hp["vocab"])
        reqs = serve.requests(items, prompts, m, args.seconds)
        st.clock.t0 = None
        t0 = time.monotonic()
        report = serve.serve(st.engine, reqs, st.key)
        wall = time.monotonic() - t0
        out = e2e.measure(report, items, m)
        recs = report.records
        ttft = [recs[i].t_first - items[i].arrival for i in range(len(items))
                if recs[i].t_first is not None]
        third = max(1, len(ttft) // 3)
        out.update(
            rate_rps=rate, requests=len(items), wall_s=wall,
            drain_s=max(r.t_done or 0 for r in recs) - args.seconds,
            ttft_first_third_ms=float(np.median(ttft[:third])) * 1e3,
            ttft_last_third_ms=float(np.median(ttft[-third:])) * 1e3,
            offered_tok_s=sum(it.max_new for it in items) / args.seconds,
            device=dev)
        print(json.dumps(out), flush=True)
        wait_ratio = out["ttft_last_third_ms"] / out["ttft_first_third_ms"]
        if wait_ratio > STOP_RATIO:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
