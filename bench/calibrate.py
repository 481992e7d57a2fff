"""The readings a cell's correctness limit is set from, for many seeds in one
process (the benchmark's own runs never run this).

For each seed: the seed's weights and traffic, a window of ``--seconds`` at
the cell's load through the engine, and the same sample of finished requests
that a run checks.  On that sample it reads the program's widest gap (what a
run compares with its limit) and the control's: the reference, put in the
program's place at the next precision down (float8 activations where the
configuration states bfloat16), read at every position of the same prompts
and served tokens.  The limit goes between the largest program reading and
the smallest control reading (PERF.md).  One JSON line per seed.

    python bench/calibrate.py --workload qwen2-1.5b.chat --seeds 101-112 \\
        --seconds 10
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp

from bench import cell as cells  # noqa: E402
from bench import check, e2e, harness  # noqa: E402


def seeds_of(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def readings(st, report, seed: int) -> dict:
    picks = check.sample(report.records, st.items, seed, harness.SAMPLE)
    prog, ctrl, toks = [], [], 0
    for i in picks:
        served = report.records[i].tokens
        p, c = check.with_control(st.ref, st.params, st.hp, st.prompts[i],
                                  served, check.fp8)
        prog.append(float(p.max()))
        ctrl.append(float(c.max()))
        toks += len(served)
    return {"program_max_gap": max(prog), "control_max_gap": max(ctrl),
            "program_gaps": prog, "control_gaps": ctrl, "tokens": toks,
            "requests": picks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    devices, _ = harness.require_chip(cell.chips)
    harness.enable_cache()
    devices = devices[:cell.chips]
    counter = harness.CompileCounter()
    for seed in seeds_of(args.seeds):
        t0 = time.monotonic()
        st = harness.set_up(cell, seed, args.seconds, devices)
        report, _ = harness.window(st, args.seconds, False, counter)
        m = e2e.measure(report, st.items, st.mix)
        harness.free_engine(st)
        t1 = time.monotonic()
        out = {"seed": seed, "attempted": m["attempted"],
               "failed": m["failed"], **readings(st, report, seed),
               "serve_s": t1 - t0, "check_s": time.monotonic() - t1,
               "limit": cell.limit,
               "device": harness.device_fields(devices)}
        print(json.dumps(out), flush=True)
        del st, report
        gc.collect()
    print(json.dumps({"total_s": time.monotonic() - T_START,
                      "compiles_in_windows": counter.count,
                      "device": harness.device_fields(devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
