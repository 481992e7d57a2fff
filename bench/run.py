"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names its
configuration and traffic files.  The run builds the configuration's engine
over weights drawn from the seed, warms up exactly the cell's shapes (that is
``setup_s``, counted from process start), serves the window's schedule
through ``ContinuousBatchingEngine.serve_detailed``, checks a sample of the
served tokens against the plain reference, and prints one JSON object as the
last line of standard output: the cell's end-to-end metrics (``--trace 0``)
or its per-layer metrics from a profiler trace of part of the window
(``--trace 1``).  The numbers compared and their limits are the last lines
of standard error.  Without a TPU, or on a device kind that
``bench/peaks.json`` lacks, it exits non-zero and prints no result.
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

from bench import cell as cells  # noqa: E402
from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.load(args.workload)
        result, out, err = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except (harness.NoChip, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in out:
        print(line)
    sys.stdout.flush()
    for line in err:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
