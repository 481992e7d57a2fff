"""Benchmark harness: one function per paper table/figure + kernel micro +
roofline report.  Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))  # make `benchmarks` importable as a package


def main() -> None:
    from benchmarks import decode_bench, kernel_bench, paper_tables, roofline_report
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    suites = (paper_tables.ALL + kernel_bench.ALL + roofline_report.ALL
              + decode_bench.ALL)
    print("name,us_per_call,derived")
    failures = 0
    for suite in suites:
        try:
            for name, us, derived in suite():
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{suite.__name__}/ERROR,0.00,{type(e).__name__}: {e}")
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
