"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration (``bench/configs/<file>.json``, with its plain
reference ``bench/configs/<reference>.py`` beside it) and a traffic mix
(``bench/traffic/<traffic>.json``); its correctness limit, set from that
model's readings under that mix, is ``bench/limits/<cell>.json``; its
per-layer metrics are readers ``bench/metrics/<metric>.py``.  Adding a cell,
a configuration, a mix or a metric adds files and entries; no code here
names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclass
class Cell:
    name: str
    chips: int
    config: dict      # the configuration file
    mix: dict         # the traffic file
    end_to_end: list  # BENCHMARK.json metric entries that apply to this cell
    per_layer: list
    limit: float | None = None  # widest gap that still counts as correct


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}")
    wl = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((ROOT / conf_entry["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text())
    limit = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return Cell(
        name=name, chips=int(wl["chips"]), config=config, mix=mix,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        limit=float(limit["max_gap"]))


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
