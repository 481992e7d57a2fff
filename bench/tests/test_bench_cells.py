"""Every cell of BENCHMARK.json resolves to its files by name, and the
command refuses to measure anywhere but on a known TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import check, harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    c = cells.load(name)
    ref = check.reference(c.config["reference"])
    hp = ref.hyper(c.config)
    assert hp["layers"] >= 1 and hp["vocab"] >= 1
    assert c.config["serving"]["tensor_parallel"] == c.chips
    assert c.mix["loop"] in ("open", "closed")
    assert c.limit > 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(cells.metric_reader(m["name"]))
        assert m["moves"] in names


def test_every_config_and_metric_is_used():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert set(c["reduced"]) <= set(conf["changed"])
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cells.load("no-such-cell")


def test_a_cell_without_its_limit_file_is_refused(tmp_path):
    spec = dict(SPEC, workloads=[dict(SPEC["workloads"][0],
                                      name="qwen2-1.5b.no-limit")])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(FileNotFoundError):
        cells.load("qwen2-1.5b.no-limit", path)


def test_unknown_device_kind_raises():
    with pytest.raises(harness.NoChip):
        harness.peaks_for("TPU v9 imaginary")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_s"] == 819e9


def test_run_exits_nonzero_on_the_cpu_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""
