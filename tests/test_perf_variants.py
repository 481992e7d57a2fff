"""Perf-variant knobs lower correctly on a multi-device mesh (subprocess)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.launch.steps import lower_cell
from repro.launch.roofline import analyze

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_reduced("qwen2-1.5b")
out = {}
for vname, spec in [
    ("baseline", {}),
    ("no_fsdp", {"fsdp": False}),
    ("pim4", {"pim_bits": 4}),
    ("no_remat", {"remat": False}),
]:
    sc = ShapeConfig("train_t" if vname == "no_remat" else "decode_t", 64, 8,
                     "train" if vname == "no_remat" else "decode")
    cell = lower_cell(cfg, sc, mesh, variant=spec)
    roof = analyze(cell, cfg, sc)
    out[vname] = {"coll": roof.collective_bytes, "bytes": roof.hlo_bytes}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_variants_lower_and_change_artifacts():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SNIPPET], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    # no_fsdp must reduce decode collective bytes vs baseline
    assert out["no_fsdp"]["coll"] < out["baseline"]["coll"]
    # all variants produced nonzero analyses
    for v in out.values():
        assert v["bytes"] > 0
