"""Launch layer: sharding rules, lowering, dry-run (subprocess, 8 devices)."""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_reduced
from repro.launch.sharding import param_spec, sanitize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------- sharding rules ---
def test_param_spec_attention():
    assert param_spec(["layers", "attn", "wq"], 3, "data") == P(None, "data", "model")
    assert param_spec(["layers", "attn", "wo"], 3, "data") == P(None, "model", "data")


def test_param_spec_moe_vs_mlp():
    moe = param_spec(["layers", "moe", "gate"], 4, "data")
    mlp = param_spec(["layers", "mlp", "gate"], 3, "data")
    assert moe == P(None, "model", "data", None)  # experts over model (EP)
    assert mlp == P(None, "data", "model")


def test_param_spec_embed_vocab_sharded():
    assert param_spec(["embed"], 2, "data") == P("model", None)


def test_sanitize_drops_indivisible():
    mesh = jax.make_mesh((1,), ("model",))  # 1-device 'model' axis
    sh = {"w": NamedSharding(mesh, P("model", None))}
    shapes = {"w": jax.ShapeDtypeStruct((7, 4), jax.numpy.float32)}
    out = sanitize(sh, shapes)
    # 7 % 1 == 0 so kept; now with a fake bigger axis we can't build on 1 CPU,
    # so test divisibility logic directly on dim < axis size via size-1 dim
    shapes2 = {"w": jax.ShapeDtypeStruct((0, 4), jax.numpy.float32)}
    assert out["w"].spec[0] == "model"


DRYRUN_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.launch.steps import lower_cell
from repro.launch.roofline import analyze

mesh = make_mesh((2, 4), ("data", "model"))
out = []
for arch in sys.argv[1].split(","):
    cfg = get_reduced(arch)
    for sc in [ShapeConfig("train_t", 64, 8, "train"),
               ShapeConfig("prefill_t", 64, 8, "prefill"),
               ShapeConfig("decode_t", 64, 8, "decode")]:
        cell = lower_cell(cfg, sc, mesh)
        roof = analyze(cell, cfg, sc)
        out.append({
            "arch": arch, "shape": sc.name, "flops": roof.hlo_flops,
            "coll": roof.collective_bytes, "bottleneck": roof.bottleneck,
        })
print("RESULT " + json.dumps(out))
"""


def _run_snippet(archs: str):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", DRYRUN_SNIPPET, archs],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_lower_compile_all_kinds_multidevice():
    """Reduced configs of three families lower + compile on a 2x4 mesh with
    real collectives present (integration version of the 512-dev dry-run)."""
    rows = _run_snippet("qwen2-1.5b,deepseek-v2-lite-16b,falcon-mamba-7b")
    assert len(rows) == 9
    for r in rows:
        assert r["flops"] > 0, r
    # sharded training must communicate
    train_rows = [r for r in rows if r["shape"] == "train_t"]
    assert all(r["coll"] > 0 for r in train_rows)


def test_lower_cell_single_device_mesh():
    """lower_cell works on the 1-device mesh (no subprocess)."""
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import lower_cell

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_reduced("qwen2-1.5b")
    cell = lower_cell(cfg, ShapeConfig("t", 32, 2, "train"), mesh)
    assert cell.lowered is not None
    compiled = cell.lowered.compile()
    assert compiled.cost_analysis() is not None
