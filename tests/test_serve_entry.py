"""The serving entry point (``launch.serve``), the full-width parameter
builder (``serving.init_serving_params``), the compile-cache helper, the
benches' --devices runner, and ``chip_smoke.py``'s refusal to run off-TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch import compile_cache
from repro.launch.serve import main as serve_main
from repro.launch.serve import make_requests, serve
from repro.models import init_params
from repro.serving import (
    ContinuousBatchingEngine,
    init_serving_params,
    quantize_tree,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
from bench_subproc import run_devices_axis  # noqa: E402


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape, path
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "starcoder2-7b"])
def test_init_serving_params_equals_quantized_init(arch, bits):
    """The layer-by-layer full-width builder draws the same model as
    quantizing ``init_params``' tree (bfloat16 weights, as at full width)."""
    cfg = get_reduced(arch).replace(param_dtype="bfloat16")
    key = jax.random.PRNGKey(3)
    want = quantize_tree(init_params(cfg, key), bits)
    _assert_trees_equal(init_serving_params(cfg, key, bits=bits), want)


def test_quantize_tree_leaves_quantized_leaves_alone():
    cfg = get_reduced("qwen2-1.5b")
    q = quantize_tree(init_params(cfg, jax.random.PRNGKey(0)), 8)
    _assert_trees_equal(quantize_tree(q, 8), q)


def test_serve_reduced_finishes_every_request_like_the_engine():
    """``serve`` = the engine on ``quantize_tree(init_params)`` weights,
    and the engine hands back its compiled decode chunk."""
    arch = "qwen2-1.5b"
    cfg = get_reduced(arch)
    reqs = make_requests(cfg.vocab, [8, 16, 8], [5, 3, 6], seed=1)
    run = serve(arch, reqs, reduced=True, slots=2)
    got = [r.tokens.tolist() for r in run.report.records]
    assert [r.status for r in run.report.records] == ["done"] * 3
    assert [len(t) for t in got] == [5, 3, 6]
    assert run.engine.nonfinite_logits == 0
    assert "HloModule" in run.engine.decode_chunk_text()
    eng = ContinuousBatchingEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), slots=2,
        max_seq=run.engine.max_seq, pim_bits=8)
    assert [t.tolist() for t in eng.serve(reqs)] == got


def test_nonfinite_logits_are_counted():
    cfg = get_reduced("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    params["ln_f"] = jnp.full_like(params["ln_f"], jnp.nan)
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=2)
    eng.serve(make_requests(cfg.vocab, [4, 4], [3, 3]))
    # 2 admit rows + 2 live decode steps per request
    assert eng.nonfinite_logits == 6


def test_serve_cli_reduced(capsys, monkeypatch, tmp_path):
    # With the variable set, the CLI leaves the compile cache config alone,
    # so this worker's later tests do not write to a persistent cache.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    serve_main(["--arch", "qwen2-1.5b", "--reduced", "--requests", "2",
                "--prompt-len", "8", "--new-tokens", "4"])
    out = capsys.readouterr().out
    assert "served 8 tokens" in out and "(reduced)" in out


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_devices_axis_runs_in_process_when_devices_suffice():
    jax.devices()  # this process holds a backend
    assert run_devices_axis("unused.py", [], 1, lambda: {"here": 1}) == {
        "here": 1}


def test_devices_axis_refuses_a_child_once_a_backend_is_held():
    jax.devices()
    with pytest.raises(RuntimeError, match="could not take them"):
        run_devices_axis("unused.py", [], len(jax.devices()) + 1,
                         lambda: pytest.fail("ran with too few devices"))


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_on_cpu_and_names_the_platform():
    r = _run_smoke(REPO, "chip_smoke.py")
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_smoke(str(tmp_path), "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


SHARDED_SERVE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from repro.configs import get_reduced
from repro.launch.serve import make_requests, serve
from repro.serving import make_decode_mesh, pim_bytes
cfg = get_reduced("starcoder2-7b")
reqs = make_requests(cfg.vocab, [8, 16, 8, 16], [6, 4, 5, 6], seed=2)
kw = dict(reduced=True, slots=2)
one = serve("starcoder2-7b", reqs, **kw)
four = serve("starcoder2-7b", reqs, mesh=make_decode_mesh(4), **kw)
tok = lambda run: [r.tokens.tolist() for r in run.report.records]
print(json.dumps({"same": tok(one) == tok(four),
                  "done": [r.status for r in four.report.records],
                  "total": pim_bytes(four.engine.params),
                  "per_device": pim_bytes(four.engine.params, per_device=True),
                  "tp": "tp" in four.engine.params["layers"]["mlp"]["gate"]}))
"""


def test_serve_over_a_4_device_mesh_matches_one_device():
    """Weights built straight onto the mesh: same greedy tokens as one
    device, split codes (tp markers), fewer bytes per device."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", SHARDED_SERVE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["same"] and out["tp"]
    assert out["done"] == ["done"] * 4
    assert out["per_device"] < 0.75 * out["total"]
