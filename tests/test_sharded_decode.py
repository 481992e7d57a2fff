"""Tensor-sharded decode (serving.sharded).

Spec derivation: decode-time PartitionSpecs are DERIVED from the train-time
``launch.sharding.param_spec`` rules (cross-checked per family below, so the
two rule sets cannot silently diverge), with 'model' always on the output
dim — the only placement whose all-gather is a pure concatenation and
therefore token-exact.

Parity: sharded greedy decode must be TOKEN-IDENTICAL to the single-device
engines for all six families, in both ``ServingEngine.generate`` and
``ContinuousBatchingEngine.serve`` (paged cache included).  The 8-device
checks run in a subprocess with ``--xla_force_host_platform_device_count=8``
(the repo's established multi-device test idiom — see test_launch.py);
mesh-size-1 parity runs in-process so the shard_map plumbing is exercised in
every tier-1 run regardless of device count.

Admit path: the direct page-write prefill must produce byte-identical caches
to the retired dense round-trip, for which ``models.paged_insert`` survives
as the reference implementation.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import FAMILY_ARCHS
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.launch.sharding import (
    paged_cache_pspecs,
    paged_cache_shardings,
    param_spec,
)
from repro.models import (
    init_cache,
    init_paged_cache,
    init_params,
    paged_insert,
    prefill,
)
from repro.quant import decode_partition_spec
from repro.serving import (
    ContinuousBatchingEngine,
    ServingEngine,
    make_decode_mesh,
    pim_bytes,
    quantize_tree,
    shard_quantized_tree,
    tree_pspecs,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Which quantized leaves the decode rule distributes, per family — the
# leaves the TRAIN rule shards somewhere (TP or FSDP).  x_proj is the one
# quantized-but-replicated leaf: param_spec replicates it at train time too.
SHARDED_LEAVES = {
    "qwen2-1.5b": {"wq", "wk", "wv", "wo", "gate", "up", "down"},
    "deepseek-v2-lite-16b": {"wq", "wk", "wv", "wo", "gate", "up", "down",
                             "head", "w_dkv", "w_uk", "w_uv"},
    "moonshot-v1-16b-a3b": {"wq", "wk", "wv", "wo", "gate", "up", "down",
                            "head"},
    "falcon-mamba-7b": {"in_proj", "out_proj", "head"},
    "zamba2-1.2b": {"wq", "wk", "wv", "wo", "gate", "up", "down", "head",
                    "in_proj", "out_proj"},
    "llama-3.2-vision-90b": {"wq", "wk", "wv", "wo", "gate", "up", "down",
                             "head"},
    "seamless-m4t-medium": {"wq", "wk", "wv", "wo", "gate", "up", "down",
                            "head"},
}
REPLICATED_QUANTIZED = {"falcon-mamba-7b": {"x_proj"}}


def _qleaves(arch):
    cfg = get_reduced(arch)
    q = quantize_tree(init_params(cfg, jax.random.PRNGKey(0)), 8)
    out = []

    def walk(t, names):
        if isinstance(t, dict) and "codes" in t:
            out.append((names, t))
        elif isinstance(t, dict):
            for k, v in t.items():
                walk(v, names + [k])

    walk(q, [])
    return out


# ------------------------------------------------------- spec derivation ----
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_decode_spec_cross_checks_train_rule(arch):
    """Per family: the decode rule shards exactly the leaves the train rule
    shards somewhere (golden set), always on the last dim; every other
    quantized leaf replicates.  Drift in param_spec shows up here."""
    sharded, repl = set(), set()
    for names, leaf in _qleaves(arch):
        spec = decode_partition_spec(names, leaf["codes"].ndim)
        if "model" in spec:
            assert spec[-1] == "model" and spec[:-1] == (None,) * (len(spec) - 1)
            sharded.add(names[-1])
        else:
            repl.add(names[-1])
        # cross-check: sharded at decode <=> train-time spec is non-trivial
        train = param_spec(names, leaf["codes"].ndim, "fsdp")
        assert ("model" in spec) == any(e is not None for e in train)
    assert sharded == SHARDED_LEAVES[arch]
    assert repl == REPLICATED_QUANTIZED.get(arch, set())


def test_decode_spec_replicates_non_weight_leaves():
    for name in ("router", "x_proj", "dt_proj", "conv_w", "ln1"):
        assert decode_partition_spec(["layers", name], 2) == P(None, None)


# ------------------------------------------------- marker / pspec plumbing --
def test_shard_tree_markers_and_pspecs():
    """codes+scale+markers travel together: tp-marked leaves shard codes AND
    scale on their last dim, markers replicate and carry the stack dims so
    lax.scan can slice them; pim_bytes never counts markers."""
    mesh = make_decode_mesh(1)
    cfg = get_reduced("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    q4 = shard_quantized_tree(quantize_tree(params, 4), mesh)
    wq = q4["layers"]["attn"]["wq"]
    assert "tp" in wq and "nibbles" in wq
    assert wq["tp"].shape == wq["codes"].shape[:-2]  # scan-sliceable
    specs = tree_pspecs(q4)
    swq = specs["layers"]["attn"]["wq"]
    assert swq["codes"][-1] == "model" and swq["scale"][-1] == "model"
    assert swq["tp"] == P() and swq["nibbles"] == P()
    assert specs["embed"] == P()  # dense leaves replicate
    # markers excluded from byte accounting; 1-device mesh: per-device == total
    assert pim_bytes(q4) == pim_bytes(q4, per_device=True)
    n_markers = sum(leaf.size for path, leaf in
                    jax.tree_util.tree_leaves_with_path(q4)
                    if str(getattr(path[-1], "key", "")) in
                    ("tp", "nibbles", "nibbles_odd"))
    assert n_markers > 0
    total = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(q4))
    assert pim_bytes(q4) < total  # markers really were excluded


def test_mesh1_trivially_divides():
    """On the 1-device mesh every output dim divides, so even an odd-width
    rule-shardable leaf gets marked (the true indivisible branch needs a
    wider mesh — asserted on 8 devices in the subprocess extras test)."""
    mesh = make_decode_mesh(1)
    q = quantize_tree({"layers": {"attn": {"wq": jnp.zeros((16, 9))}}}, 8)
    t = shard_quantized_tree(q, mesh)
    assert "tp" in t["layers"]["attn"]["wq"]  # 9 % 1 == 0: mesh-1 shards


# ------------------------------------------------------ mesh-size-1 parity --
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "falcon-mamba-7b"])
def test_mesh1_parity_both_engines(arch):
    """shard_map plumbing end-to-end on the always-available 1-device mesh:
    tokens identical to the plain engines (the 8-device version of this
    runs in the subprocess tests below)."""
    mesh = make_decode_mesh(1)
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    plain = ServingEngine(cfg, params, max_seq=16, pim_bits=8)
    shard = ServingEngine(cfg, params, max_seq=16, pim_bits=8, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(plain.generate(prompt, n_new=5)),
        np.asarray(shard.generate(prompt, n_new=5)))
    pc = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                  page_size=4, chunk=4, pim_bits=8)
    sc = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                  page_size=4, chunk=4, pim_bits=8, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(pc.generate(prompt, n_new=5)),
        np.asarray(sc.generate(prompt, n_new=5)))


def test_reference_loop_refuses_mesh():
    cfg = get_reduced("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_seq=16, pim_bits=8,
                        mesh=make_decode_mesh(1))
    with pytest.raises(NotImplementedError, match="single-device"):
        eng.generate_reference(jnp.zeros((1, 4), jnp.int32), 2)


# ------------------------------------------------------- direct admit path --
@pytest.mark.parametrize("arch,kv_bits", [
    ("qwen2-1.5b", 0), ("qwen2-1.5b", 8), ("deepseek-v2-lite-16b", 0),
    ("falcon-mamba-7b", 0), ("zamba2-1.2b", 0),
])
def test_direct_admit_matches_paged_insert_reference(arch, kv_bits):
    """prefill(pages=, slot=) writes the pool pages / per-slot state rows
    byte-identically to the retired dense round-trip (batch-1 dense prefill
    + models.paged_insert), under a permuted page list."""
    cfg = get_reduced(arch)
    if kv_bits:
        cfg = cfg.replace(kv_cache_bits=kv_bits)
    params = init_params(cfg, jax.random.PRNGKey(0))
    spad, ps, length, slot = 8, 4, 6, 1
    prompt = np.zeros((1, spad), np.int32)
    prompt[0, :length] = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (length,), 0, cfg.vocab))
    prompt = jnp.asarray(prompt)
    pages = jnp.asarray([3, 1], jnp.int32)  # non-contiguous on purpose

    paged = init_paged_cache(cfg, 2, 16, 6, ps)
    tmp = init_cache(cfg, 1, spad)
    logits_ref, tmp = prefill(params, cfg, prompt, tmp, None,
                              length=jnp.int32(length))
    ref = paged_insert(cfg, paged, tmp, jnp.int32(slot), pages)

    logits_new, got = prefill(params, cfg, prompt,
                              init_paged_cache(cfg, 2, 16, 6, ps), None,
                              length=jnp.int32(length), pages=pages,
                              slot=jnp.int32(slot))
    np.testing.assert_array_equal(np.asarray(logits_ref),
                                  np.asarray(logits_new))
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref),
            jax.tree_util.tree_leaves_with_path(got)):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(pa))


# ---------------------------------------------------- paged cache specs -----
def test_paged_cache_pspecs_table():
    cfg = get_reduced("zamba2-1.2b")  # hybrid: pools + per-slot state + tail
    shape = jax.eval_shape(lambda: init_paged_cache(cfg, 2, 16, 9, 4))
    specs = paged_cache_pspecs(shape, cfg)
    assert specs["block_tables"] == P(None, None)  # replicated
    k = specs["groups_attn"]["k"]
    assert k[-4] == "data" and all(e is None for i, e in enumerate(k)
                                   if i != len(k) - 4)  # pages over data
    # per-slot mamba2 state: 'data' on the BATCH dim, not the head dim
    h = specs["tail"]["h"]  # (tail, B, nh, hd, sd)
    assert h == P(None, "data", None, None, None)
    gh = specs["groups_ssm"]["h"]  # (G, attn_every, B, nh, hd, sd)
    assert gh == P(None, None, "data", None, None, None)
    # mamba1 payload is rank-3: batch still resolved via cfg
    cfg1 = get_reduced("falcon-mamba-7b")
    specs1 = paged_cache_pspecs(
        jax.eval_shape(lambda: init_paged_cache(cfg1, 2, 16, 9, 4)), cfg1)
    assert specs1["layers"]["h"] == P(None, "data", None, None)
    # NamedSharding wrapper: on a mesh WITHOUT a data axis (the engines'
    # 1-D model mesh) every cache leaf degenerates to replication
    named = paged_cache_shardings(make_decode_mesh(1), shape, cfg)
    assert all(all(e is None for e in sh.spec)
               for sh in jax.tree.leaves(named))


# ----------------------------------------------- 8-device token identity ----
SHARDED_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.models import init_params, encode
from repro.serving import (ServingEngine, ContinuousBatchingEngine, Request,
                           make_decode_mesh, pim_bytes, tree_pspecs)
from repro.models.common import set_kernel_dispatch

MODE = sys.argv[1]
ARCHS = sys.argv[2].split(",")
mesh = make_decode_mesh(8)
out = []
for arch in ARCHS:
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    extras = None
    if cfg.family == "vlm":
        extras = {"image_embeds": jax.random.normal(
            jax.random.PRNGKey(2), (2, cfg.vision.n_image_tokens, cfg.d_model))}
    elif cfg.family == "encdec":
        frames = jax.random.normal(
            jax.random.PRNGKey(3), (2, cfg.audio.n_frames, cfg.d_model))
        extras = {"enc_out": encode(params, cfg, frames)}
    row = {"arch": arch}
    if MODE == "fixed":
        plain = ServingEngine(cfg, params, max_seq=16, pim_bits=8)
        shard = ServingEngine(cfg, params, max_seq=16, pim_bits=8, mesh=mesh)
        row["identical"] = bool(np.array_equal(
            np.asarray(plain.generate(prompt, n_new=5, extras=extras)),
            np.asarray(shard.generate(prompt, n_new=5, extras=extras))))
        row["per_device_lt_total"] = bool(
            pim_bytes(shard.params, per_device=True) < pim_bytes(shard.params))
    elif MODE == "paged":
        plain = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                         page_size=4, chunk=4, pim_bits=8,
                                         page_alloc_seed=7)
        shard = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                         page_size=4, chunk=4, pim_bits=8,
                                         page_alloc_seed=7, mesh=mesh)
        reqs_a = [Request(prompt=np.asarray(prompt[i]), max_new=4 + i,
                          extras=(None if extras is None else
                                  jax.tree.map(lambda a: a[i], extras)))
                  for i in range(2)]
        reqs_b = [Request(prompt=r.prompt, max_new=r.max_new, extras=r.extras)
                  for r in reqs_a]
        a, b = plain.serve(reqs_a), shard.serve(reqs_b)
        row["identical"] = bool(all(np.array_equal(x, y)
                                    for x, y in zip(a, b)))
    elif MODE == "extras":
        # int4 odd-K packing under sharding
        a = ServingEngine(cfg, params, max_seq=16, pim_bits=4)
        b = ServingEngine(cfg, params, max_seq=16, pim_bits=4, mesh=mesh)
        row["int4_identical"] = bool(np.array_equal(
            np.asarray(a.generate(prompt, n_new=5)),
            np.asarray(b.generate(prompt, n_new=5))))
        # the pim_matvec kernel dispatch applies per-shard (one arch is
        # enough: interpret-mode pallas inside the scan is slow)
        if arch == "qwen2-1.5b":
            set_kernel_dispatch("force")
            a = ServingEngine(cfg, params, max_seq=16, pim_bits=8)
            b = ServingEngine(cfg, params, max_seq=16, pim_bits=8, mesh=mesh)
            row["matvec_identical"] = bool(np.array_equal(
                np.asarray(a.generate(prompt, n_new=3)),
                np.asarray(b.generate(prompt, n_new=3))))
            set_kernel_dispatch("auto")
        # a dense tree over a multi-device mesh distributes nothing: refuse
        try:
            ServingEngine(cfg, params, max_seq=16, mesh=mesh)
            row["dense_mesh_raises"] = False
        except ValueError:
            row["dense_mesh_raises"] = True
        if cfg.family == "ssm":
            from repro.serving import shard_quantized_tree, quantize_tree
            t = shard_quantized_tree(quantize_tree(params, 8), mesh)
            # x_proj is replicated by the RULE itself (train spec is
            # trivial); in_proj is rule-sharded and divides
            row["indivisible_replicated"] = (
                "tp" not in t["layers"]["ssm"]["x_proj"]
                and "tp" in t["layers"]["ssm"]["in_proj"])
            # the DIVISIBILITY branch: a rule-sharded leaf (wq) whose
            # output width 12 does not divide 8 devices must stay
            # unmarked, while its divisible sibling shards
            import jax.numpy as jnp
            fake = quantize_tree({"layers": {"attn": {
                "wq": jnp.zeros((16, 12)), "wk": jnp.zeros((16, 16))}}}, 8)
            ft = shard_quantized_tree(fake, mesh)
            row["indivisible_replicated"] = (
                row["indivisible_replicated"]
                and "tp" not in ft["layers"]["attn"]["wq"]
                and "tp" in ft["layers"]["attn"]["wk"])
    out.append(row)
print("RESULT " + json.dumps(out))
"""


def _run_sharded(mode: str, archs: str):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_SNIPPET, mode, archs],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_sharded_fixed_engine_token_identity_all_families():
    """Acceptance: greedy ServingEngine.generate on a forced 8-virtual-
    device mesh is token-identical to single-device, all six families, and
    per-device weight bytes really shrink."""
    rows = _run_sharded("fixed", ",".join(FAMILY_ARCHS))
    for r in rows:
        assert r["identical"], r
        assert r["per_device_lt_total"], r


def test_sharded_paged_engine_token_identity_all_families():
    """Acceptance: the continuous-batching scheduler on the paged cache,
    serving staggered per-request budgets under shard_map, stays
    token-identical to its single-device run for all six families."""
    rows = _run_sharded("paged", ",".join(FAMILY_ARCHS))
    for r in rows:
        assert r["identical"], r


def test_sharded_int4_matvec_and_divisibility():
    rows = _run_sharded("extras", "qwen2-1.5b,falcon-mamba-7b")
    for r in rows:
        assert r["int4_identical"], r
        assert r["dense_mesh_raises"], r
    assert [r for r in rows
            if r["arch"] == "qwen2-1.5b"][0]["matvec_identical"]
    ssm = [r for r in rows if r["arch"] == "falcon-mamba-7b"][0]
    assert ssm["indivisible_replicated"], ssm
