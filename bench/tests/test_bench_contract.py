"""The configuration contract on the CPU: what a reference module may
declare of its block (``LEAF_ROLES``, ``hp["linears"]``), and that a
configuration which declares nothing draws, builds and counts exactly as
before the contract."""
import json
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import check, serve, weights  # noqa: E402

SEED = 2**31 + 7
TINY = json.loads((DATA / "tiny.json").read_text())
HP = check.reference("dense_decoder").hyper(TINY)

# crc32 of every leaf's bytes for TINY at SEED, recorded before weights.py
# drew by role: the draw of a configuration that declares no roles.
CHECKSUMS = {
    "float32": {
        "embed": 2049281317,
        "head/codes": 313333231,
        "head/scale": 1694885182,
        "layers/attn/bk": 3255471992,
        "layers/attn/bq": 1006299432,
        "layers/attn/bv": 1046584786,
        "layers/attn/wk/codes": 2783857054,
        "layers/attn/wk/scale": 1448808105,
        "layers/attn/wo/codes": 3267736602,
        "layers/attn/wo/scale": 3104108214,
        "layers/attn/wq/codes": 1705912683,
        "layers/attn/wq/scale": 2790005035,
        "layers/attn/wv/codes": 2999452690,
        "layers/attn/wv/scale": 1289672652,
        "layers/ln1": 559440723,
        "layers/ln2": 2826946393,
        "layers/mlp/down/codes": 2689059167,
        "layers/mlp/down/scale": 2497494847,
        "layers/mlp/gate/codes": 1618000013,
        "layers/mlp/gate/scale": 1305960124,
        "layers/mlp/up/codes": 2730693051,
        "layers/mlp/up/scale": 1142239184,
        "ln_f": 1725102563,
    },
    "bfloat16": {
        "embed": 3633995429,
        "head/codes": 313333231,
        "head/scale": 1694885182,
        "layers/attn/bk": 2711488264,
        "layers/attn/bq": 2970014665,
        "layers/attn/bv": 1176086729,
        "layers/attn/wk/codes": 2783857054,
        "layers/attn/wk/scale": 1448808105,
        "layers/attn/wo/codes": 3267736602,
        "layers/attn/wo/scale": 3104108214,
        "layers/attn/wq/codes": 1705912683,
        "layers/attn/wq/scale": 2790005035,
        "layers/attn/wv/codes": 2999452690,
        "layers/attn/wv/scale": 1289672652,
        "layers/ln1": 1884031161,
        "layers/ln2": 2706312009,
        "layers/mlp/down/codes": 2689059167,
        "layers/mlp/down/scale": 2497494847,
        "layers/mlp/gate/codes": 1618000013,
        "layers/mlp/gate/scale": 1305960124,
        "layers/mlp/up/codes": 2730693051,
        "layers/mlp/up/scale": 1142239184,
        "ln_f": 3439733937,
    },
}


def _tiny(dtype="float32"):
    conf = json.loads(json.dumps(TINY))
    conf["torch_dtype"] = dtype
    return conf


def _cfg(conf):
    return serve.model_config(conf, HP)


def _leaves(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("dtype", sorted(CHECKSUMS))
def test_undeclared_roles_draw_the_same_bits(dtype):
    params = weights.make(_cfg(_tiny(dtype)), SEED)
    got = {k: zlib.crc32(np.asarray(v).tobytes())
           for k, v in _leaves(params).items()}
    assert got == CHECKSUMS[dtype]


@pytest.mark.parametrize("path", ["layers/attn/bo", "layers/mlp/c_fc/bias",
                                  "head/bias"])
def test_leaf_without_a_role_names_its_path(path):
    name = path.rsplit("/", 1)[-1]
    with pytest.raises(ValueError, match=repr(path)):
        weights._draw(weights.base_key(1), path, name, (2, 4), "float32", {})


def test_role_that_no_rule_draws_names_its_path():
    with pytest.raises(ValueError, match="'layers/attn/bo'.*'shift'"):
        weights._draw(weights.base_key(1), "layers/attn/bo", "bo", (2, 4),
                      "float32", {"bo": "shift"})


@pytest.mark.parametrize("dtype", sorted(CHECKSUMS))
def test_declared_bias_leaf_is_drawn_from_the_seed(dtype):
    roles = {"ln2": "bias"}
    made = _leaves(weights.make(_cfg(_tiny(dtype)), SEED, roles=roles))
    a = np.asarray(made["layers/ln2"])

    def draw(seed):
        return np.asarray(weights._draw(weights.base_key(seed), "layers/ln2",
                                        "ln2", a.shape, a.dtype, roles))

    np.testing.assert_array_equal(draw(SEED), a)
    assert not np.array_equal(draw(SEED + 1), a)
    # a shift of spread BIAS_STD about 0, where a gain is 1 + N(0, 0.1^2)
    f = a.astype(np.float32)
    assert abs(f.mean()) < 0.02
    assert 0.6 * weights.BIAS_STD < f.std() < 1.4 * weights.BIAS_STD


def test_no_declaration_builds_the_registry_entry_at_the_stated_sizes():
    from repro.configs.registry import get_config

    assert _cfg(_tiny()) == get_config("qwen2-1.5b").replace(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=256, vocab=2048, qkv_bias=True, rope_theta=1e6, norm_eps=1e-5,
        tie_embeddings=False, param_dtype="float32", pim_bits=8,
        kv_cache_bits=16)


@pytest.mark.parametrize("registry_id,ok", [
    ("qwen2-1.5b", True),
    ("starcoder2-15b", True),
    ("moonshot-v1-16b-a3b", False),  # moe
    ("falcon-mamba-7b", False),  # ssm
])
def test_only_a_dense_registry_entry_is_served(registry_id, ok):
    conf = _tiny()
    conf["serving"]["registry_id"] = registry_id
    if ok:
        assert _cfg(conf).arch_id == registry_id
    else:
        with pytest.raises(ValueError, match="dense"):
            _cfg(conf)
