"""Work counts checked against hand counts at the benchmark's shapes."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import check, readers, work  # noqa: E402

REF = check.reference("dense_decoder")


def _hp(name):
    return REF.hyper(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()))


def test_qwen2_linear_params_by_hand():
    hp = _hp("qwen2-1.5b")
    # q, o 1536x1536; k, v 1536x256; gate, up, down 1536x8960
    per_layer = 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert per_layer == 46_792_704
    assert work.linear_params(hp) == 28 * per_layer == 1_310_195_712


# StarCoder2-15B's published sizes (bigcode/starcoder2-15b config.json)
STARCODER2_15B = {
    "hidden_size": 6144, "intermediate_size": 24576, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 4, "vocab_size": 49152,
    "rope_theta": 100000.0, "norm_epsilon": 1e-05, "use_bias": True,
    "tie_word_embeddings": False,
}


def test_starcoder2_15b_published_linear_params_by_hand(monkeypatch):
    # The two-matrix MLP's linears come from the reference's hyper(), and
    # every count and the readers' shapes follow them.
    monkeypatch.setattr(check, "CONFIGS", DATA)
    hp = check.reference("two_matrix_mlp").hyper(STARCODER2_15B)
    # q, o 6144x6144; k, v 6144x512; c_fc, c_proj 6144x24576
    per_layer = 2 * 6144 ** 2 + 2 * 6144 * 512 + 2 * 6144 * 24576
    assert per_layer == 383_778_816
    assert work.linear_params(hp) == 40 * per_layer == 15_351_152_640
    assert work.decode_flops(hp, 1) == pytest.approx(
        2 * 15_351_152_640 + 4 * 48 * 128 * 40 + 2 * 6144 * 49152)
    ctx = SimpleNamespace(hp=hp, serving={"tensor_parallel": 4})
    whole = [(6144, 6144), (6144, 512), (6144, 512), (6144, 6144),
             (6144, 24576), (24576, 6144), (6144, 49152)]  # and the head
    assert readers._linear_shapes(ctx) == whole + [(k, n // 4)
                                                   for k, n in whole]


@pytest.mark.parametrize("reference,config,mlp", [
    ("dense_decoder", "tiny.json", (("gate", 128, 256, False),
                                    ("up", 128, 256, False),
                                    ("down", 256, 128, False))),
    ("two_matrix_mlp", None, (("c_fc", 6144, 24576, True),
                              ("c_proj", 24576, 6144, True))),
])
def test_layer_linears_follow_the_reference(reference, config, mlp,
                                            monkeypatch):
    # The gated MLP by default; the reference's own list where hyper() gives
    # one (the published StarCoder2-15B block here).
    conf = (STARCODER2_15B if config is None
            else json.loads((DATA / config).read_text()))
    if reference != "dense_decoder":
        monkeypatch.setattr(check, "CONFIGS", DATA)
    linears = tuple(work.layer_linears(check.reference(reference).hyper(conf)))
    assert [n for n, *_ in linears[:4]] == ["wq", "wk", "wv", "wo"]
    assert linears[4:] == mlp


def test_decode_and_prefill_flops_by_hand():
    hp = _hp("qwen2-1.5b")
    head = 2 * 1536 * 151936
    attn_per_key = 4 * 12 * 128 * 28
    assert work.decode_flops(hp, 1000) == pytest.approx(
        2 * 1_310_195_712 + attn_per_key * 1000 + head)
    # causal prefill of 4 tokens attends to 1 + 2 + 3 + 4 keys
    assert work.prefill_flops(hp, 4) == pytest.approx(
        2 * 1_310_195_712 * 4 + attn_per_key * 10 + head)


def test_quant_linear_work_by_hand():
    f, b = work.quant_linear(8, 1536, 8960)
    assert f == 2 * 8 * 1536 * 8960
    assert b == 1536 * 8960 + 4 * 8960 + 2 * 8 * 1536 + 2 * 8 * 8960
    _, bb = work.quant_linear(8, 1536, 256, bias=True)
    assert bb == 1536 * 256 + 4 * 256 + 2 * 8 * 1536 + 2 * 8 * 256 + 2 * 256


def test_least_seconds_takes_the_binding_roof():
    peaks = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}
    # a decode matvec is bound by bytes, a 4096-row prefill by FLOPs
    f, b = work.quant_linear(8, 1536, 8960)
    assert work.least_seconds(f, b, peaks) == pytest.approx(b / 819e9)
    f, b = work.quant_linear(4096, 1536, 8960)
    assert work.least_seconds(f, b, peaks) == pytest.approx(f / 197e12)
