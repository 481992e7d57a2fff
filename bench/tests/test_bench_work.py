"""Work counts checked against hand counts at the benchmark's shapes."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, work  # noqa: E402

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


def test_starcoder2_15b_linear_params_by_hand():
    hp = _hp("starcoder2-15b")
    per_layer = 2 * 6144 * 6144 + 2 * 6144 * 512 + 3 * 6144 * 24576
    assert per_layer == 534_773_760  # the gated MLP's 534.77 M a layer
    assert work.linear_params(hp) == 40 * per_layer == 21_390_950_400


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
