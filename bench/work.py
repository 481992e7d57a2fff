"""The work a computation needs, from its shapes alone.

These count what the algorithm needs, not what an implementation does: a
kernel that pads, re-reads a tile or widens an operand does not change them.
So a roofline share built on them moves only when the time moves.  Bytes are
those an operation must move through HBM at least once: its operands in and
its result out, in the dtypes the configuration serves (int8 codes, a
float32 scale per output column, bfloat16 activations and biases).
"""
from __future__ import annotations

from collections.abc import Sequence

ACT_BYTES = 2  # bfloat16 activations


def quant_linear(m: int, k: int, n: int, *, bias: bool = False,
                 code_bits: int = 8) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of ``x (m, k) @ dequant(codes (k, n)) [+ b]``."""
    flops = 2.0 * m * k * n
    nbytes = (k * n * code_bits / 8 + 4 * n + ACT_BYTES * m * k
              + ACT_BYTES * m * n + (ACT_BYTES * n if bias else 0))
    return flops, nbytes


def layer_linears(hp: dict) -> Sequence[tuple[str, int, int, bool]]:
    """(name, K, N, bias) of one decoder layer's weight matrices: the list
    that the reference's ``hyper()`` gives as ``hp["linears"]``, else a dense
    decoder's with a gated MLP."""
    if "linears" in hp:
        return hp["linears"]
    d, f = hp["d_model"], hp["d_ff"]
    q, kv = hp["heads"] * hp["head_dim"], hp["kv_heads"] * hp["head_dim"]
    b = hp["qkv_bias"]
    return [("wq", d, q, b), ("wk", d, kv, b), ("wv", d, kv, b),
            ("wo", q, d, False), ("gate", d, f, False), ("up", d, f, False),
            ("down", f, d, False)]


def linear_params(hp: dict) -> int:
    """Weights that each token multiplies in the decoder layers."""
    return hp["layers"] * sum(k * n for _, k, n, _ in layer_linears(hp))


def head_flops(hp: dict) -> float:
    """The output head for one position."""
    return 2.0 * hp["d_model"] * hp["vocab"]


def attention_flops(hp: dict, queries: int, keys_total: float) -> float:
    """Scores and weighted values: 4 * heads * head_dim per (query, key)
    pair, over all layers; ``keys_total`` is the sum over the queries of the
    keys each attends to."""
    return 4.0 * hp["heads"] * hp["head_dim"] * keys_total * hp["layers"] \
        if queries else 0.0


def prefill_flops(hp: dict, length: int) -> float:
    """A causal prefill of ``length`` true tokens, logits of the last
    position only."""
    keys = length * (length + 1) / 2.0
    return (2.0 * linear_params(hp) * length
            + attention_flops(hp, length, keys) + head_flops(hp))


def decode_flops(hp: dict, context: int) -> float:
    """One decode step of one live sequence that attends to ``context``
    keys (its own new one included)."""
    return (2.0 * linear_params(hp) + attention_flops(hp, 1, context)
            + head_flops(hp))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time at the bf16 peak and HBM
    time at the bandwidth peak."""
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
