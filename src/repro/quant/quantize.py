"""Symmetric per-channel weight quantization + bit-plane / nibble packing,
plus the decode-time PartitionSpec derivation for sharding quantized leaves
over a tensor-parallel mesh (see :func:`decode_partition_spec`)."""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass
class QuantizedTensor:
    """Weights as stored in 'PIM mode': integer codes + per-channel scale.

    codes: int8 codes in [-2^(bits-1), 2^(bits-1)-1], shape = original shape
           (or nibble-packed along axis 0 when ``packed`` is True, bits=4).
    scale: f32, broadcastable along the quantization axis.
    """

    codes: jnp.ndarray
    scale: jnp.ndarray
    bits: int
    packed: bool = False

    @property
    def shape(self):
        if self.packed:
            return (2 * self.codes.shape[0],) + self.codes.shape[1:]
        return self.codes.shape


def quantize_symmetric(w: jnp.ndarray, bits: int = 8, axis: int = 0) -> QuantizedTensor:
    """Per-output-channel symmetric quantization (axis = reduction axis).

    The scale is chosen per channel of the *non*-reduction dims so the matmul
    can rescale once per output column.
    """
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    # Multiply by the reciprocal: a compiler rewrites division by a constant
    # into exactly this, so eager and jitted quantization agree bit for bit.
    scale = jnp.where(amax > 0, amax * (1.0 / qmax), 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax).astype(jnp.int8)
    return QuantizedTensor(codes=codes, scale=scale, bits=bits)


def dequantize(q: QuantizedTensor) -> jnp.ndarray:
    codes = unpack_int4(q.codes) if q.packed else q.codes
    return codes.astype(jnp.float32) * q.scale


def pack_int4(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 codes pairwise along axis 0: (K, ...) int8 -> (K//2, ...) int8.

    Row 2i goes to the low nibble, row 2i+1 to the high nibble.  K must be
    even — callers with an odd K pad one zero-code row first (that is what
    ``serving.quantize_tree`` does, flagging it with ``nibbles_odd``).
    """
    if codes.shape[0] % 2:
        raise ValueError(
            f"pack_int4 requires an even K, got K={codes.shape[0]}; "
            "pad one zero code row (see serving.quantize_tree)")
    lo = codes[0::2] & 0xF
    hi = codes[1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`, with sign extension."""
    lo = ((packed & 0xF) ^ 8) - 8
    hi = (((packed >> 4) & 0xF) ^ 8) - 8
    k2 = packed.shape[0]
    out = jnp.stack([lo, hi], axis=1).reshape((2 * k2,) + packed.shape[1:])
    return out.astype(jnp.int8)


# ------------------------------------------------------- decode sharding ----
# Sentinel FSDP axis fed to launch.sharding.param_spec so the train-time rule
# reveals every dim it shards (batch axes included), not just 'model'.
_FSDP_SENTINEL = "fsdp"


def _train_axes(path_names: list[str], ndim: int) -> set:
    """The set of named axes the TRAIN-time rule puts on this leaf."""
    from repro.launch.sharding import param_spec

    axes: set = set()
    for entry in param_spec(path_names, ndim, _FSDP_SENTINEL):
        if entry is None:
            continue
        axes.update(entry if isinstance(entry, tuple) else (entry,))
    return axes


def decode_partition_spec(path_names: list[str], ndim: int,
                          axis: str = "model") -> P:
    """Decode-time PartitionSpec for a quantized weight leaf.

    The WHICH question — which leaves are worth distributing — is answered
    by the train-time rule (:func:`repro.launch.sharding.param_spec`): a
    leaf the trainer shards somewhere (tensor-parallel over 'model' or FSDP
    over the batch axes) is a real matmul weight whose bytes dominate the
    decode stream; a leaf the trainer replicates (router, norms, x_proj,
    conv kernels, SSM dynamics params) stays replicated at decode too.
    Deriving from ``param_spec`` instead of a second name table keeps the
    train-time and decode-time spec sets cross-checked — a new weight name
    added to one rule cannot silently diverge in the other
    (tests/test_sharded_decode.py asserts the correspondence per family).

    The WHERE question has a decode-specific answer: ``axis`` always lands
    on the LAST (output) dim, whatever dim the trainer shards.  Decode must
    be token-identical to the single-device engines, and only
    output-column sharding is exact — each column's contraction runs over
    the full K locally and the all-gather is pure concatenation.  The
    train-time placements (K-dim for wo/down, expert-dim for MoE) would
    need a psum whose float reassociation can flip greedy argmax at
    near-ties.

    Codes, scales, and int4 packing markers all follow this one spec: codes
    and scale both carry N on their last dim (int4 packs along K, never N),
    and the marker leaves hold only leading stack dims, so the returned
    spec left-truncates to a pure-replication spec for them.
    """
    if not _train_axes(path_names, ndim):
        return P(*(None,) * ndim)
    return P(*((None,) * (ndim - 1) + (axis,)))


def to_bitplanes(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Integer codes -> bit-planes, LSB first: shape ``(bits,) + codes.shape``.

    Two's complement: plane ``bits-1`` carries weight ``-2^(bits-1)``.  This is
    the *spatial* analogue of PiCaSO's bit-serial striped storage (§III-A).
    """
    shifts = jnp.arange(bits, dtype=jnp.int32).reshape((bits,) + (1,) * codes.ndim)
    return ((codes.astype(jnp.int32)[None] >> shifts) & 1).astype(jnp.int8)


def from_bitplanes(planes: jnp.ndarray) -> jnp.ndarray:
    """Bit-planes -> int32 codes (two's complement)."""
    bits = planes.shape[0]
    weights = 2 ** jnp.arange(bits, dtype=jnp.int32)
    weights = weights.at[bits - 1].set(-weights[bits - 1])
    weights = weights.reshape((bits,) + (1,) * (planes.ndim - 1))
    return jnp.sum(planes.astype(jnp.int32) * weights, axis=0)
