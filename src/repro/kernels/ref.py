"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax.numpy as jnp

from .epilogue import apply_epilogue


def _unpack_int4(w_packed: jnp.ndarray) -> jnp.ndarray:
    """(K//2, N) nibble-packed int8 -> (K, N) f32 codes (sign-extended)."""
    lo = (((w_packed & 0xF) ^ 8) - 8).astype(jnp.float32)
    hi = ((((w_packed >> 4) & 0xF) ^ 8) - 8).astype(jnp.float32)
    k2, n = w_packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, n)


def pim_matvec_ref(
    x: jnp.ndarray,
    w_codes: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bits: int = 8,
    bias=None,
    activation: str = "none",
    residual=None,
) -> jnp.ndarray:
    """Oracle for kernels.pim_matvec, and for kernels.pim_matmul at any row
    count: unscaled code matmul, then the same fused-epilogue order
    (scale [+ bias] -> activation [+ residual])."""
    w = w_codes.astype(jnp.float32) if bits == 8 else _unpack_int4(w_codes)
    acc = jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)
    bias = None if bias is None else jnp.asarray(bias, jnp.float32).reshape(1, -1)
    res = None if residual is None else residual.astype(jnp.float32)
    return apply_epilogue(acc, scale, bias, res, activation)


def pim_matmul_int8_ref(
    x: jnp.ndarray, w_codes: jnp.ndarray, scale: jnp.ndarray
) -> jnp.ndarray:
    """f32(M,K) @ dequant(int8 (K,N), scale (1,N)) -> f32 (M,N)."""
    w = w_codes.astype(jnp.float32) * scale
    return jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)


def pim_matmul_int4_ref(
    x: jnp.ndarray, w_packed: jnp.ndarray, scale: jnp.ndarray
) -> jnp.ndarray:
    """Nibble-packed variant: w_packed (K//2, N) int8 (low nibble = even K)."""
    lo = (((w_packed & 0xF) ^ 8) - 8).astype(jnp.int8)
    hi = ((((w_packed >> 4) & 0xF) ^ 8) - 8).astype(jnp.int8)
    k2, n = w_packed.shape
    w = jnp.stack([lo, hi], axis=1).reshape(2 * k2, n).astype(jnp.float32) * scale
    return jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)


def bitplane_matmul_ref(
    x: jnp.ndarray, planes: jnp.ndarray, scale: jnp.ndarray
) -> jnp.ndarray:
    """Bit-plane-decomposed matmul (the PIM-semantic form).

    planes: (B, K, N) in {0,1}; two's complement, LSB-first.
    out = sum_b weight_b * (x @ plane_b) * scale — one 'bit-serial step' per
    plane, mirroring how a PiCaSO PE consumes the striped operand.
    """
    bits = planes.shape[0]
    weights = 2.0 ** jnp.arange(bits)
    weights = weights.at[bits - 1].multiply(-1.0)
    acc = jnp.zeros((x.shape[0], planes.shape[2]), jnp.float32)
    for b in range(bits):
        acc = acc + weights[b] * jnp.dot(
            x.astype(jnp.float32),
            planes[b].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    return acc * scale


def fold_reduce_ref(x: jnp.ndarray) -> jnp.ndarray:
    """Sum along the last axis (the OpMux fold tree computes exactly this).

    Uses the same halve-and-add association order as the kernel so float
    results are bit-identical.
    """
    q = x.shape[-1]
    assert q & (q - 1) == 0, "q must be a power of two"
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]
