"""Shared epilogue + pad-to-tile helpers for the PIM matmul kernels.

The epilogue is the set of per-output ops (channel scale, bias, activation,
residual add) that a naive lowering runs as separate XLA ops AFTER the
matmul — each one a full (M, N) round-trip through HBM.  Fusing them into
the kernel's flush step keeps the tile in VMEM until the final value is
written once: the PIM discipline (compute at the memory boundary) applied to
the epilogue, not just the dequant.

``apply_epilogue`` is pure jnp so the same code runs inside a Pallas kernel
body (on VMEM tiles) and in the pure-jnp oracles (kernels.ref).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ACTIVATIONS = {
    "none": lambda y: y,
    "relu": lambda y: jnp.maximum(y, 0.0),
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
}


def apply_epilogue(acc, scale, bias, residual, activation: str):
    """acc * scale [+ bias] -> activation -> [+ residual], all in f32."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; "
                         f"one of {sorted(ACTIVATIONS)}")
    y = acc * scale
    if bias is not None:
        y = y + bias
    y = ACTIVATIONS[activation](y)
    if residual is not None:
        y = y + residual
    return y


def split_activations(x, bits: int) -> tuple:
    """The activation operands a quantized kernel streams, as XLA arrays.

    bits=8: ``(x,)``.  bits=4: ``(x[:, 0::2], x[:, 1::2])`` — the even and
    odd K columns, which meet the low and high nibbles of the packed weight
    rows.  The split happens here, outside the kernel, because Mosaic
    refuses strided lane slices inside a kernel body; each half then
    streams with a plain ``(rows, bk // 2)`` block."""
    if bits == 8:
        return (x,)
    return (x[:, 0::2], x[:, 1::2])


def quant_accumulate(xs, w_tile, bits: int):
    """One K-step contribution: activation tiles @ quantized weight tile.

    bits=8: ``xs`` is ``(x,)`` with x (bm, bk) f32 and ``w_tile`` (bk, bn)
    int8, dequantized at the VMEM boundary.  bits=4: ``xs`` is the (even,
    odd) K-column pair from ``split_activations``, each (bm, bk//2), and
    ``w_tile`` is (bk//2, bn) nibble-packed int8 — even K rows in the low
    nibbles, odd K rows in the high nibbles.  Shared by pim_matmul and
    pim_matvec so the dequant semantics can never drift between them.
    """
    if bits == 8:
        (x,) = xs
        return jnp.dot(x, w_tile.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    x_even, x_odd = xs
    w = w_tile.astype(jnp.int32)  # the TPU vector unit has no int8 arithmetic
    lo = (((w & 0xF) ^ 8) - 8).astype(jnp.float32)
    hi = ((((w >> 4) & 0xF) ^ 8) - 8).astype(jnp.float32)
    return (jnp.dot(x_even, lo, preferred_element_type=jnp.float32)
            + jnp.dot(x_odd, hi, preferred_element_type=jnp.float32))


def unpack_epilogue_refs(rest, has_bias: bool, has_residual: bool):
    """(o_ref, b_ref, r_ref) from a kernel's trailing variadic refs
    (ordering: [bias?], [residual?], out)."""
    o_ref = rest[-1]
    b_ref = rest[0] if has_bias else None
    r_ref = rest[1 if has_bias else 0] if has_residual else None
    return o_ref, b_ref, r_ref


def round_up(dim: int, mult: int) -> int:
    return -(-dim // mult) * mult


def pad_axis(a, axis: int, target: int):
    """Zero-pad ``axis`` of ``a`` up to length ``target`` (no-op if equal)."""
    cur = a.shape[axis]
    if cur == target:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, target - cur)
    return jnp.pad(a, widths)


def normalize_bias(bias, n: int):
    """Accept (N,) or (1, N) bias; return (1, N) f32 or None."""
    if bias is None:
        return None
    b = jnp.asarray(bias, jnp.float32).reshape(1, -1)
    assert b.shape[1] == n, (b.shape, n)
    return b


def build_epilogue_inputs(bias, residual, *, m: int, n: int, m_pad: int,
                          n_pad: int, bm: int, bn: int, row_map, tile_map):
    """BlockSpecs + padded operands for the optional epilogue inputs.

    Shared by pim_matmul / pim_matvec / bitplane_matmul so the bias and
    residual padding/dtype handling can never drift between kernels.
    ``row_map``/``tile_map`` are the grid index maps for a (1, bn) row
    block and a (bm, bn) tile block respectively (grid arity differs per
    kernel).  ``bias`` must already be normalized via ``normalize_bias``.
    """
    specs, operands = [], []
    if bias is not None:
        specs.append(pl.BlockSpec((1, bn), row_map))
        operands.append(pad_axis(bias, 1, n_pad))
    if residual is not None:
        assert residual.shape == (m, n), (residual.shape, m, n)
        specs.append(pl.BlockSpec((bm, bn), tile_map))
        operands.append(
            pad_axis(pad_axis(residual.astype(jnp.float32), 1, n_pad), 0, m_pad))
    return specs, operands
