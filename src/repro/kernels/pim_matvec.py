"""Decode-shaped PIM matvec — epilogue-fused quantized GEMV (Pallas).

The decode regime the paper targets (§I: MLP/RNN inference dominated by
weight traffic) has M = batch ≤ 8 rows of activations against a (K, N)
quantized weight: the matmul is pure weight streaming, and every extra HBM
round-trip (dequant materialisation, bias add, activation, residual) costs
as much as the matmul itself.  This kernel keeps the whole output tile
resident in VMEM for the full K sweep and runs the epilogue
(scale × acc + bias → activation → + residual) in the flush step, so the
only HBM traffic is: packed codes in, final activations out — the PiCaSO
structure (compute at the BRAM port) applied to serving.

Grid: (N/bn, K/bk), K innermost.  M is padded to 8 (the f32 sublane tile);
K and N are padded to the block sizes, so non-multiple shapes work (zero
codes/activations contribute zero).  bits=8 streams int8 codes; bits=4
streams nibble-packed pairs (two K rows per byte) and unpacks next to the
MXU — 8x less weight HBM traffic than f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .epilogue import (
    apply_epilogue,
    build_epilogue_inputs,
    normalize_bias,
    pad_axis,
    quant_accumulate,
    round_up,
    split_activations,
    unpack_epilogue_refs,
)

MAX_M = 8  # decode-shaped: one f32 sublane tile of activation rows


def _mv_kernel(*refs, n_x: int, n_k: int, bits: int, activation: str,
               has_bias: bool, has_residual: bool):
    x_refs, (w_ref, s_ref), rest = refs[:n_x], refs[n_x:n_x + 2], refs[n_x + 2:]
    o_ref, b_ref, r_ref = unpack_epilogue_refs(rest, has_bias, has_residual)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xs = [r[...].astype(jnp.float32) for r in x_refs]  # (8, bk) or 2x (8, bk/2)
    o_ref[...] += quant_accumulate(xs, w_ref[...], bits)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = apply_epilogue(
            o_ref[...], s_ref[...],
            b_ref[...] if has_bias else None,
            r_ref[...] if has_residual else None,
            activation,
        )


@functools.partial(
    jax.jit, static_argnames=("bits", "activation", "bn", "bk", "interpret")
)
def pim_matvec(
    x: jnp.ndarray,
    w_codes: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bits: int = 8,
    bias: jnp.ndarray | None = None,
    activation: str = "none",
    residual: jnp.ndarray | None = None,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """x (M≤8, K) @ quantized w -> (M, N) f32, epilogue fused.

    bits=8: ``w_codes`` is (K, N) int8.  bits=4: ``w_codes`` is the
    nibble-packed (K//2, N) int8 from ``quant.pack_int4``.
    ``scale``: (1, N) f32 per-output-channel scale.  ``bias``: (N,) or
    (1, N); ``residual``: (M, N); ``activation``: none|relu|silu|gelu.
    Shapes that are not block multiples are zero-padded to tile.
    """
    m, k_dim = x.shape
    if m > MAX_M:
        raise ValueError(f"pim_matvec is decode-shaped (M <= {MAX_M}); "
                         f"got M={m} — use pim_matmul")
    if bits == 8:
        k_w, n = w_codes.shape
        assert k_w == k_dim, (k_w, k_dim)
    elif bits == 4:
        k_w, n = w_codes.shape
        assert 2 * k_w == k_dim, (k_w, k_dim)
    else:
        raise ValueError(f"bits must be 4 or 8, got {bits}")

    bn = min(bn, n)
    bk = min(bk, k_dim)
    if bits == 4 and bk % 2:
        bk += 1  # keep nibble pairs whole
    n_pad, k_pad = round_up(n, bn), round_up(k_dim, bk)

    bias = normalize_bias(bias, n)
    x = pad_axis(pad_axis(x, 1, k_pad), 0, MAX_M)
    scale = pad_axis(scale, 1, n_pad)
    xs = split_activations(x, bits)
    bkx = bk if bits == 8 else bk // 2  # K columns per activation block
    if bits == 8:
        w_codes = pad_axis(pad_axis(w_codes, 0, k_pad), 1, n_pad)
    else:
        w_codes = pad_axis(pad_axis(w_codes, 0, k_pad // 2), 1, n_pad)

    n_k = k_pad // bk
    grid = (n_pad // bn, n_k)

    in_specs = [pl.BlockSpec((MAX_M, bkx), lambda j, k: (0, k)) for _ in xs]
    in_specs += [
        pl.BlockSpec((bkx, bn), lambda j, k: (k, j)),
        pl.BlockSpec((1, bn), lambda j, k: (0, j)),
    ]
    operands = [*xs, w_codes, scale]
    ep_specs, ep_ops = build_epilogue_inputs(
        bias, residual, m=m, n=n, m_pad=MAX_M, n_pad=n_pad, bm=MAX_M, bn=bn,
        row_map=lambda j, k: (0, j), tile_map=lambda j, k: (0, j))
    in_specs += ep_specs
    operands += ep_ops

    out = pl.pallas_call(
        functools.partial(
            _mv_kernel, n_x=len(xs), n_k=n_k, bits=bits, activation=activation,
            has_bias=bias is not None, has_residual=residual is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((MAX_M, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((MAX_M, n_pad), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]
