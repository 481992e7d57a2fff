"""Dequant-fused quantized matmul — the TPU-native PiCaSO adaptation.

PIM thesis: compute sits at the memory boundary, operands are stored at
reduced precision, so throughput is limited only by memory bandwidth.  On TPU
the analogous structure is: weights live in HBM as INT8 codes or INT4 nibble
pairs; each grid step DMAs a *packed* tile into VMEM, expands it to f32 right
next to the MXU, and accumulates into the resident output tile.  HBM traffic
for the weights drops 4x/8x vs f32 (2x/4x vs bf16), moving memory-bound
layers (decode-time matvecs — the paper's MLP/RNN regime, §I) toward the
compute roofline.

Tiling: grid (M/bm, N/bn, K/bk), K innermost; the output BlockSpec ignores k,
so the f32 accumulator tile stays resident in VMEM across the K sweep (zero
spill) — exactly like PiCaSO keeping partial sums in the PE register file
during a row MAC.  MXU alignment: bm/bn/bk multiples of 128 for full-size
inputs (smaller shapes shrink the tile); shapes that are not multiples of
the chosen blocks are zero-padded to tile and the output sliced back.

Epilogue: the final K step applies scale × acc [+ bias] → activation
[+ residual] while the tile is still in VMEM (see kernels.epilogue), so the
per-output ops never round-trip through HBM.
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


def _mm_kernel(*refs, n_x: int, n_k: int, bits: int, activation: str,
               has_bias: bool, has_residual: bool):
    x_refs, (w_ref, s_ref), rest = refs[:n_x], refs[n_x:n_x + 2], refs[n_x + 2:]
    o_ref, b_ref, r_ref = unpack_epilogue_refs(rest, has_bias, has_residual)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Dequantize the weight tile at the VMEM boundary (the 'BRAM port').
    xs = [r[...].astype(jnp.float32) for r in x_refs]
    o_ref[...] += quant_accumulate(xs, w_ref[...], bits)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = apply_epilogue(
            o_ref[...], s_ref[...],
            b_ref[...] if has_bias else None,
            r_ref[...] if has_residual else None,
            activation,
        )


def _pick(block: int, dim: int) -> int:
    return min(block, dim)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "activation", "bm", "bn", "bk", "interpret"),
)
def pim_matmul(
    x: jnp.ndarray,
    w_codes: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bits: int = 8,
    bias: jnp.ndarray | None = None,
    activation: str = "none",
    residual: jnp.ndarray | None = None,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """x (M,K) f32/bf16 @ quantized w -> (M,N) f32, epilogue fused.

    bits=8: ``w_codes`` is (K, N) int8.  bits=4: ``w_codes`` is the
    nibble-packed (K//2, N) int8 from ``quant.pack_int4``.
    ``scale``: (1, N) f32 per-output-channel scale.  ``bias``: (N,) or
    (1, N); ``residual``: (M, N); ``activation``: none|relu|silu|gelu.
    """
    m, k_dim = x.shape
    if bits == 8:
        k_w, n = w_codes.shape
        assert k_w == k_dim, (k_w, k_dim)
    elif bits == 4:
        k_w, n = w_codes.shape
        assert 2 * k_w == k_dim, (k_w, k_dim)
    else:
        raise ValueError(f"bits must be 4 or 8, got {bits}")

    bm, bn, bk = _pick(bm, m), _pick(bn, n), _pick(bk, k_dim)
    if bits == 4 and bk % 2:
        bk += 1  # keep nibble pairs whole
    m_pad, n_pad, k_pad = round_up(m, bm), round_up(n, bn), round_up(k_dim, bk)
    n_k = k_pad // bk
    grid = (m_pad // bm, n_pad // bn, n_k)

    bias = normalize_bias(bias, n)
    x = pad_axis(pad_axis(x, 1, k_pad), 0, m_pad)
    scale = pad_axis(scale, 1, n_pad)

    xs = split_activations(x, bits)
    bkx = bk if bits == 8 else bk // 2  # K columns per activation block
    if bits == 8:
        w_codes = pad_axis(pad_axis(w_codes, 0, k_pad), 1, n_pad)
    else:
        w_codes = pad_axis(pad_axis(w_codes, 0, k_pad // 2), 1, n_pad)

    in_specs = [pl.BlockSpec((bm, bkx), lambda i, j, k: (i, k)) for _ in xs]
    in_specs += [
        pl.BlockSpec((bkx, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
    ]
    operands = [*xs, w_codes, scale]
    ep_specs, ep_ops = build_epilogue_inputs(
        bias, residual, m=m, n=n, m_pad=m_pad, n_pad=n_pad, bm=bm, bn=bn,
        row_map=lambda i, j, k: (0, j), tile_map=lambda i, j, k: (i, j))
    in_specs += ep_specs
    operands += ep_ops

    out = pl.pallas_call(
        functools.partial(
            _mm_kernel, n_x=len(xs), n_k=n_k, bits=bits, activation=activation,
            has_bias=bias is not None, has_residual=residual is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(*operands)
    if m_pad != m or n_pad != n:
        out = out[:m, :n]
    return out
