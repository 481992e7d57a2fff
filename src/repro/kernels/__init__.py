"""PIM compute kernels (Pallas, TPU target; interpret-mode validated on CPU).

  pim_matmul   — dequant-fused INT4/INT8 weight matmul (the PIM adaptation)
  pim_matvec   — decode-shaped (M<=8) variant with the fused epilogue
  bitplane     — bit-plane-decomposed matmul (PIM-semantic faithful form)
  fold_reduce  — OpMux-style log-step folding reduction
  paged_attention — decode attention over each slot's live pages of the
                 paged KV pool, read in place
  epilogue     — shared epilogue (scale/bias/activation/residual) + padding
  ops          — jit'd public wrappers;  ref — pure-jnp oracles
"""
from .ops import (
    bitplane_matmul,
    fold_reduce,
    fold_sum,
    pim_dense,
    pim_dense_bitplane,
    pim_matmul,
    pim_matvec,
    pim_matvec_dense,
    quantize_for_pim,
)
from . import ref

__all__ = [
    "pim_matmul", "pim_matvec", "bitplane_matmul", "fold_reduce", "ref",
    "quantize_for_pim", "pim_dense", "pim_matvec_dense",
    "pim_dense_bitplane", "fold_sum",
]
