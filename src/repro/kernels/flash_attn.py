"""Flash attention (Pallas, TPU target) — VMEM-resident online softmax.

The dry-run roofline shows train cells dominated by S^2 score traffic: the
XLA attention path materialises (B,H,S,S) f32 scores in HBM (fwd + bwd).
This kernel is the canonical fix: the grid walks (batch*heads, q-blocks),
each program streams KV blocks through VMEM with a running (max, denom, acc)
triple, so nothing S^2-sized ever reaches HBM — the same
transfer-compute-overlap insight as PiCaSO's binary-hopping network, applied
at the VMEM boundary.

Validated against models.attention._direct_attention in interpret mode
(tests/test_flash_attn.py); the roofline credits it via the S^2-traffic
adjustment in launch.roofline.flash_adjusted (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, sk: int, bkv: int,
                  causal: bool, bq: int, scale: float):
    qi = pl.program_id(1)  # q-block index
    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)

    n_kv = sk // bkv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bkv, bkv), :]
        v = v_ref[0, pl.ds(j * bkv, bkv), :]
        s = q @ k.astype(jnp.float32).T  # (bq, bkv)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + p @ v.astype(jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bkv", "interpret")
)
def flash_attention(
    q: jnp.ndarray,  # (BH, Sq, D)
    k: jnp.ndarray,  # (BH, Sk, D)
    v: jnp.ndarray,  # (BH, Sk, D)
    *,
    causal: bool = True,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused attention over flattened (batch*heads) leading dim."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    bq = min(bq, sq)
    bkv = min(bkv, sk)
    assert sq % bq == 0 and sk % bkv == 0, (sq, bq, sk, bkv)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel, sk=sk, bkv=bkv, causal=causal, bq=bq, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(q, k, v)


def flash_attention_ref(q, k, v, causal: bool = True) -> jnp.ndarray:
    """Pure-jnp oracle (naive softmax attention)."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32)).astype(q.dtype)
