"""Paged decode attention (Pallas, TPU target): read only each slot's live
pages, in place.

One decode step attends every batch slot's query to the K/V it has cached
so far.  The pool keeps K and V as ``(P, KV, page_size, D)`` pages, and a
slot's ``(W,)`` block-table row names its pages in order.  Gathering the
whole row for every slot moves ``B * W`` pages a layer whatever the slots'
lengths; this kernel instead walks, per slot, only the pages that hold
positions ``0 .. length - 1``, copying them from HBM straight out of the
pool (a page of all KV heads is one contiguous DMA) into a double-buffered
VMEM block of ``pages_per_block`` pages, and runs an online softmax over
the blocks.  A free slot parked at length 1 on the trash page costs one
page.

Grid: one step per slot.  The block table and the lengths are scalar-
prefetched into SMEM; inside a step a loop whose trip count is the slot's
own number of blocks starts the next block's copies (after the last block,
the next slot's first) before computing on the current one.  Per KV head
the group's ``G = H / KV`` query heads share one ``(G, D) x (D, T)``
product.  Scores, softmax and the running sums are float32; probabilities
meet V in the pool's dtype with a float32 accumulator, the precision of
``models.attention.paged_gather_attention``, the gather it replaces and the
oracle of tests/test_paged_attention_kernel.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_POSITIONS = 128  # positions per VMEM block (16 pages of 8)
# On the chip Mosaic (jax 0.9) slices a page out of the pool only where
# head_dim is a multiple of the lane width: at 64 it refuses the copy ("slice
# shape along dimension 3 must be aligned to tiling (128)").  Interpret mode
# runs any head_dim.
LANES = 128


def pages_per_block(page_size: int, width: int) -> int:
    """Pages a VMEM block holds: about ``BLOCK_POSITIONS`` positions, never
    more pages than a block-table row has."""
    return max(1, min(width, BLOCK_POSITIONS // page_size))


def _kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
            first_ref, *, ppb: int, width: int, root: float):
    b = pl.program_id(0)
    n_kv, g, d = q_ref.shape
    ps = k_hbm.shape[2]
    bk = ppb * ps

    def n_pages(slot):
        return jnp.minimum((len_ref[slot] + ps - 1) // ps, width)

    length = len_ref[b]
    n_blocks = (n_pages(b) + ppb - 1) // ppb

    def copies(slot, blk, buf, action):
        """Start (or wait for) the copies of block ``blk`` of ``slot``'s
        pages into buffer ``buf``: only the pages that hold its positions,
        a page of every KV head per copy, K and V on their own semaphores."""
        def page(j, _):
            pid = bt_ref[slot * width + blk * ppb + j]
            for i, (hbm, vmem) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                action(pltpu.make_async_copy(hbm.at[pid], vmem.at[buf, j],
                                             sems.at[i, buf]))

        live = jnp.clip(n_pages(slot) - blk * ppb, 0, ppb)
        jax.lax.fori_loop(0, live, page, None)

    def start(slot, blk, buf):
        copies(slot, blk, buf, lambda c: c.start())

    # Each slot's first block was started by the slot before, into the
    # buffer its last block left free: ``first_ref`` carries which.
    @pl.when(b == 0)
    def _first_slot():
        first_ref[0] = 0
        start(0, 0, 0)

    first = first_ref[0]
    q = q_ref[...].astype(k_buf.dtype)  # (KV, G, D)

    def body(blk, carry):
        buf = (first + blk) % 2
        # The next block, or after the last the next slot's first.
        last = blk + 1 == n_blocks
        nxt = jnp.where(last, b + 1, b)

        @pl.when(nxt < pl.num_programs(0))
        def _prefetch():
            start(nxt, jnp.where(last, 0, blk + 1), 1 - buf)

        copies(b, blk, buf, lambda c: c.wait())
        # (ppb, KV, ps, D) -> per head (bk, D).  The float32 round trip is
        # exact and keeps the merge of pages onto sublanes tile-aligned.
        kb = k_buf[buf].astype(jnp.float32)
        vb = v_buf[buf].astype(jnp.float32)
        col = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        row = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, d), 0)
        out = []
        for h in range(n_kv):
            m, l, acc = carry[h]
            kh = kb[:, h].reshape(bk, d).astype(k_buf.dtype)
            # Rows past the slot's length hold stale VMEM: zero them so a
            # zero probability never meets a non-finite value.
            vh = jnp.where(row < length, vb[:, h].reshape(bk, d),
                           0.0).astype(v_buf.dtype)
            s = jax.lax.dot_general(
                q[h], kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / root  # (G, bk)
            s = jnp.where(col < length, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(v_buf.dtype), vh,
                                        preferred_element_type=jnp.float32)
            out.append((m_new, l, acc))
        return tuple(out)

    init = tuple((jnp.full((g, 1), -jnp.inf, jnp.float32),
                  jnp.zeros((g, 1), jnp.float32),
                  jnp.zeros((g, d), jnp.float32)) for _ in range(n_kv))
    final = jax.lax.fori_loop(0, n_blocks, body, init)
    first_ref[0] = (first + n_blocks) % 2
    for h in range(n_kv):
        _, l, acc = final[h]
        o_ref[h] = acc / l


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           interpret: bool = False):
    """Decode attention of ``q`` (B, H, D) over each slot's first
    ``lengths`` (B,) positions, read through ``block_tables`` (B, W) from
    the pools ``k_pages``/``v_pages`` (P, KV, page_size, D).  Returns
    (B, H, D) float32; query head ``h`` reads KV head ``h // (H / KV)``.
    Every length must be at least 1 (a decode step has written its own
    token before it attends)."""
    b, n_heads, d = q.shape
    _, n_kv, ps, _ = k_pages.shape
    width = block_tables.shape[1]
    g = n_heads // n_kv
    ppb = pages_per_block(ps, width)
    buf = pltpu.VMEM((2, ppb, n_kv, ps, d), k_pages.dtype)
    # The group's query heads travel as float32 (exact): a (G, D) block of
    # fewer than a tile's sublanes lowers for float32 at any G.
    qg = q.reshape(b, n_kv, g, d).astype(jnp.float32)
    head_block = pl.BlockSpec((None, n_kv, g, d), lambda i, *_: (i, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, ppb=ppb, width=width,
                          root=math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[head_block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=head_block,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        # Sequential: a slot's first block is fetched by the slot before.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, d), jnp.float32),
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(b, n_heads, d)
