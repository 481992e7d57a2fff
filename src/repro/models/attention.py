"""GQA attention: direct, chunked (online-softmax), decode-with-cache, cross.

Chunked attention scans KV blocks with a running (max, denom, acc) triple so
prefill at 32k+ never materialises the (S x S) score matrix — the pure-JAX
equivalent of flash attention, and the TPU analogue of PiCaSO streaming
partial products through the reduction network instead of buffering them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import _kernels_enabled, apply_rope, dense_init, linear, split_keys

CHUNKED_THRESHOLD = 8192
KV_CHUNK = 512


def _shard_heads(x):
    """Pin (B, S, H, D) activations to batch x head sharding.

    GSPMD propagation sometimes contracts attention over a sharded head_dim
    and all-reduces the S^2 score tensor (309 GB/step on starcoder2-7b
    train_4k — EXPERIMENTS.md §Perf cell B); this constraint forces the
    scores to be computed head-local.  No-op off-mesh.
    """
    from jax.sharding import PartitionSpec as P
    import jax

    try:
        # Requires an enclosing `with mesh:` whose axes include data/model —
        # exactly how launch.steps lowers; plain CPU tests take the except.
        return jax.lax.with_sharding_constraint(x, P("data", None, "model", None))
    except Exception:
        return x


def attn_init(key, d: int, n_heads: int, n_kv: int, head_dim: int, dtype,
              bias: bool = False) -> dict:
    kq, kk, kv, ko = split_keys(key, 4)
    p = {
        "wq": dense_init(kq, (d, n_heads * head_dim), dtype),
        "wk": dense_init(kk, (d, n_kv * head_dim), dtype),
        "wv": dense_init(kv, (d, n_kv * head_dim), dtype),
        "wo": dense_init(ko, (n_heads * head_dim, d), dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def _split_heads(x, n, d):
    return x.reshape(x.shape[:-1] + (n, d))


@functools.lru_cache(maxsize=None)
def tree_layout(fan: int, depth: int):
    """Static layout of a fan-of-chains candidate tree of ``1 + fan*depth``
    nodes in *node order*: node 0 is the shared root (the last accepted
    token), node ``1 + f*depth + i`` is step ``i`` of candidate chain ``f``.

    Returns ``(dep, vis)`` numpy arrays: ``dep[j]`` is node j's logical
    depth (rope position offset from the root), ``vis[q, j]`` is True when
    node j is an ancestor-or-self of query node q — the shared-prefix
    attention mask.  With ``fan == 1`` this degenerates to the linear
    window: ``dep == arange`` and ``vis`` lower-triangular, making the tree
    code path boolean-identical to the plain verify mask."""
    t = 1 + fan * depth
    dep = np.zeros((t,), np.int32)
    vis = np.zeros((t, t), np.bool_)
    vis[:, 0] = True  # the root is every node's ancestor
    for f in range(fan):
        for i in range(depth):
            j = 1 + f * depth + i
            dep[j] = i + 1
            vis[j, 1 + f * depth : j + 1] = True  # own-chain prefix + self
    return dep, vis


def _tree_valid(vis, pos, t: int, store: int):
    """(B, T, S) bool: query node q of the window rooted at per-row ``pos``
    may attend store column c iff c is in the cached prefix (c < pos) or c
    holds a window node on q's root-path (``vis[q, c - pos]``)."""
    rel = jnp.arange(store, dtype=pos.dtype)[None, :] - pos[:, None]  # (B, S)
    inwin = (rel >= 0) & (rel < t)
    vm = jnp.asarray(vis)[:, jnp.clip(rel, 0, t - 1)]  # (T, B, S)
    return (rel < 0)[:, None, :] | (inwin[:, None, :] & jnp.moveaxis(vm, 0, 1))


def _direct_attention(q, k, v, causal: bool, q_offset: int = 0):
    """q: (B,Sq,KV,G,D); k,v: (B,Sk,KV,D)."""
    b, sq, kvh, g, d = q.shape
    sk = k.shape[1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if causal:
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(sk)[None, :]
        scores = jnp.where(qi >= ki, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", w, v)


def _chunked_attention(q, k, v, causal: bool, kv_chunk: int = KV_CHUNK):
    """Online-softmax over KV chunks. q: (B,Sq,KV,G,D); k,v: (B,Sk,KV,D)."""
    b, sq, kvh, g, d = q.shape
    sk = k.shape[1]
    c = min(kv_chunk, sk)
    while sk % c:  # fall back to the largest divisor (defensive)
        c -= 1
    n_chunks = sk // c
    kc = k.reshape(b, n_chunks, c, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, c, kvh, d).transpose(1, 0, 2, 3, 4)

    qf = q.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(d)
    qi = jnp.arange(sq)[:, None]

    def body(carry, xs):
        m, l, acc = carry
        j, kj, vj = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kj.astype(jnp.float32)) * scale
        if causal:
            ki = j * c + jnp.arange(c)[None, :]
            s = jnp.where(qi >= ki, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # Guard fully-masked rows (all -inf) against NaNs.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, vj.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, sq, kvh, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.astype(v.dtype)


def attn_apply(
    p: dict,
    x: jnp.ndarray,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 0.0,
    causal: bool = True,
    kv_input: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
    kv_chunk: int = KV_CHUNK,
    act_shard: bool = False,
) -> jnp.ndarray:
    """Full-sequence attention (training / prefill). Cross-attn if kv_input."""
    b, s, _ = x.shape
    kv_src = x if kv_input is None else kv_input
    q = _split_heads(linear(x, p["wq"], p.get("bq")), n_heads, head_dim)
    k = _split_heads(linear(kv_src, p["wk"], p.get("bk")), n_kv, head_dim)
    v = _split_heads(linear(kv_src, p["wv"], p.get("bv")), n_kv, head_dim)
    if act_shard:
        q, k, v = _shard_heads(q), _shard_heads(k), _shard_heads(v)
    if positions is None:
        positions = jnp.arange(s)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        kpos = jnp.arange(k.shape[1]) if kv_input is not None else positions
        k = apply_rope(k, kpos, rope_theta)
    g = n_heads // n_kv
    qg = q.reshape(b, s, n_kv, g, head_dim)
    # Chunk on KV length: long-KV self-attn streams blocks (online softmax);
    # cross-attn over a short modality memory (e.g. 1600 image tokens) stays
    # direct regardless of query length.
    if k.shape[1] > CHUNKED_THRESHOLD:
        o = _chunked_attention(qg, k, v, causal, kv_chunk=kv_chunk)
    else:
        o = _direct_attention(qg, k, v, causal)
    o = o.reshape(b, s, n_heads * head_dim)
    return linear(o, p["wo"])


# ---------------------------------------------------------------- prefill ---
def attn_prefill(
    p: dict,
    x: jnp.ndarray,  # (B, S, D)
    cache: dict,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 0.0,
    kv_chunk: int = KV_CHUNK,
    pages: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, dict]:
    """Single-pass prefill: full-sequence causal attention that also writes
    all S prompt tokens' K/V into the preallocated decode cache at once.

    Replaces S sequential ``attn_decode`` calls with one lowered program —
    the host-dispatch overhead the paper's PIM argument says must not
    dominate the memory-bound regime.  Numerics match the per-token path:
    with an int8 cache the prompt attends against the quantize->dequantize
    K/V, i.e. exactly what later decode steps will read back.

    With ``pages`` (n,) the cache is a PAGED pool (``paged_kv_cache_init``
    leaves) and x must be batch-1 with ``S == n * page_size``: the prompt's
    K/V scatter straight into the slot's pool pages — the admit path writes
    pages directly instead of round-tripping a temporary dense cache
    through ``models.paged_insert``.
    """
    b, s, _ = x.shape
    q = _split_heads(linear(x, p["wq"], p.get("bq")), n_heads, head_dim)
    k = _split_heads(linear(x, p["wk"], p.get("bk")), n_kv, head_dim)
    v = _split_heads(linear(x, p["wv"], p.get("bv")), n_kv, head_dim)
    if rope_theta:
        positions = jnp.arange(s)
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    with jax.named_scope("kv_write"):
        k_t = k.transpose(0, 2, 1, 3)  # (B, KV, S, D) — the cache layout
        v_t = v.transpose(0, 2, 1, 3)

        if pages is not None:
            n, ps = pages.shape[0], cache["k"].shape[2]

            def to_pages(t):  # (1, KV, n*ps, ...) -> (n, KV, ps, ...)
                t = t[0].reshape((t.shape[1], n, ps) + t.shape[3:])
                return jnp.moveaxis(t, 1, 0)

            if "k_scale" in cache:
                k_codes, k_sc = _quant_kv(k_t)
                v_codes, v_sc = _quant_kv(v_t)
                new_cache = {
                    "k": cache["k"].at[pages].set(to_pages(k_codes)),
                    "v": cache["v"].at[pages].set(to_pages(v_codes)),
                    "k_scale": cache["k_scale"].at[pages].set(to_pages(k_sc)),
                    "v_scale": cache["v_scale"].at[pages].set(to_pages(v_sc)),
                }
                k = (k_codes.astype(x.dtype)
                     * k_sc[..., None].astype(x.dtype)).transpose(0, 2, 1, 3)
                v = (v_codes.astype(x.dtype)
                     * v_sc[..., None].astype(x.dtype)).transpose(0, 2, 1, 3)
            else:
                new_cache = {
                    "k": cache["k"].at[pages].set(
                        to_pages(k_t).astype(cache["k"].dtype)),
                    "v": cache["v"].at[pages].set(
                        to_pages(v_t).astype(cache["v"].dtype)),
                }
        elif "k_scale" in cache:
            k_codes, k_sc = _quant_kv(k_t)
            v_codes, v_sc = _quant_kv(v_t)
            new_cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], k_codes, (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], v_codes, (0, 0, 0, 0)),
                "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], k_sc, (0, 0, 0)),
                "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], v_sc, (0, 0, 0)),
            }
            k = (k_codes.astype(x.dtype) * k_sc[..., None].astype(x.dtype)).transpose(0, 2, 1, 3)
            v = (v_codes.astype(x.dtype) * v_sc[..., None].astype(x.dtype)).transpose(0, 2, 1, 3)
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], k_t.astype(cache["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], v_t.astype(cache["v"].dtype), (0, 0, 0, 0)),
            }
    with jax.named_scope("attention"):
        g = n_heads // n_kv
        qg = q.reshape(b, s, n_kv, g, head_dim)
        if k.shape[1] > CHUNKED_THRESHOLD:
            o = _chunked_attention(qg, k, v, causal=True, kv_chunk=kv_chunk)
        else:
            o = _direct_attention(qg, k, v, causal=True)
    o = o.reshape(b, s, n_heads * head_dim)
    return linear(o, p["wo"]), new_cache


# ----------------------------------------------------------------- decode ---
def kv_cache_init(batch: int, max_seq: int, n_kv: int, head_dim: int, dtype,
                  bits: int = 16) -> dict:
    """Head-major cache (B, KV, S, D): the decode contraction then reads the
    cache in its stored layout — the (B,S,KV,D) layout forced two ~1.4 GB
    transpose copies per layer per step on starcoder2-15b decode_32k
    (EXPERIMENTS.md §Perf cell A, iteration 4).

    ``bits=8``: int8 storage + per-token f32 scales — the paper's
    reduced-precision-operand thesis (Fig 7) applied to the decode cache,
    halving cache HBM bytes vs bf16."""
    if bits == 8:
        return {
            "k": jnp.zeros((batch, n_kv, max_seq, head_dim), jnp.int8),
            "v": jnp.zeros((batch, n_kv, max_seq, head_dim), jnp.int8),
            "k_scale": jnp.zeros((batch, n_kv, max_seq), jnp.float32),
            "v_scale": jnp.zeros((batch, n_kv, max_seq), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, n_kv, max_seq, head_dim), dtype),
        "v": jnp.zeros((batch, n_kv, max_seq, head_dim), dtype),
    }


def _quant_kv(x):
    """(B,KV,1,D) -> int8 codes + per-token scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


# ------------------------------------------------------------ paged cache ---
def paged_kv_cache_init(num_pages: int, page_size: int, n_kv: int,
                        head_dim: int, dtype, bits: int = 16) -> dict:
    """Page-pool KV storage: ``(P, KV, page_size, D)`` instead of the dense
    ``(B, KV, max_seq, D)``.  Page ``i`` of a slot's block table covers that
    slot's positions ``[i*page_size, (i+1)*page_size)``; the pool is shared
    across batch slots, so cache memory scales with live tokens (pages in
    use), not ``B * max_seq``.  Page 0 is reserved as the trash page for
    inactive slots."""
    if bits == 8:
        return {
            "k": jnp.zeros((num_pages, n_kv, page_size, head_dim), jnp.int8),
            "v": jnp.zeros((num_pages, n_kv, page_size, head_dim), jnp.int8),
            "k_scale": jnp.zeros((num_pages, n_kv, page_size), jnp.float32),
            "v_scale": jnp.zeros((num_pages, n_kv, page_size), jnp.float32),
        }
    return {
        "k": jnp.zeros((num_pages, n_kv, page_size, head_dim), dtype),
        "v": jnp.zeros((num_pages, n_kv, page_size, head_dim), dtype),
    }


def paged_kv_insert(pool: dict, dense: dict, pages: jnp.ndarray,
                    lead: int = 0) -> dict:
    """Scatter a batch-1 dense cache (filled by ``attn_prefill``) into pool
    pages ``pages`` (n,).  ``lead`` counts leading stack dims (layer/group
    axes) shared by both trees; the dense seq length must be
    ``n * page_size``."""
    idx = (slice(None),) * lead
    n = pages.shape[0]
    ps = pool["k"].shape[lead + 2]
    out = {}
    for key in ("k", "v"):
        d = dense[key][idx + (0,)]  # lead + (KV, n*ps, D)
        kv, dd = d.shape[lead], d.shape[-1]
        d = d.reshape(d.shape[:lead] + (kv, n, ps, dd))
        d = jnp.moveaxis(d, lead + 1, lead)  # lead + (n, KV, ps, D)
        out[key] = pool[key].at[idx + (pages,)].set(d.astype(pool[key].dtype))
    for key in ("k_scale", "v_scale"):
        if key in pool:
            d = dense[key][idx + (0,)]  # lead + (KV, n*ps)
            kv = d.shape[lead]
            d = d.reshape(d.shape[:lead] + (kv, n, ps))
            d = jnp.moveaxis(d, lead + 1, lead)
            out[key] = pool[key].at[idx + (pages,)].set(d)
    return out


def _paged_kernel_takes(head_dim: int) -> bool:
    """Whether ``attn_decode_paged`` reads its pages through
    ``kernels.paged_attention`` (the kernel dispatch of ``common.linear``).
    On the chip Mosaic slices a page out of the pool only where its rows
    fill whole 128-lane tiles; other head sizes keep the gather there."""
    if not _kernels_enabled():
        return False
    from repro.kernels.ops import _interpret
    from repro.kernels.paged_attention import LANES

    return _interpret() or head_dim % LANES == 0


def attn_decode_paged(
    p: dict,
    x: jnp.ndarray,  # (B, 1, D)
    cache: dict,  # paged pool leaves, see paged_kv_cache_init
    block_tables: jnp.ndarray,  # (B, W) int32 page ids
    pos: jnp.ndarray,  # (B,) int32 per-slot lengths
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 0.0,
    page_size: int,
) -> tuple[jnp.ndarray, dict]:
    """One decode step against the paged KV pool: scatter the new token's
    K/V into its slot's current page, then attend to the slot's first
    ``pos + 1`` positions.  An unquantized pool is read in place, only the
    live pages, by ``kernels.paged_attention`` where the kernels are on
    (``_paged_kernel_takes``); otherwise the slot's whole block-table row is
    gathered at the contraction and the dead positions masked.  Per-slot
    ``pos`` makes every batch slot independent — the carry the
    continuous-batching scheduler steps."""
    b = x.shape[0]
    ps = page_size
    q = _split_heads(linear(x, p["wq"], p.get("bq")), n_heads, head_dim)
    k = _split_heads(linear(x, p["wk"], p.get("bk")), n_kv, head_dim)
    v = _split_heads(linear(x, p["wv"], p.get("bv")), n_kv, head_dim)
    if rope_theta:
        pvec = pos[:, None]  # (B, 1)
        q = apply_rope(q, pvec, rope_theta)
        k = apply_rope(k, pvec, rope_theta)
    k_t = k.transpose(0, 2, 1, 3)  # (B, KV, 1, D)
    v_t = v.transpose(0, 2, 1, 3)
    page = jnp.take_along_axis(block_tables, (pos // ps)[:, None], axis=1)[:, 0]
    off = pos % ps
    with jax.named_scope("kv_write"):
        quantized = "k_scale" in cache
        new_cache = dict(cache)
        if quantized:
            k_codes, k_sc = _quant_kv(k_t)
            v_codes, v_sc = _quant_kv(v_t)
            new_cache["k"] = cache["k"].at[page, :, off, :].set(k_codes[:, :, 0, :])
            new_cache["v"] = cache["v"].at[page, :, off, :].set(v_codes[:, :, 0, :])
            new_cache["k_scale"] = cache["k_scale"].at[page, :, off].set(k_sc[:, :, 0])
            new_cache["v_scale"] = cache["v_scale"].at[page, :, off].set(v_sc[:, :, 0])
        else:
            # One head_dim row per slot and KV head.  For a (KV, D) window
            # XLA lays the pool out with KV minor, and copies the whole pool
            # back to row-major for paged_decode_attention, every layer.
            row = (page[:, None], jnp.arange(n_kv)[None, :], off[:, None])
            new_cache["k"] = cache["k"].at[row].set(
                k_t[:, :, 0, :].astype(cache["k"].dtype))
            new_cache["v"] = cache["v"].at[row].set(
                v_t[:, :, 0, :].astype(cache["v"].dtype))
    with jax.named_scope("attention"):
        if not quantized and _paged_kernel_takes(head_dim):
            # Read each slot's live pages in place.
            from repro.kernels.ops import _interpret
            from repro.kernels.paged_attention import paged_decode_attention

            o = paged_decode_attention(
                q.reshape(b, n_heads, head_dim), new_cache["k"],
                new_cache["v"], block_tables, pos + 1,
                interpret=_interpret())
        else:
            o = paged_gather_attention(q, new_cache, block_tables, pos,
                                       n_kv=n_kv, dtype=x.dtype)
    o = o.reshape(b, 1, n_heads * head_dim).astype(x.dtype)
    return linear(o, p["wo"]), new_cache


def paged_gather_attention(q, pool: dict, block_tables, pos, *, n_kv: int,
                           dtype) -> jnp.ndarray:
    """Attention of ``q`` (B, 1, H, D) over each slot's first ``pos + 1``
    positions, by gathering the slot's whole block-table row of ``pool``
    (``k``/``v``; an int8 pool's codes times ``k_scale``/``v_scale``,
    dequantized to ``dtype``) and masking the dead positions.  Returns
    (B, 1, KV, H / KV, D) float32."""
    b, _, n_heads, head_dim = q.shape
    ps = pool["k"].shape[2]
    # Gather the slot's pages: (B, W, KV, ps, D) -> (B, KV, W*ps, D) — the
    # same head-major layout the dense contraction reads, just assembled
    # from the block table.  The gather is a transient; only the pool
    # persists.
    seq = block_tables.shape[1] * ps

    def gather(leaf):
        g = leaf[block_tables]  # (B, W, KV, ps, ...)
        g = jnp.moveaxis(g, 1, 2)  # (B, KV, W, ps, ...)
        return g.reshape((b, n_kv, seq) + g.shape[4:])

    if "k_scale" in pool:
        ck = gather(pool["k"]).astype(dtype) \
            * gather(pool["k_scale"])[..., None].astype(dtype)
        cv = gather(pool["v"]).astype(dtype) \
            * gather(pool["v_scale"])[..., None].astype(dtype)
    else:
        ck = gather(pool["k"])
        cv = gather(pool["v"])
    g = n_heads // n_kv
    qg = q.reshape(b, 1, n_kv, g, head_dim).astype(ck.dtype)
    s = jnp.einsum("bqhgd,bhkd->bhgqk", qg, ck,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(head_dim)
    valid = jnp.arange(seq)[None, None, None, None, :] \
        <= pos[:, None, None, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bhkd->bqhgd", w.astype(cv.dtype), cv,
                      preferred_element_type=jnp.float32)


def attn_verify(
    p: dict,
    x: jnp.ndarray,  # (B, T, D)
    cache: dict,
    pos: jnp.ndarray,  # (B,) int32 per-row lengths (tokens already cached)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 0.0,
    block_tables: Optional[jnp.ndarray] = None,
    page_size: int = 0,
    tree: Optional[tuple[int, int]] = None,
) -> tuple[jnp.ndarray, dict]:
    """T-token decode for speculative verification: consume T proposed
    tokens at per-row positions ``pos .. pos+T-1`` against an existing cache
    (dense or paged), causal *within* the window and over the cached prefix.

    ``tree=(fan, depth)`` switches the window to a fan-of-chains candidate
    tree in node order (``T == 1 + fan*depth``, see ``tree_layout``): write
    columns stay ``pos + node``, rope positions become ``pos + dep[node]``,
    and the causal mask is replaced by the shared-prefix ancestor mask, so
    each chain scores exactly as if it were verified alone.

    Per query t the math is exactly ``attn_decode``'s — same projections,
    same f32 score accumulation, same masked softmax over the full store —
    so greedy verification reproduces the per-token path's argmax.  Rollback
    of rejected positions is free by construction: positions ``> pos + a``
    are (1) never attended by later steps, whose masks stop at their own
    frontier, and (2) rewritten by the next verify window, which starts at
    the accepted frontier ``pos + a + 1``.  Writes that would land past the
    store (``pos + t >= max_seq``, only reachable by already-finished rows)
    are dropped (dense) or routed to the trash page (paged)."""
    b, t, _ = x.shape
    q = _split_heads(linear(x, p["wq"], p.get("bq")), n_heads, head_dim)
    k = _split_heads(linear(x, p["wk"], p.get("bk")), n_kv, head_dim)
    v = _split_heads(linear(x, p["wv"], p.get("bv")), n_kv, head_dim)
    posm = pos[:, None] + jnp.arange(t, dtype=pos.dtype)[None, :]  # (B, T)
    if tree is None:
        posr = posm  # linear window: logical position == write column
    else:
        dep, _ = tree_layout(*tree)
        posr = pos[:, None] + jnp.asarray(dep, pos.dtype)[None, :]
    if rope_theta:
        q = apply_rope(q, posr, rope_theta)
        k = apply_rope(k, posr, rope_theta)
    quantized = "k_scale" in cache
    # k/v are already (B, T, KV, D) — the scatter-row layout — and
    # _quant_kv reduces over the last axis, so it applies in place.
    if quantized:
        k_rows, ks_rows = _quant_kv(k)  # (B, T, KV, D), (B, T, KV)
        v_rows, vs_rows = _quant_kv(v)
    else:
        k_rows = k.astype(cache["k"].dtype)
        v_rows = v.astype(cache["v"].dtype)

    with jax.named_scope("kv_write"):
        new_cache = dict(cache)
        if block_tables is None:
            seq = cache["k"].shape[2]
            rows = jnp.arange(b)[:, None]  # (B, 1) broadcasts with posm
            col = jnp.where(posm < seq, posm, seq)  # out-of-store -> dropped
            new_cache["k"] = cache["k"].at[rows, :, col, :].set(k_rows, mode="drop")
            new_cache["v"] = cache["v"].at[rows, :, col, :].set(v_rows, mode="drop")
            if quantized:
                new_cache["k_scale"] = cache["k_scale"].at[rows, :, col].set(
                    ks_rows, mode="drop")
                new_cache["v_scale"] = cache["v_scale"].at[rows, :, col].set(
                    vs_rows, mode="drop")
        else:
            ps = page_size
            w_pages = block_tables.shape[1]
            seq = w_pages * ps
            logical = jnp.clip(posm // ps, 0, w_pages - 1)
            page = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, T)
            page = jnp.where(posm < seq, page, 0)  # past the store -> trash page
            off = posm % ps
            new_cache["k"] = cache["k"].at[page, :, off, :].set(k_rows)
            new_cache["v"] = cache["v"].at[page, :, off, :].set(v_rows)
            if quantized:
                new_cache["k_scale"] = cache["k_scale"].at[page, :, off].set(ks_rows)
                new_cache["v_scale"] = cache["v_scale"].at[page, :, off].set(vs_rows)

    with jax.named_scope("attention"):
        if block_tables is None:
            def fetch(key):
                return new_cache[key]
        else:
            def fetch(key):
                g = new_cache[key][block_tables]  # (B, W, KV, ps, ...)
                g = jnp.moveaxis(g, 1, 2)  # (B, KV, W, ps, ...)
                return g.reshape((b, n_kv, seq) + g.shape[4:])

        if quantized:
            ck = fetch("k").astype(x.dtype) * fetch("k_scale")[..., None].astype(x.dtype)
            cv = fetch("v").astype(x.dtype) * fetch("v_scale")[..., None].astype(x.dtype)
        else:
            ck, cv = fetch("k"), fetch("v")
        g = n_heads // n_kv
        qg = q.reshape(b, t, n_kv, g, head_dim).astype(ck.dtype)
        s = jnp.einsum("bqhgd,bhkd->bhgqk", qg, ck,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(head_dim)
        if tree is None:
            # query t's frontier is pos + t: the cached prefix plus the
            # window's earlier tokens and itself — causal across cache and
            # window at once.
            valid = (jnp.arange(ck.shape[2])[None, None, None, None, :]
                     <= posm[:, None, None, :, None])
        else:
            _, vis = tree_layout(*tree)
            valid = _tree_valid(vis, pos, t, ck.shape[2])[:, None, None, :, :]
        s = jnp.where(valid, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bhkd->bqhgd", w.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
    o = o.reshape(b, t, n_heads * head_dim).astype(x.dtype)
    return linear(o, p["wo"]), new_cache


def attn_decode(
    p: dict,
    x: jnp.ndarray,  # (B, 1, D)
    cache: dict,
    pos: jnp.ndarray,  # scalar int32: current length (tokens already cached)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 0.0,
) -> tuple[jnp.ndarray, dict]:
    """One decode step against a preallocated KV cache."""
    b = x.shape[0]
    q = _split_heads(linear(x, p["wq"], p.get("bq")), n_heads, head_dim)
    k = _split_heads(linear(x, p["wk"], p.get("bk")), n_kv, head_dim)
    v = _split_heads(linear(x, p["wv"], p.get("bv")), n_kv, head_dim)
    if rope_theta:
        pvec = jnp.full((b, 1), pos, jnp.int32)
        q = apply_rope(q, pvec, rope_theta)
        k = apply_rope(k, pvec, rope_theta)
    quantized = "k_scale" in cache
    k_t = k.transpose(0, 2, 1, 3)  # (B,KV,1,D)
    v_t = v.transpose(0, 2, 1, 3)
    new_cache = {}
    if quantized:
        k_codes, k_sc = _quant_kv(k_t)
        v_codes, v_sc = _quant_kv(v_t)
        ck8 = jax.lax.dynamic_update_slice(cache["k"], k_codes, (0, 0, pos, 0))
        cv8 = jax.lax.dynamic_update_slice(cache["v"], v_codes, (0, 0, pos, 0))
        cks = jax.lax.dynamic_update_slice(cache["k_scale"], k_sc, (0, 0, pos))
        cvs = jax.lax.dynamic_update_slice(cache["v_scale"], v_sc, (0, 0, pos))
        new_cache = {"k": ck8, "v": cv8, "k_scale": cks, "v_scale": cvs}
        # dequant at the compute boundary (fuses into the contraction on TPU)
        ck = ck8.astype(x.dtype) * cks[..., None].astype(x.dtype)
        cv = cv8.astype(x.dtype) * cvs[..., None].astype(x.dtype)
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_t.astype(cache["k"].dtype), (0, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_t.astype(cache["v"].dtype), (0, 0, pos, 0))
        new_cache = {"k": ck, "v": cv}
    g = n_heads // n_kv
    # Keep the cache in its storage dtype through the contraction: upcasting
    # with .astype(f32) materialised (and all-gathered) a full f32 copy of
    # the 2S-byte cache per step — 2x the HBM + ICI bytes (EXPERIMENTS.md
    # §Perf, starcoder2-15b decode iteration 1).  preferred_element_type
    # keeps the accumulator in f32 without touching operand storage.
    qg = q.reshape(b, 1, n_kv, g, head_dim).astype(ck.dtype)
    s = jnp.einsum("bqhgd,bhkd->bhgqk", qg, ck,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(head_dim)
    valid = jnp.arange(ck.shape[2])[None, None, None, None, :] <= pos
    s = jnp.where(valid, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bqhgd", w.astype(cv.dtype), cv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, n_heads * head_dim).astype(x.dtype)
    return linear(o, p["wo"]), new_cache
