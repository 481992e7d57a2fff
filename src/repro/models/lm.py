"""Full model assembly for all six architecture families.

Homogeneous layer stacks are *scanned* (``lax.scan`` over stacked params):
one trace per block type regardless of depth, which keeps HLO size and
compile time bounded for the 100-layer dry-run cells.  Heterogeneous
patterns (hybrid shared-attention, VLM cross-attn groups) scan over repeating
groups.  Every scanned block body is wrapped in ``jax.checkpoint`` so
training remat saves only layer boundaries.

Public API:
  init_params(cfg, key)          -> params pytree
  forward(params, cfg, batch)    -> (logits, aux)      [train / prefill]
  loss_fn(params, cfg, batch)    -> (loss, metrics)
  init_cache(cfg, batch, max_seq)-> cache pytree        [decode]
  decode_step(params, cfg, tokens, cache, pos, extras) -> (logits, cache)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from . import blocks as bk
from .attention import (
    attn_apply,
    attn_decode,
    attn_decode_paged,
    attn_init,
    attn_prefill,
    attn_verify,
    kv_cache_init,
    paged_kv_cache_init,
    paged_kv_insert,
)
from .common import (
    cross_entropy,
    dtype_of,
    embed_init,
    embed_lookup,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    split_keys,
    unembed,
)


def _stack_init(key, n: int, fn, prepare=None):
    """``n`` layers of ``fn(key_i)`` stacked on a new leading dim.

    Built one layer at a time (``lax.map``), so the float32 draws behind
    each layer are the only full-precision temporaries — never a float32
    copy of the whole stack.  ``prepare`` maps each layer's subtree inside
    the loop (see ``init_params``)."""
    if n == 0:
        return None
    prepare = prepare or (lambda t: t)
    return jax.lax.map(lambda k: prepare(fn(k)), jax.random.split(key, n))


def _stack_cache(cache, n: int):
    return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype), cache)


def _scan(stack, x, body, remat: bool = True):
    b = jax.checkpoint(body) if remat else body

    def f(h, lp):
        out = b(lp, h)
        if isinstance(out, tuple):
            return out
        return out, None

    return jax.lax.scan(f, x, stack)


def _scan_cached(stack, caches, x, body):
    def f(h, xs):
        lp, c = xs
        h, c_new = body(lp, h, c)
        return h, c_new

    return jax.lax.scan(f, x, (stack, caches))


# ---------------------------------------------------------------- init ------
def init_params(cfg: ModelConfig, key, *, prepare=None) -> dict:
    """Random parameters for ``cfg``.

    ``prepare`` (optional) maps every layer's subtree as it is built, one
    layer at a time inside the stacked init loop, and every unstacked
    subtree once; values are the same as applying it to the finished tree.
    ``serving.init_serving_params`` quantizes through it, so a full-width
    model never holds its full-precision stacks on a device."""
    dtype = dtype_of(cfg.param_dtype)
    k_emb, k_layers, k_extra, k_head = split_keys(key, 4)
    p: dict[str, Any] = {
        "embed": embed_init(k_emb, cfg.vocab, cfg.d_model, dtype),
        "ln_f": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = embed_init(k_head, cfg.vocab, cfg.d_model, dtype).T

    fam = cfg.family
    if fam == "dense":
        p["layers"] = _stack_init(
            k_layers, cfg.n_layers, lambda k: bk.dense_block_init(k, cfg, dtype),
            prepare)
    elif fam == "moe":
        nd = cfg.n_dense_layers
        p["dense_layers"] = _stack_init(
            k_extra, nd, lambda k: bk.dense_block_init(k, cfg, dtype, d_ff=cfg.d_ff),
            prepare)
        p["layers"] = _stack_init(
            k_layers, cfg.n_layers - nd, lambda k: bk.moe_block_init(k, cfg, dtype),
            prepare)
    elif fam == "ssm":
        p["layers"] = _stack_init(
            k_layers, cfg.n_layers, lambda k: bk.ssm_block_init(k, cfg, dtype),
            prepare)
    elif fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every  # groups of ssm layers
        tail = cfg.n_layers - g * cfg.attn_every
        k1, k2, k3 = split_keys(k_layers, 3)
        p["groups"] = _stack_init(
            k1, g,
            lambda k: _stack_init(k, cfg.attn_every,
                                  lambda kk: bk.ssm_block_init(kk, cfg, dtype),
                                  prepare),
        )
        p["tail"] = _stack_init(
            k2, tail, lambda k: bk.ssm_block_init(k, cfg, dtype), prepare
        )
        p["shared_attn"] = bk.dense_block_init(k3, cfg, dtype)  # ONE shared block
        if prepare is not None:
            p["shared_attn"] = prepare(p["shared_attn"])
    elif fam == "vlm":
        every = cfg.vision.cross_attn_every
        g = cfg.n_layers // every
        k1, k2 = split_keys(k_layers, 2)
        p["groups"] = {
            "self": _stack_init(
                k1, g,
                lambda k: _stack_init(k, every - 1,
                                      lambda kk: bk.dense_block_init(kk, cfg, dtype),
                                      prepare),
            ),
            "cross": _stack_init(
                k2, g, lambda k: bk.cross_block_init(k, cfg, dtype), prepare
            ),
        }
    elif fam == "encdec":
        k1, k2 = split_keys(k_layers, 2)
        p["encoder"] = _stack_init(
            k1, cfg.n_layers, lambda k: bk.dense_block_init(k, cfg, dtype),
            prepare)
        p["decoder"] = _stack_init(
            k2, cfg.dec_layers, lambda k: _encdec_dec_block_init(k, cfg, dtype),
            prepare)
    else:
        raise ValueError(fam)
    if prepare is not None:
        top = {k: p[k] for k in ("embed", "ln_f", "head") if k in p}
        p.update(prepare(top))
    return p


def _encdec_dec_block_init(key, cfg: ModelConfig, dtype) -> dict:
    ks, kx, km = split_keys(key, 3)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "self": attn_init(ks, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "cross": attn_init(kx, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, dtype),
        "ln3": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(km, cfg.d_model, cfg.d_ff, dtype),
    }


def _encdec_dec_block_apply(p, x, enc_out, cfg: ModelConfig):
    h = attn_apply(p["self"], rmsnorm(x, p["ln1"], cfg.norm_eps),
                   n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                   rope_theta=cfg.rope_theta, causal=True)
    x = x + h
    h = attn_apply(p["cross"], rmsnorm(x, p["ln2"], cfg.norm_eps),
                   n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                   rope_theta=0.0, causal=False, kv_input=enc_out)
    x = x + h
    return x + mlp_apply(p["mlp"], rmsnorm(x, p["ln3"], cfg.norm_eps))


# -------------------------------------------------------------- forward -----
def forward(params: dict, cfg: ModelConfig, batch: dict) -> tuple[jnp.ndarray, dict]:
    """Teacher-forced forward. Returns (logits, aux-losses)."""
    aux = {"aux_total": jnp.float32(0.0)}
    fam = cfg.family

    if fam == "encdec":
        enc_x = batch["frames"].astype(dtype_of(cfg.param_dtype))  # audio STUB
        enc_x, _ = _scan(params["encoder"], enc_x,
                         lambda lp, h: bk.dense_block_apply(lp, h, cfg, causal=False))
        x = embed_lookup(params["embed"], batch["dec_tokens"])
        x, _ = _scan(params["decoder"], x,
                     lambda lp, h: _encdec_dec_block_apply(lp, h, enc_x, cfg))
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        return unembed(x, params.get("head", params["embed"])), aux

    x = embed_lookup(params["embed"], batch["tokens"])

    if fam == "dense":
        x, _ = _scan(params["layers"], x,
                     lambda lp, h: bk.dense_block_apply(lp, h, cfg),
                     remat=cfg.remat)
    elif fam == "moe":
        if params.get("dense_layers") is not None:
            x, _ = _scan(params["dense_layers"], x,
                         lambda lp, h: bk.dense_block_apply(lp, h, cfg))
        x, auxs = _scan(params["layers"], x,
                        lambda lp, h: bk.moe_block_apply(lp, h, cfg))
        aux = {k: jnp.sum(v) for k, v in auxs.items()}
    elif fam == "ssm":
        x, _ = _scan(params["layers"], x,
                     lambda lp, h: bk.ssm_block_apply(lp, h, cfg))
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def group_body(gp, h):
            h, _ = _scan(gp, h, lambda lp, hh: bk.ssm_block_apply(lp, hh, cfg),
                         remat=False)
            return bk.dense_block_apply(shared, h, cfg)

        x, _ = _scan(params["groups"], x, group_body)
        if params.get("tail") is not None:
            x, _ = _scan(params["tail"], x,
                         lambda lp, h: bk.ssm_block_apply(lp, h, cfg))
    elif fam == "vlm":
        img = batch["image_embeds"].astype(x.dtype)  # vision STUB

        def group_body(gp, h):
            h, _ = _scan(gp["self"], h,
                         lambda lp, hh: bk.dense_block_apply(lp, hh, cfg),
                         remat=False)
            return bk.cross_block_apply(gp["cross"], h, img, cfg)

        x, _ = _scan(params["groups"], x, group_body)
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed(x, params.get("head", params["embed"])), aux


def loss_fn(params: dict, cfg: ModelConfig, batch: dict):
    logits, aux = forward(params, cfg, batch)
    tokens = batch["dec_tokens"] if cfg.family == "encdec" else batch["tokens"]
    labels = batch.get("labels")
    if labels is None:
        logits, labels = logits[:, :-1], tokens[:, 1:]
    if not cfg.logits_f32:
        logits = logits.astype(jnp.bfloat16)
    ce = cross_entropy(logits, labels)
    loss = ce + aux.get("aux_total", 0.0)
    metrics = {"loss": loss, "ce": ce, **{k: v for k, v in aux.items()}}
    return loss, metrics


# -------------------------------------------------------------- prefill -----
def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, S)
    cache: dict,
    extras: Optional[dict] = None,
    length=None,  # scalar int32: true prompt length for right-padded prompts
    pages=None,  # (n,) int32 pool page ids: write a paged cache directly
    slot=None,  # scalar int32: per-slot state row (SSM/conv) for paged admit
) -> tuple[jnp.ndarray, dict]:
    """Single-pass prefill: lowers the full-sequence forward ONCE over the
    whole prompt while filling the decode cache for all S positions.

    Replaces S sequential ``decode_step`` calls (the seed hot path): one XLA
    program instead of S Python dispatches, and the prompt's weight reads are
    amortised over S tokens — prefill runs compute-bound while decode stays
    in the paper's memory-bound regime.  ``cache`` must be fresh from
    ``init_cache`` (positions 0..S-1 empty).  Returns (logits (B,S,V), cache).

    ``length`` supports right-padded prompts (the continuous-batching admit
    path pads to a page multiple): causal attention already ignores trailing
    pads for the valid positions' logits and their K/V rows are overwritten
    or masked downstream, but SSM/conv state is sequential — ``length``
    masks pad steps so the carried state equals an unpadded prefill.

    With ``pages``/``slot``, ``cache`` is a PAGED tree (``init_paged_cache``)
    and ``tokens`` must be batch-1 with ``S == len(pages) * page_size``: the
    prompt's K/V (or MLA latents) scatter straight into the slot's pool
    pages and SSM/conv state lands in its per-slot row — the admit half of
    the continuous-batching scheduler without the temporary dense cache
    round-trip that ``models.paged_insert`` needed (paged_insert survives as
    the reference implementation for the equivalence test).
    """
    extras = extras or {}
    fam = cfg.family
    x = embed_lookup(params["embed"], tokens)
    new_cache = dict(cache)

    if fam == "dense":
        x, cs = _scan_cached(
            params["layers"], cache["layers"], x,
            lambda lp, h, c: bk.dense_block_prefill(lp, h, c, cfg, pages=pages),
        )
        new_cache["layers"] = cs
    elif fam == "moe":
        if params.get("dense_layers") is not None:
            x, cs = _scan_cached(
                params["dense_layers"], cache["dense_layers"], x,
                lambda lp, h, c: bk.dense_block_prefill(lp, h, c, cfg,
                                                        pages=pages),
            )
            new_cache["dense_layers"] = cs
        x, cs = _scan_cached(
            params["layers"], cache["layers"], x,
            lambda lp, h, c: bk.moe_block_prefill(lp, h, c, cfg, pages=pages),
        )
        new_cache["layers"] = cs
    elif fam == "ssm":
        x, cs = _scan_cached(
            params["layers"], cache["layers"], x,
            lambda lp, h, c: bk.ssm_block_prefill(lp, h, c, cfg, length=length,
                                                  slot=slot),
        )
        new_cache["layers"] = cs
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def f(h, xs):
            gp, sc, ac = xs
            h, ssm_new = _scan_cached(
                gp, sc, h,
                lambda lp, hh, cc: bk.ssm_block_prefill(lp, hh, cc, cfg,
                                                        length=length,
                                                        slot=slot)
            )
            h, attn_new = bk.dense_block_prefill(shared, h, ac, cfg,
                                                 pages=pages)
            return h, (ssm_new, attn_new)

        x, (ssm_cs, attn_cs) = jax.lax.scan(
            f, x, (params["groups"], cache["groups_ssm"], cache["groups_attn"])
        )
        new_cache["groups_ssm"], new_cache["groups_attn"] = ssm_cs, attn_cs
        if params.get("tail") is not None:
            x, cs = _scan_cached(
                params["tail"], cache["tail"], x,
                lambda lp, h, c: bk.ssm_block_prefill(lp, h, c, cfg,
                                                      length=length,
                                                      slot=slot),
            )
            new_cache["tail"] = cs
    elif fam == "vlm":
        img = extras["image_embeds"].astype(x.dtype)

        def f(h, xs):
            gp, c = xs
            h, cs = _scan_cached(
                gp["self"], c, h,
                lambda lp, hh, cc: bk.dense_block_prefill(lp, hh, cc, cfg,
                                                          pages=pages),
            )
            h = bk.cross_block_apply(gp["cross"], h, img, cfg)
            return h, cs

        x, cs = jax.lax.scan(f, x, (params["groups"], cache["groups_self"]))
        new_cache["groups_self"] = cs
    elif fam == "encdec":
        enc_out = extras["enc_out"].astype(x.dtype)

        def dec_block_prefill(lp, h, c):
            hh, c_new = attn_prefill(
                lp["self"], rmsnorm(h, lp["ln1"], cfg.norm_eps), c,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, pages=pages,
            )
            h = h + hh
            hh = attn_apply(
                lp["cross"], rmsnorm(h, lp["ln2"], cfg.norm_eps),
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=0.0, causal=False, kv_input=enc_out,
            )
            h = h + hh
            return h + mlp_apply(lp["mlp"], rmsnorm(h, lp["ln3"], cfg.norm_eps)), c_new

        x, cs = _scan_cached(params["decoder"], cache["decoder"], x, dec_block_prefill)
        new_cache["decoder"] = cs
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed(x, params.get("head", params["embed"])), new_cache


# --------------------------------------------------------------- decode -----
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    fam = cfg.family
    bits = cfg.kv_cache_bits
    if fam == "dense":
        c = kv_cache_init(batch, max_seq, cfg.n_kv_heads, cfg.head_dim, dtype,
                          bits=bits)
        return {"layers": _stack_cache(c, cfg.n_layers)}
    if fam == "moe":
        if cfg.mla:
            from .mla import mla_cache_init

            c = mla_cache_init(batch, max_seq, cfg.mla, dtype)
        else:
            c = kv_cache_init(batch, max_seq, cfg.n_kv_heads, cfg.head_dim, dtype)
        out = {"layers": _stack_cache(c, cfg.n_layers - cfg.n_dense_layers)}
        if cfg.n_dense_layers:
            cd = kv_cache_init(batch, max_seq, cfg.n_kv_heads, cfg.head_dim, dtype,
                               bits=bits)
            out["dense_layers"] = _stack_cache(cd, cfg.n_dense_layers)
        return out
    if fam == "ssm":
        return {"layers": _stack_cache(bk.ssm_cache_init(cfg, batch), cfg.n_layers)}
    if fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        tail = cfg.n_layers - g * cfg.attn_every
        ssm_c = bk.ssm_cache_init(cfg, batch)
        attn_c = kv_cache_init(batch, max_seq, cfg.n_kv_heads, cfg.head_dim, dtype,
                               bits=bits)
        out = {
            "groups_ssm": _stack_cache(_stack_cache(ssm_c, cfg.attn_every), g),
            "groups_attn": _stack_cache(attn_c, g),
        }
        if tail:
            out["tail"] = _stack_cache(ssm_c, tail)
        return out
    if fam == "vlm":
        every = cfg.vision.cross_attn_every
        g = cfg.n_layers // every
        c = kv_cache_init(batch, max_seq, cfg.n_kv_heads, cfg.head_dim, dtype,
                          bits=bits)
        return {"groups_self": _stack_cache(_stack_cache(c, every - 1), g)}
    if fam == "encdec":
        c = kv_cache_init(batch, max_seq, cfg.n_kv_heads, cfg.head_dim, dtype,
                          bits=bits)
        return {"decoder": _stack_cache(c, cfg.dec_layers)}
    raise ValueError(fam)


def init_paged_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     num_pages: int, page_size: int) -> dict:
    """Paged decode cache: the attention/MLA sequence state lives in
    per-layer page pools of ``num_pages`` pages of ``page_size`` tokens,
    shared across the ``batch`` slots; SSM/conv state stays per-slot dense
    (it is O(1) per slot — there is nothing to page).

    ``block_tables`` (batch, ceil(max_seq/page_size)) maps each slot's
    logical page i to a pool page id; ``decode_step`` dispatches to the
    paged attention path whenever this key is present.  Page 0 is the trash
    page for inactive slots, so usable capacity is ``num_pages - 1`` pages.
    Structure mirrors ``init_cache`` family-by-family."""
    if max_seq % page_size:
        max_seq += page_size - max_seq % page_size
    width = max_seq // page_size
    dtype = dtype_of(cfg.param_dtype)
    fam = cfg.family
    bits = cfg.kv_cache_bits
    out: dict[str, Any] = {
        "block_tables": jnp.zeros((batch, width), jnp.int32)}
    if fam == "dense":
        c = paged_kv_cache_init(num_pages, page_size, cfg.n_kv_heads,
                                cfg.head_dim, dtype, bits=bits)
        out["layers"] = _stack_cache(c, cfg.n_layers)
    elif fam == "moe":
        if cfg.mla:
            from .mla import mla_paged_cache_init

            c = mla_paged_cache_init(num_pages, page_size, cfg.mla, dtype)
        else:
            c = paged_kv_cache_init(num_pages, page_size, cfg.n_kv_heads,
                                    cfg.head_dim, dtype)
        out["layers"] = _stack_cache(c, cfg.n_layers - cfg.n_dense_layers)
        if cfg.n_dense_layers:
            cd = paged_kv_cache_init(num_pages, page_size, cfg.n_kv_heads,
                                     cfg.head_dim, dtype, bits=bits)
            out["dense_layers"] = _stack_cache(cd, cfg.n_dense_layers)
    elif fam == "ssm":
        out["layers"] = _stack_cache(bk.ssm_cache_init(cfg, batch), cfg.n_layers)
    elif fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        tail = cfg.n_layers - g * cfg.attn_every
        ssm_c = bk.ssm_cache_init(cfg, batch)
        attn_c = paged_kv_cache_init(num_pages, page_size, cfg.n_kv_heads,
                                     cfg.head_dim, dtype, bits=bits)
        out["groups_ssm"] = _stack_cache(_stack_cache(ssm_c, cfg.attn_every), g)
        out["groups_attn"] = _stack_cache(attn_c, g)
        if tail:
            out["tail"] = _stack_cache(ssm_c, tail)
    elif fam == "vlm":
        every = cfg.vision.cross_attn_every
        g = cfg.n_layers // every
        c = paged_kv_cache_init(num_pages, page_size, cfg.n_kv_heads,
                                cfg.head_dim, dtype, bits=bits)
        out["groups_self"] = _stack_cache(_stack_cache(c, every - 1), g)
    elif fam == "encdec":
        c = paged_kv_cache_init(num_pages, page_size, cfg.n_kv_heads,
                                cfg.head_dim, dtype, bits=bits)
        out["decoder"] = _stack_cache(c, cfg.dec_layers)
    else:
        raise ValueError(fam)
    return out


def _copy_slot(paged_tree, dense_tree, slot, lead: int):
    """Copy a batch-1 dense state tree into per-slot state at ``slot``.
    ``lead`` counts leading stack dims before the batch axis."""
    idx = (slice(None),) * lead

    def cp(pt, dt):
        return pt.at[idx + (slot,)].set(dt[idx + (0,)].astype(pt.dtype))

    return jax.tree.map(cp, paged_tree, dense_tree)


def paged_insert(cfg: ModelConfig, paged: dict, dense: dict, slot,
                 pages) -> dict:
    """Insert a freshly prefilled batch-1 dense cache into the paged cache:
    sequence leaves (attention K/V, MLA latents) are scattered into pool
    pages ``pages`` (n,) — the slot's block-table entries — and per-slot
    state leaves (SSM h / conv tail) are copied into row ``slot``.

    No longer on the serving hot path: admit now prefills STRAIGHT into the
    pages (``prefill(pages=, slot=)``).  Kept as the independent reference
    implementation the direct path is checked against byte-for-byte
    (tests/test_sharded_decode.py::test_direct_admit_matches_paged_insert_reference)."""
    fam = cfg.family
    out = dict(paged)
    if fam == "dense":
        out["layers"] = paged_kv_insert(paged["layers"], dense["layers"],
                                        pages, lead=1)
    elif fam == "moe":
        if cfg.mla:
            from .mla import mla_paged_insert

            out["layers"] = mla_paged_insert(paged["layers"], dense["layers"],
                                             pages, lead=1)
        else:
            out["layers"] = paged_kv_insert(paged["layers"], dense["layers"],
                                            pages, lead=1)
        if "dense_layers" in paged:
            out["dense_layers"] = paged_kv_insert(
                paged["dense_layers"], dense["dense_layers"], pages, lead=1)
    elif fam == "ssm":
        out["layers"] = _copy_slot(paged["layers"], dense["layers"], slot,
                                   lead=1)
    elif fam == "hybrid":
        out["groups_ssm"] = _copy_slot(paged["groups_ssm"],
                                       dense["groups_ssm"], slot, lead=2)
        out["groups_attn"] = paged_kv_insert(paged["groups_attn"],
                                             dense["groups_attn"], pages,
                                             lead=1)
        if "tail" in paged:
            out["tail"] = _copy_slot(paged["tail"], dense["tail"], slot,
                                     lead=1)
    elif fam == "vlm":
        out["groups_self"] = paged_kv_insert(paged["groups_self"],
                                             dense["groups_self"], pages,
                                             lead=2)
    elif fam == "encdec":
        out["decoder"] = paged_kv_insert(paged["decoder"], dense["decoder"],
                                         pages, lead=1)
    else:
        raise ValueError(fam)
    return out


def decode_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, 1)
    cache: dict,
    pos: jnp.ndarray,  # scalar int32; paged cache: (B,) per-slot lengths
    extras: Optional[dict] = None,
    page_size: int = 0,
) -> tuple[jnp.ndarray, dict]:
    """One decode step.  With a dense cache (``init_cache``) ``pos`` is a
    scalar shared by the whole batch.  With a paged cache
    (``init_paged_cache`` — detected by its ``block_tables`` key) ``pos`` is
    a per-slot (B,) vector and ``page_size`` must match the pool's page
    size: attention scatters/gathers through the block tables, which is what
    lets the continuous-batching scheduler step slots at different depths in
    one program."""
    extras = extras or {}
    fam = cfg.family
    bt = cache.get("block_tables")
    x = embed_lookup(params["embed"], tokens)
    new_cache = dict(cache)

    if bt is None:
        dense_body = lambda lp, h, c: bk.dense_block_decode(lp, h, c, pos, cfg)
        moe_body = lambda lp, h, c: bk.moe_block_decode(lp, h, c, pos, cfg)
    else:
        dense_body = lambda lp, h, c: bk.dense_block_decode_paged(
            lp, h, c, bt, pos, cfg, page_size)
        moe_body = lambda lp, h, c: bk.moe_block_decode_paged(
            lp, h, c, bt, pos, cfg, page_size)

    if fam == "dense":
        x, cs = _scan_cached(params["layers"], cache["layers"], x, dense_body)
        new_cache["layers"] = cs
    elif fam == "moe":
        if params.get("dense_layers") is not None:
            x, cs = _scan_cached(
                params["dense_layers"], cache["dense_layers"], x, dense_body,
            )
            new_cache["dense_layers"] = cs
        x, cs = _scan_cached(params["layers"], cache["layers"], x, moe_body)
        new_cache["layers"] = cs
    elif fam == "ssm":
        x, cs = _scan_cached(
            params["layers"], cache["layers"], x,
            lambda lp, h, c: bk.ssm_block_decode(lp, h, c, cfg),
        )
        new_cache["layers"] = cs
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def group_decode(gp, h, c):
            ssm_c, attn_c = c
            h, ssm_new = _scan_cached(
                gp, ssm_c, h, lambda lp, hh, cc: bk.ssm_block_decode(lp, hh, cc, cfg)
            )
            if bt is None:
                h, attn_new = bk.dense_block_decode(shared, h, attn_c, pos, cfg)
            else:
                h, attn_new = bk.dense_block_decode_paged(
                    shared, h, attn_c, bt, pos, cfg, page_size)
            return h, (ssm_new, attn_new)

        def f(h, xs):
            gp, sc, ac = xs
            h, (sn, an) = group_decode(gp, h, (sc, ac))
            return h, (sn, an)

        x, (ssm_cs, attn_cs) = jax.lax.scan(
            f, x, (params["groups"], cache["groups_ssm"], cache["groups_attn"])
        )
        new_cache["groups_ssm"], new_cache["groups_attn"] = ssm_cs, attn_cs
        if params.get("tail") is not None:
            x, cs = _scan_cached(
                params["tail"], cache["tail"], x,
                lambda lp, h, c: bk.ssm_block_decode(lp, h, c, cfg),
            )
            new_cache["tail"] = cs
    elif fam == "vlm":
        img = extras["image_embeds"].astype(x.dtype)

        def f(h, xs):
            gp, c = xs
            h, cs = _scan_cached(gp["self"], c, h, dense_body)
            h = bk.cross_block_apply(gp["cross"], h, img, cfg)
            return h, cs

        x, cs = jax.lax.scan(f, x, (params["groups"], cache["groups_self"]))
        new_cache["groups_self"] = cs
    elif fam == "encdec":
        enc_out = extras["enc_out"].astype(x.dtype)

        def dec_block_decode(lp, h, c):
            h_in = rmsnorm(h, lp["ln1"], cfg.norm_eps)
            if bt is None:
                hh, c_new = attn_decode(
                    lp["self"], h_in, c, pos,
                    n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                )
            else:
                hh, c_new = attn_decode_paged(
                    lp["self"], h_in, c, bt, pos,
                    n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                    page_size=page_size,
                )
            h = h + hh
            hh = attn_apply(
                lp["cross"], rmsnorm(h, lp["ln2"], cfg.norm_eps),
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=0.0, causal=False, kv_input=enc_out,
            )
            h = h + hh
            return h + mlp_apply(lp["mlp"], rmsnorm(h, lp["ln3"], cfg.norm_eps)), c_new

        x, cs = _scan_cached(params["decoder"], cache["decoder"], x, dec_block_decode)
        new_cache["decoder"] = cs
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed(x, params.get("head", params["embed"])), new_cache


# ---------------------------------------------------------- verify (spec) ---
def _tree_to_chains(x: jnp.ndarray, fan: int, depth: int) -> jnp.ndarray:
    """Node-order tree window ``(B, 1+fan*depth, ...)`` -> chain batch
    ``(B*fan, 1+depth, ...)``: each candidate chain gets the shared root
    prepended, so per-slot recurrences (SSM/conv state) can run every chain
    as an ordinary sequential verify window."""
    b = x.shape[0]
    root = jnp.broadcast_to(x[:, None, 0:1], (b, fan, 1) + x.shape[2:])
    chains = x[:, 1:].reshape((b, fan, depth) + x.shape[2:])
    return jnp.concatenate([root, chains], axis=2).reshape(
        (b * fan, 1 + depth) + x.shape[2:])


def _chains_to_tree(y: jnp.ndarray, fan: int, depth: int,
                    axis: int = 0) -> jnp.ndarray:
    """Inverse of ``_tree_to_chains`` along ``(axis, axis+1)``: chain batch
    ``(..., B*fan, 1+depth, ...)`` -> node order ``(..., B, 1+fan*depth,
    ...)``.  The root step is identical across a row's chains (same input,
    same starting state), so chain 0's copy stands for node 0."""
    y = jnp.moveaxis(y, (axis, axis + 1), (0, 1))
    b = y.shape[0] // fan
    y = y.reshape((b, fan, 1 + depth) + y.shape[2:])
    out = jnp.concatenate(
        [y[:, 0, 0:1], y[:, :, 1:].reshape((b, fan * depth) + y.shape[3:])],
        axis=1)
    return jnp.moveaxis(out, (0, 1), (axis, axis + 1))


def verify_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, T): last accepted token + T-1 proposed tokens
    cache: dict,
    pos: jnp.ndarray,  # (B,) int32 per-row lengths (tokens already cached)
    extras: Optional[dict] = None,
    page_size: int = 0,
    tree: Optional[tuple[int, int]] = None,
) -> tuple[jnp.ndarray, dict]:
    """Speculative-verify forward: run the target model ONCE over a window
    of T proposed tokens at per-row positions ``pos .. pos+T-1`` against an
    existing decode cache — one weight stream for up to T emitted tokens,
    the multiplier on the paper's weight-bytes-per-token bound.

    Per window position the math matches ``decode_step`` exactly (same
    projections, masks, and float association — the SSM families run the
    sequential per-token recurrence, not the chunked scan), so greedy
    acceptance against these logits reproduces the per-token decode's
    tokens.  The FULL logits matter, not just their argmax: sampled
    speculation (``serving.sampling.rejection_sample``) warps them into
    the target distribution ``p`` that proposals are accepted against, and
    the draft model's own verify-step logits supply the aligned proposal
    distribution ``q`` at the same positions — bit-equality with
    ``decode_step``'s logits is what makes rejection-sampled output
    distributionally identical to plain sampled decode AND
    key-deterministic across the dense/paged engines.

    Returns ``(logits (B,T,V), cache')`` where attention/MLA sequence
    leaves are already written in place for all T positions (rejected
    positions need no rollback: they are never attended by later frontiers
    and the next window rewrites them) and SSM/conv per-slot state leaves
    come back STACKED with a time axis after the batch axis — pass the
    result through ``commit_verify`` with the per-row accepted step to get
    a normal cache back.

    ``tree=(fan, depth)`` verifies a fan-of-chains candidate tree of
    ``T == 1 + fan*depth`` tokens in node order (``attention.tree_layout``):
    attention scores each node against the cached prefix plus its own
    root-path via the shared-prefix mask, and SSM/conv recurrences run each
    chain as an ordinary sequential window (``_tree_to_chains``) so every
    chain's logits are bit-identical to verifying that chain alone.  The
    stacked state time axis and the returned logits stay in node order —
    ``commit_verify`` selects by node index, and the accepted chain's
    attention rows are moved into linear positions by ``tree_relocate``."""
    extras = extras or {}
    fam = cfg.family
    bt = cache.get("block_tables")
    x = embed_lookup(params["embed"], tokens)
    new_cache = dict(cache)

    dense_body = lambda lp, h, c: bk.dense_block_verify(
        lp, h, c, bt, pos, cfg, page_size, tree=tree)
    moe_body = lambda lp, h, c: bk.moe_block_verify(
        lp, h, c, bt, pos, cfg, page_size, tree=tree)
    ssm_body = lambda lp, h, c: bk.ssm_block_verify(lp, h, c, cfg)
    if tree is not None:
        fan, dpt = tree
        tile = lambda c: jax.tree.map(lambda l: jnp.repeat(l, fan, axis=0), c)
        ssm_body = lambda lp, h, c: bk.ssm_block_verify(lp, h, tile(c), cfg)

    def ssm_stack(stack, caches, h):
        """Run an SSM layer stack; in tree mode convert the node-order
        window to per-chain windows around it (states come back stacked
        (L, B, T, ...) in node order either way)."""
        if tree is None:
            return _scan_cached(stack, caches, h, ssm_body)
        hc, cs = _scan_cached(stack, caches, _tree_to_chains(h, fan, dpt),
                              ssm_body)
        return (_chains_to_tree(hc, fan, dpt),
                jax.tree.map(lambda l: _chains_to_tree(l, fan, dpt, axis=1),
                             cs))

    if fam == "dense":
        x, cs = _scan_cached(params["layers"], cache["layers"], x, dense_body)
        new_cache["layers"] = cs
    elif fam == "moe":
        if params.get("dense_layers") is not None:
            x, cs = _scan_cached(
                params["dense_layers"], cache["dense_layers"], x, dense_body,
            )
            new_cache["dense_layers"] = cs
        x, cs = _scan_cached(params["layers"], cache["layers"], x, moe_body)
        new_cache["layers"] = cs
    elif fam == "ssm":
        x, cs = ssm_stack(params["layers"], cache["layers"], x)
        new_cache["layers"] = cs
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def f(h, xs):
            gp, sc, ac = xs
            h, ssm_new = ssm_stack(gp, sc, h)
            h, attn_new = dense_body(shared, h, ac)
            return h, (ssm_new, attn_new)

        x, (ssm_cs, attn_cs) = jax.lax.scan(
            f, x, (params["groups"], cache["groups_ssm"], cache["groups_attn"])
        )
        new_cache["groups_ssm"], new_cache["groups_attn"] = ssm_cs, attn_cs
        if params.get("tail") is not None:
            x, cs = ssm_stack(params["tail"], cache["tail"], x)
            new_cache["tail"] = cs
    elif fam == "vlm":
        img = extras["image_embeds"].astype(x.dtype)

        def f(h, xs):
            gp, c = xs
            h, cs = _scan_cached(gp["self"], c, h, dense_body)
            h = bk.cross_block_apply(gp["cross"], h, img, cfg)
            return h, cs

        x, cs = jax.lax.scan(f, x, (params["groups"], cache["groups_self"]))
        new_cache["groups_self"] = cs
    elif fam == "encdec":
        enc_out = extras["enc_out"].astype(x.dtype)

        def dec_block_verify(lp, h, c):
            hh, c_new = attn_verify(
                lp["self"], rmsnorm(h, lp["ln1"], cfg.norm_eps), c, pos,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                block_tables=bt, page_size=page_size, tree=tree,
            )
            h = h + hh
            hh = attn_apply(
                lp["cross"], rmsnorm(h, lp["ln2"], cfg.norm_eps),
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=0.0, causal=False, kv_input=enc_out,
            )
            h = h + hh
            return h + mlp_apply(lp["mlp"], rmsnorm(h, lp["ln3"], cfg.norm_eps)), c_new

        x, cs = _scan_cached(params["decoder"], cache["decoder"], x,
                             dec_block_verify)
        new_cache["decoder"] = cs
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed(x, params.get("head", params["embed"])), new_cache


def _select_step(tree, sel: jnp.ndarray, lead: int):
    """Select per-row step ``sel`` (B,) from verify-stacked state leaves
    shaped ``lead-dims + (B, T, ...)``, dropping the time axis."""

    def f(leaf):
        ax = lead + 1
        idx = sel.reshape((1,) * lead + (-1, 1) + (1,) * (leaf.ndim - lead - 2))
        picked = jnp.take_along_axis(leaf, idx.astype(jnp.int32), axis=ax)
        return jnp.squeeze(picked, axis=ax)

    return jax.tree.map(f, tree)


def stack_verify_caches(cfg: ModelConfig, caches: list) -> dict:
    """Merge a CHAIN of verify caches (successive windows over consecutive
    positions, each committed into the next) into one verify cache whose
    stacked time axis spans the whole chain: SSM/conv state leaves
    concatenate along their time axis, attention/MLA leaves take the last
    cache's (its in-place writes already accumulate the chain's).  Lets a
    draft's k+1 single-token steps be committed once at any accepted length
    without re-running the window."""
    fam = cfg.family
    out = dict(caches[-1])

    def cat(key, lead):
        return jax.tree.map(
            lambda *ls: jnp.concatenate(ls, axis=lead + 1),
            *[c[key] for c in caches])

    if fam == "ssm":
        out["layers"] = cat("layers", lead=1)
    elif fam == "hybrid":
        out["groups_ssm"] = cat("groups_ssm", lead=2)
        if "tail" in out:
            out["tail"] = cat("tail", lead=1)
    return out


def commit_verify(cfg: ModelConfig, cache: dict, sel: jnp.ndarray) -> dict:
    """Commit a ``verify_step`` cache: per batch row, keep the SSM/conv
    state after step ``sel[b]`` (0-indexed within the verify window — the
    row's accepted length minus one) and drop the stacked time axis.
    Attention/MLA leaves pass through: their rejected positions are rolled
    back implicitly by masking and the next window's rewrites."""
    fam = cfg.family
    out = dict(cache)
    if fam == "ssm":
        out["layers"] = _select_step(cache["layers"], sel, lead=1)
    elif fam == "hybrid":
        out["groups_ssm"] = _select_step(cache["groups_ssm"], sel, lead=2)
        if "tail" in cache:
            out["tail"] = _select_step(cache["tail"], sel, lead=1)
    return out


def _reloc_dense(arr, nlead: int, sax: int, pos, a, cf, depth: int):
    """Move ``a[b]`` rows of a dense sequence leaf ``lead-dims + (B, ...,
    S@sax, ...)`` from chain ``cf[b]``'s tree columns ``pos+1+cf*depth+i``
    to linear columns ``pos+1+i`` (masked scatter, gather-before-scatter so
    chain 0 relocation is the identity)."""
    sh = arr.shape
    x = arr.reshape((-1,) + sh[nlead:])  # (LL, B, ..., S, ...)
    x = jnp.moveaxis(x, sax, 2)  # (LL, B, S, rest...)
    x = jnp.moveaxis(x, 0, 1)  # (B, LL, S, rest...)
    seq = x.shape[2]
    steps = jnp.arange(depth, dtype=pos.dtype)
    src = pos[:, None] + 1 + cf[:, None] * depth + steps[None, :]  # (B, D)
    dst = pos[:, None] + 1 + steps[None, :]

    def one(xb, s_row, d_row, a_b):
        rows = xb[:, jnp.clip(s_row, 0, seq - 1)]  # (LL, D, rest...)
        d_ok = jnp.where(steps < a_b, d_row, seq)  # out-of-range -> dropped
        return xb.at[:, d_ok].set(rows, mode="drop")

    x = jax.vmap(one)(x, src, dst, a)
    x = jnp.moveaxis(x, 1, 0)
    return jnp.moveaxis(x, 2, sax).reshape(sh)


def _reloc_paged(arr, nlead: int, sax: int, bt, pos, a, cf, depth: int,
                 ps: int):
    """Paged-pool variant of ``_reloc_dense``: source/destination columns go
    through the block tables; masked or out-of-store destinations route to
    an out-of-range page and are dropped."""
    sh = arr.shape
    x = arr.reshape((-1,) + sh[nlead:])  # (LL, NP, ...)
    x = jnp.moveaxis(x, sax, 2)  # (LL, NP, ps, rest...)
    npg = x.shape[1]
    w = bt.shape[1]
    steps = jnp.arange(depth, dtype=pos.dtype)
    src = pos[:, None] + 1 + cf[:, None] * depth + steps[None, :]  # (B, D)
    dst = pos[:, None] + 1 + steps[None, :]
    sp = jnp.take_along_axis(bt, jnp.clip(src // ps, 0, w - 1), axis=1)
    sp = jnp.where(src < w * ps, sp, 0)
    dp = jnp.take_along_axis(bt, jnp.clip(dst // ps, 0, w - 1), axis=1)
    dp = jnp.where((steps[None, :] < a[:, None]) & (dst < w * ps), dp, npg)
    rows = x[:, sp, src % ps]  # (LL, B, D, rest...)
    x = x.at[:, dp, dst % ps].set(rows, mode="drop")
    return jnp.moveaxis(x, 2, sax).reshape(sh)


def tree_relocate(cfg: ModelConfig, cache: dict, pos: jnp.ndarray,
                  a: jnp.ndarray, cf: jnp.ndarray, *, fan: int, depth: int,
                  page_size: int = 0) -> dict:
    """After tree verification accepted ``a[b]`` draft tokens from chain
    ``cf[b]``, rewrite the accepted chain's attention/MLA rows from their
    tree columns ``pos+1+cf*depth .. pos+cf*depth+a`` into the linear
    columns ``pos+1 .. pos+a`` the next window's frontier mask expects.
    SSM/conv per-slot state is positionless — ``commit_verify`` with the
    node-order step index already handles it.  Requires the store to be
    over-provisioned by ``fan*depth`` columns past ``max_seq`` so tree
    columns of rows near the cap stay addressable (mirrors the draft-mode
    reserve in the engines)."""
    bt = cache.get("block_tables")
    fam = cfg.family
    out = dict(cache)

    def reloc(sub: dict, nlead: int) -> dict:
        new = {}
        for kk, vv in sub.items():
            sax = -1 if kk.endswith("_scale") else -2
            if bt is None:
                new[kk] = _reloc_dense(vv, nlead, sax, pos, a, cf, depth)
            else:
                new[kk] = _reloc_paged(vv, nlead, sax, bt, pos, a, cf, depth,
                                       page_size)
        return new

    if fam in ("dense", "moe"):
        out["layers"] = reloc(cache["layers"], 1)
        if fam == "moe" and "dense_layers" in cache:
            out["dense_layers"] = reloc(cache["dense_layers"], 1)
    elif fam == "hybrid":
        out["groups_attn"] = reloc(cache["groups_attn"], 1)
    elif fam == "vlm":
        out["groups_self"] = reloc(cache["groups_self"], 2)
    elif fam == "encdec":
        out["decoder"] = reloc(cache["decoder"], 1)
    return out


def encode(params: dict, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """Encoder-only pass (enc-dec serving: run once, feed decode_step)."""
    assert cfg.family == "encdec"
    x = frames.astype(dtype_of(cfg.param_dtype))
    x, _ = _scan(params["encoder"], x,
                 lambda lp, h: bk.dense_block_apply(lp, h, cfg, causal=False))
    return x
