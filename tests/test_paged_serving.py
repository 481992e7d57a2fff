"""Continuous batching on the paged KV cache.

Parity: paged-cache decode must be token-identical (greedy) to dense-cache
decode across all six families, under random block-table permutations
(``page_alloc_seed`` shuffles the free list, so physical page placement is
arbitrary), and under staggered admit/retire (each request's tokens equal a
solo run).  Scheduler: stop-token retirement, page accounting, recompute
preemption.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import FAMILY_ARCHS, assert_serve_matches_solo, setup_family as _setup

from repro.configs import get_reduced
from repro.models import init_params
from repro.serving import (
    ContinuousBatchingEngine,
    Request,
    ServingEngine,
    mask_after_stop,
    pim_bytes,
    quantize_tree,
)


# ------------------------------------------------------- paged/dense parity -
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_matches_dense_all_families(arch):
    """Greedy tokens from the paged continuous engine == the dense
    fixed-batch engine, with the free list shuffled so block tables are a
    random permutation of physical pages."""
    cfg, params, prompt, extras = _setup(arch)
    dense = ServingEngine(cfg, params, max_seq=16)
    want = np.asarray(dense.generate(prompt, n_new=5, extras=extras))
    paged = ContinuousBatchingEngine(
        cfg, params, slots=2, max_seq=16, page_size=4, chunk=4,
        page_alloc_seed=7)
    got = np.asarray(paged.generate(prompt, n_new=5, extras=extras))
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_block_table_permutations(seed):
    """Decode is layout-independent: any permutation of physical pages
    behind the block tables yields identical tokens."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    base = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                    page_size=4, chunk=4)
    want = np.asarray(base.generate(prompt, n_new=6))
    perm = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                    page_size=4, chunk=4,
                                    page_alloc_seed=seed)
    np.testing.assert_array_equal(want, np.asarray(perm.generate(prompt, n_new=6)))


def test_paged_matches_dense_int8_kv_and_pim_weights():
    """The quantized serving stack end-to-end: int8 KV page pools + int8 PIM
    weights still decode token-identically to the dense engine."""
    cfg = get_reduced("qwen2-1.5b").replace(kv_cache_bits=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    dense = ServingEngine(cfg, params, max_seq=16, pim_bits=8)
    want = np.asarray(dense.generate(prompt, n_new=5))
    paged = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                     page_size=4, chunk=4, pim_bits=8,
                                     page_alloc_seed=11)
    np.testing.assert_array_equal(want, np.asarray(paged.generate(prompt, n_new=5)))


# ------------------------------------------------------- scheduler behavior -
def test_per_request_extras_follow_the_request():
    """extras ride on the Request, not the slot: with more requests than
    slots, a request admitted into a freed slot must still be conditioned
    on its own image embeds — each output equals a solo dense run with that
    request's extras."""
    cfg = get_reduced("llama-3.2-vision-90b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_req = 4
    embeds = jax.random.normal(
        jax.random.PRNGKey(2),
        (n_req, cfg.vision.n_image_tokens, cfg.d_model))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=8).astype(np.int32),
                    max_new=m, extras={"image_embeds": embeds[i]})
            for i, m in enumerate([3, 6, 4, 5])]
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=2)
    outs = eng.serve(reqs)
    dense = ServingEngine(cfg, params, max_seq=16)
    for i, (r, got) in enumerate(zip(reqs, outs)):
        want = np.asarray(dense.generate(
            jnp.asarray(r.prompt)[None], r.max_new,
            extras={"image_embeds": embeds[i : i + 1]}))[0]
        np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "falcon-mamba-7b"])
def test_scheduler_staggered_matches_solo(arch):
    """More requests than slots, mixed (non-page-multiple) prompt lengths
    and max_new: every request's tokens must equal running it alone on the
    dense engine — admit/retire staggering and padded-prompt prefill
    (length-masked SSM state) must not leak across slots."""
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    shapes = [(5, 4), (7, 6), (3, 3), (9, 5), (4, 7)]
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=L).astype(np.int32),
                    max_new=m) for L, m in shapes]
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=24,
                                   page_size=4, chunk=3, page_alloc_seed=1)
    outs = eng.serve(reqs)
    dense = ServingEngine(cfg, params, max_seq=24)
    for r, got in zip(reqs, outs):
        want = np.asarray(
            dense.generate(jnp.asarray(r.prompt)[None], r.max_new))[0]
        np.testing.assert_array_equal(want, got)
    # With 2 slots over 5 mixed-length requests the pool never needs the
    # dense worst case (slots * max_seq tokens of cache).
    assert eng.peak_pages_in_use < eng.slots * eng.width


def test_scheduler_stop_token_retires_early():
    """A stop token ends the request's output at the stop token and frees
    its slot/pages (the continuous engine's real early-exit, vs the fixed
    engine's post-masking)."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    dense = ServingEngine(cfg, params, max_seq=16)
    base = np.asarray(dense.generate(prompt, n_new=6))
    stop = int(base[0, 3])
    first = int(np.argmax(base[0] == stop))  # first occurrence in row 0
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=2)
    outs = eng.serve([
        Request(prompt=np.asarray(prompt[0]), max_new=6, stop_tokens=(stop,)),
        Request(prompt=np.asarray(prompt[1]), max_new=6),
    ])
    np.testing.assert_array_equal(outs[0], base[0, : first + 1])
    assert outs[0][-1] == stop
    np.testing.assert_array_equal(outs[1], base[1])
    assert eng.pages_in_use() == 0  # everything retired -> pages freed


def test_scheduler_preemption_recomputes():
    """A pool too small for both requests triggers recompute preemption of
    the younger one; outputs still match solo runs exactly."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=32,
                                   page_size=4, num_pages=9, chunk=4)
    reqs = [Request(prompt=np.asarray(prompt[0]), max_new=20),
            Request(prompt=np.asarray(prompt[1]), max_new=20)]
    outs = eng.serve(reqs)
    assert eng.preemptions > 0
    dense = ServingEngine(cfg, params, max_seq=32)
    for r, got in zip(reqs, outs):
        want = np.asarray(
            dense.generate(jnp.asarray(r.prompt)[None], r.max_new))[0]
        np.testing.assert_array_equal(want, got)


def test_scheduler_rejects_oversized_request():
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ContinuousBatchingEngine(cfg, params, slots=1, max_seq=16,
                                   page_size=4)
    with pytest.raises(ValueError, match="max_seq"):
        eng.serve([Request(prompt=np.asarray(prompt[0]), max_new=100)])


# ------------------------------------------------- fixed-engine stop tokens -
def test_fixed_engine_stop_tokens_mask_after_stop():
    """ServingEngine.generate masks post-stop emissions with pad_id; the
    per-token oracle agrees."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ServingEngine(cfg, params, max_seq=16)
    base = np.asarray(eng.generate(prompt, n_new=6))
    stop = int(base[0, 0])
    got = np.asarray(eng.generate(prompt, n_new=6, stop_tokens=(stop,),
                                  pad_id=-1))
    ref = np.asarray(eng.generate_reference(prompt, n_new=6,
                                            stop_tokens=(stop,), pad_id=-1))
    np.testing.assert_array_equal(got, ref)
    for row_base, row in zip(base, got):
        hits = np.flatnonzero(row_base == stop)
        if hits.size:  # stop kept, everything after masked
            t = hits[0]
            np.testing.assert_array_equal(row[: t + 1], row_base[: t + 1])
            assert (row[t + 1 :] == -1).all()
        else:
            np.testing.assert_array_equal(row, row_base)


def test_reference_sampling_matches_scan():
    """generate_reference mirrors generate's sampling options and key-split
    order — one parity oracle for greedy AND sampled decoding."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ServingEngine(cfg, params, max_seq=16, pim_bits=8)
    k = jax.random.PRNGKey(5)
    a = np.asarray(eng.generate(prompt, n_new=6, greedy=False,
                                temperature=0.8, top_k=8, key=k))
    b = np.asarray(eng.generate_reference(prompt, n_new=6, greedy=False,
                                          temperature=0.8, top_k=8, key=k))
    np.testing.assert_array_equal(a, b)


# -------------------------------------------------- stop-token edge cases ---
def test_mask_after_stop_edge_positions():
    """Stop in the LAST emitted position masks nothing (there is no 'after');
    stop in the first position masks the whole tail; with multiple stop
    tokens the FIRST hit wins; the empty stop set is the identity."""
    toks = jnp.asarray([
        [1, 2, 3, 9],   # stop 9 at the last position: row unchanged
        [9, 1, 2, 3],   # stop at position 0: everything after -> pad
        [1, 9, 5, 2],   # stops 9 AND 5 present: mask after the FIRST (9)
        [1, 2, 3, 4],   # no stop: unchanged
    ], jnp.int32)
    out = np.asarray(mask_after_stop(toks, (9, 5), pad_id=-1))
    np.testing.assert_array_equal(out, [
        [1, 2, 3, 9],
        [9, -1, -1, -1],
        [1, 9, -1, -1],
        [1, 2, 3, 4],
    ])
    np.testing.assert_array_equal(np.asarray(mask_after_stop(toks, ())), toks)


def test_mask_after_stop_repeated_stop_token():
    """A second occurrence of the stop token is itself masked — only the
    first survives."""
    toks = jnp.asarray([[9, 9, 1, 9]], jnp.int32)
    out = np.asarray(mask_after_stop(toks, (9,), pad_id=0))
    np.testing.assert_array_equal(out, [[9, 0, 0, 0]])


def test_scheduler_stop_in_prompt_does_not_retire():
    """Stop tokens apply to EMITTED tokens only: a prompt that ends with the
    stop token must still decode its full budget."""
    cfg, params, prompt, _ = _setup("starcoder2-7b")
    dense = ServingEngine(cfg, params, max_seq=16)
    # find a stop value whose placement as the prompt's last token yields
    # emissions that never hit it (rewriting the prompt changes the
    # emissions, so check against the rewritten prompt's solo run)
    for stop in {int(t) for t in np.asarray(prompt[0])} | {0, 1, 7}:
        p0 = np.asarray(prompt[0]).copy()
        p0[-1] = stop  # stop token in the prompt's LAST position
        solo = np.asarray(dense.generate(jnp.asarray(p0)[None], 5))[0]
        if stop not in solo:
            break
    else:
        pytest.skip("fixture regression: every candidate re-emits the stop")
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=2)
    outs = eng.serve([Request(prompt=p0, max_new=5, stop_tokens=(stop,))])
    np.testing.assert_array_equal(outs[0], solo)  # full budget emitted


def test_scheduler_stop_at_exactly_max_new():
    """The stop token landing on the max_new-th (final) emission retires the
    request exactly once: output length == max_new, ends with the stop."""
    cfg, params, prompt, _ = _setup("starcoder2-7b")
    dense = ServingEngine(cfg, params, max_seq=16)
    base = np.asarray(dense.generate(prompt, n_new=6))
    # the latest emission (past the first) whose token first appears there,
    # so the stop's first hit is the final emission
    n = max(i + 1 for i in range(1, 6) if base[0, i] not in base[0, :i])
    stop = int(base[0, n - 1])
    assert stop not in base[0, : n - 1]  # first hit is the final emission
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=3)
    outs = eng.serve([Request(prompt=np.asarray(prompt[0]), max_new=n,
                              stop_tokens=(stop,))])
    np.testing.assert_array_equal(outs[0], base[0, :n])
    assert outs[0][-1] == stop and len(outs[0]) == n
    assert eng.pages_in_use() == 0


def test_scheduler_multiple_stops_in_one_chunk():
    """Two slots hitting their (different) stop tokens inside the SAME
    compiled chunk both truncate correctly and free their pages; a request
    with several stop tokens retires at whichever fires first."""
    cfg, params, prompt, _ = _setup("starcoder2-7b")
    dense = ServingEngine(cfg, params, max_seq=16)
    base = np.asarray(dense.generate(prompt, n_new=6))
    s0, s1 = int(base[0, 2]), int(base[1, 3])
    f0 = int(np.argmax(base[0] == s0))
    f1 = int(np.argmax(base[1] == s1))
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=6)  # one chunk covers all
    outs = eng.serve([
        Request(prompt=np.asarray(prompt[0]), max_new=6,
                stop_tokens=(s0, 255)),  # extra stop never fires
        Request(prompt=np.asarray(prompt[1]), max_new=6, stop_tokens=(s1,)),
    ])
    np.testing.assert_array_equal(outs[0], base[0, : f0 + 1])
    np.testing.assert_array_equal(outs[1], base[1, : f1 + 1])
    assert eng.pages_in_use() == 0


def test_fixed_engine_stop_at_exactly_n_new():
    """ServingEngine: a stop token on the last emitted position leaves the
    row unmasked (nothing comes after it)."""
    cfg, params, prompt, _ = _setup("starcoder2-7b")
    eng = ServingEngine(cfg, params, max_seq=16)
    base = np.asarray(eng.generate(prompt, n_new=5))
    # the latest emission whose token first appears there, so the stop's
    # first hit is the LAST emitted position of an n-token generate
    n = max(i + 1 for i in range(5) if base[0, i] not in base[0, :i])
    stop = int(base[0, n - 1])
    got = np.asarray(eng.generate(prompt, n_new=n, stop_tokens=(stop,),
                                  pad_id=-1))
    np.testing.assert_array_equal(got[0], base[0, :n])


# ------------------------------------------------- page rollback / reuse ----
@pytest.mark.parametrize("speculate", [None, 4])
def test_freed_pages_reused_after_retirement(speculate):
    """A pool far smaller than the trace's total page demand forces retired
    requests' pages to be re-issued to later admits; every request must
    still match its solo run — freed pages carry no ghost K/V (and, with
    speculation, no ghost speculative writes from their previous owner)."""
    cfg, params, _, _ = _setup("qwen2-1.5b")
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=L).astype(np.int32),
                    max_new=m)
            for L, m in [(6, 6), (5, 7), (8, 4), (7, 5), (4, 8), (6, 5),
                         (7, 6), (5, 6)]]
    ps, num_pages = 4, 10  # usable capacity: 9 pages
    demand = sum(-(-(len(r.prompt) + r.max_new) // ps) for r in reqs)
    assert demand > 2 * (num_pages - 1)  # reuse is forced, repeatedly
    eng = ContinuousBatchingEngine(
        cfg, params, slots=2, max_seq=16, page_size=ps, num_pages=num_pages,
        chunk=3, page_alloc_seed=5, speculate=speculate)
    assert_serve_matches_solo(eng, cfg, params, reqs)
    assert eng.pages_in_use() == 0  # everything retired -> all pages freed


@pytest.mark.parametrize("speculate", [None, 4])
def test_preemption_recompute_identical_tokens(speculate):
    """Recompute preemption frees the victim's pages mid-flight and
    re-admits it from scratch; tokens must be identical to solo runs —
    including when the freed pages contained speculative writes past the
    victim's accepted frontier."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ContinuousBatchingEngine(
        cfg, params, slots=2, max_seq=32, page_size=4, num_pages=9, chunk=4,
        speculate=speculate)
    reqs = [Request(prompt=np.asarray(prompt[0]), max_new=20),
            Request(prompt=np.asarray(prompt[1]), max_new=20)]
    assert_serve_matches_solo(eng, cfg, params, reqs)
    assert eng.preemptions > 0


def test_speculative_rejected_writes_do_not_leak_across_slots():
    """Two slots interleave speculative windows whose rejected tail writes
    land beyond their accepted frontiers; a page-permuted pool must still
    reproduce the dense engine exactly (rejected writes stay confined to
    each slot's own pages / the trash page)."""
    cfg, params, prompt, _ = _setup("falcon-mamba-7b")
    dense = ServingEngine(cfg, params, max_seq=24)
    want = np.asarray(dense.generate(prompt, n_new=8))
    for seed in (0, 11):
        eng = ContinuousBatchingEngine(
            cfg, params, slots=2, max_seq=24, page_size=4, chunk=2,
            page_alloc_seed=seed, speculate=6)
        got = np.asarray(eng.generate(prompt, n_new=8))
        np.testing.assert_array_equal(want, got, err_msg=f"seed={seed}")


# ------------------------------------------------------------- pim_bytes ----
def test_pim_bytes_skips_int4_markers():
    """The nibbles/nibbles_odd marker leaves are packing metadata, not
    shipped HBM storage — pim_bytes must count codes + scales only."""
    w = {"odd": jnp.zeros((33, 16)), "even": jnp.zeros((32, 16))}
    q = quantize_tree(w, bits=4)
    assert "nibbles_odd" in q["odd"] and "nibbles" in q["even"]
    want = sum(
        leaf.size * leaf.dtype.itemsize
        for sub in q.values()
        for name, leaf in sub.items()
        if name in ("codes", "scale")
    )
    assert pim_bytes(q) == want


# ------------------------------------------------------ page-pool guards ----
def test_page_pool_quiescent_after_serve():
    """After every request retires, every page is back on the free list
    exactly once — the no-leak invariant serve_detailed also self-checks."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=3)
    eng.serve([Request(prompt=np.asarray(prompt[0]), max_new=6),
               Request(prompt=np.asarray(prompt[1]), max_new=8)])
    assert eng.pages_in_use() == 0
    eng.assert_quiescent()  # raises on leak or double-free


def test_free_pages_rejects_double_free():
    """A page freed twice would be issued to two slots at once and
    silently cross-corrupt their KV state — _free_pages must refuse."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=3)
    eng._reset([], 0)
    pages = eng._alloc_pages(2)
    eng._free_pages(pages)
    with pytest.raises(ValueError, match="double-free"):
        eng._free_pages(pages)
    with pytest.raises(ValueError, match="double-free"):
        eng._free_pages([0])  # the trash page never circulates


def test_alloc_pages_rejects_overdraw():
    """Allocating past the free list must fail loudly, not hand out a
    short page list that would silently alias the trash page."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, num_pages=5, chunk=3)
    eng._reset([], 0)
    with pytest.raises(RuntimeError, match="overdraw"):
        eng._alloc_pages(5)  # only 4 circulating pages (page 0 = trash)
    eng.assert_quiescent()  # failed alloc must not have taken anything


def test_quiescence_detects_injected_leak():
    """assert_quiescent actually fires: simulate a leaked page."""
    cfg, params, prompt, _ = _setup("qwen2-1.5b")
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=16,
                                   page_size=4, chunk=3)
    eng._reset([], 0)
    eng._alloc_pages(1)  # taken, never freed
    with pytest.raises(AssertionError, match="page leak"):
        eng.assert_quiescent()
