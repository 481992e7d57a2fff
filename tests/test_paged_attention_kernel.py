"""Paged decode attention through ``kernels.paged_attention`` against the
whole-table gather it replaces.

``attn_decode_paged`` runs once with the kernels forced (the Pallas kernel
in interpret mode) and once with them off (the gather of every slot's whole
block-table row), on the same weights, pool, block tables and positions.
The block tables are shuffled page ids, some slots share prefix pages, free
slots sit at ``pos = 0`` on the trash page, and the lengths cover one
position, either side of a page boundary, a length that ends inside a
block, and the whole row.  The new token's K/V is written by the same
``kv_write`` in both, so in float32 the pools after the step match
exactly; the outputs differ only by where the softmax rounds (the kernel
normalises once, after its last block).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_attention
from repro.kernels.paged_attention import pages_per_block
from repro.models.attention import attn_decode_paged
from repro.models.common import set_kernel_dispatch

# (n_heads, n_kv, head_dim, page_size, width in pages)
SHAPES = {
    "gqa12-2_d128": (12, 2, 128, 8, 20),  # qwen2-1.5b's heads and page
    "mha4-4_d64": (4, 4, 64, 16, 12),
    "mqa8-1_d64": (8, 1, 64, 16, 12),
}


@pytest.fixture
def dispatch(monkeypatch):
    """A setter of the kernel dispatch; it returns a list that gains an
    entry each time the kernel is traced under the mode it set."""
    traced = []
    kernel = paged_attention.paged_decode_attention

    def counted(*a, **kw):
        traced.append(1)
        return kernel(*a, **kw)

    monkeypatch.setattr(paged_attention, "paged_decode_attention", counted)
    prev = set_kernel_dispatch("off")

    def use(mode):
        set_kernel_dispatch(mode)
        traced.clear()
        return traced

    yield use
    set_kernel_dispatch(prev)


def _case(n_heads, n_kv, head_dim, ps, width, dtype, seed=0):
    rng = np.random.default_rng(seed)
    d_model = 64
    bk = pages_per_block(ps, width) * ps
    full = width * ps
    assert full > bk, "the widest slot must span more than one block"
    # pos + 1 = positions the step attends to, the new token included
    lengths = [1, ps - 1, ps, ps + 1, bk + 3, full, 1, bk + ps + 2]
    free = [0, 6]  # parked on the trash page
    b = len(lengths)
    pages_needed = [-(-n // ps) for n in lengths]
    num_pages = 1 + sum(pages_needed)
    ids = rng.permutation(np.arange(1, num_pages))
    bt = np.zeros((b, width), np.int32)
    at = 0
    for s, n in enumerate(pages_needed):
        if s in free:
            continue
        bt[s, :n] = ids[at:at + n]
        at += n
    # the last slot shares the prefix pages of slot 4 (copy-on-write
    # sharing), writing only into its own last page
    shared = pages_needed[4] - 1
    bt[7, :shared] = bt[4, :shared]
    pos = np.asarray(lengths, np.int32) - 1
    pos[free] = 0

    def w(shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.2, dtype)

    p = {"wq": w((d_model, n_heads * head_dim)),
         "wk": w((d_model, n_kv * head_dim)),
         "wv": w((d_model, n_kv * head_dim)),
         "wo": w((n_heads * head_dim, d_model))}
    cache = {"k": w((num_pages, n_kv, ps, head_dim)) * 5,
             "v": w((num_pages, n_kv, ps, head_dim)) * 5}
    x = w((b, 1, d_model)) * 5
    return p, x, cache, jnp.asarray(bt), jnp.asarray(pos)


CASES = [(name, "f32") for name in SHAPES] + [("gqa12-2_d128", "bf16")]


@pytest.mark.parametrize("shape,dtype", CASES,
                         ids=[f"{s}-{d}" for s, d in CASES])
def test_kernel_matches_whole_table_gather(shape, dtype, dispatch):
    """In bfloat16, the chip's precision, the gather runs on the same values
    in float32: XLA's CPU backend has no bfloat16 dot with a float32 result
    for the gather's grouped einsum."""
    n_heads, n_kv, head_dim, ps, width = SHAPES[shape]
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    p, x, cache, bt, pos = _case(n_heads, n_kv, head_dim, ps, width, dt)
    kw = dict(n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
              rope_theta=1e4, page_size=ps)
    step = jax.jit(lambda *a: attn_decode_paged(*a, **kw))
    traced = dispatch("force")
    y_kernel, c_kernel = step(p, x, cache, bt, pos)
    assert traced
    traced = dispatch("off")
    p, x, cache = jax.tree.map(lambda a: a.astype(jnp.float32),
                               (p, x, cache))
    y_gather, c_gather = step(p, x, cache, bt, pos)
    assert not traced
    if dtype == "f32":
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(c_kernel[key]),
                                          np.asarray(c_gather[key]))
    yk = np.asarray(y_kernel, np.float32)
    yg = np.asarray(y_gather, np.float32)
    assert np.all(np.isfinite(yk))
    tol = 2e-5 if dtype == "f32" else 2e-2
    np.testing.assert_allclose(yk, yg, rtol=tol, atol=tol * np.abs(yg).max())


def test_sharded_decode_chunk_reads_pages_through_the_kernel(dispatch):
    """``_decode_chunk_sharded`` on the one-device mesh: the kernel runs on
    the shard's local shapes and the chunk emits the tokens the gather path
    emits, with the same pools to rounding.  Dense float weights keep every
    linear on the same path in both modes, so only attention differs."""
    from repro.configs import get_reduced
    from repro.models import init_paged_cache, init_params
    from repro.serving import engine as eng
    from repro.serving import make_decode_mesh

    cfg = get_reduced("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    slots, ps, max_seq = 4, 4, 32
    width = max_seq // ps
    num_pages = slots * width + 1
    rng = np.random.default_rng(1)
    cache = init_paged_cache(cfg, slots, max_seq, num_pages, ps)
    for key in ("k", "v"):
        cache["layers"][key] = jnp.asarray(
            rng.standard_normal(cache["layers"][key].shape), jnp.float32)
    bt = np.zeros((slots, width), np.int32)
    bt[:3] = rng.permutation(np.arange(1, num_pages))[:3 * width].reshape(
        3, width)
    cache["block_tables"] = jnp.asarray(bt)  # slot 3 is free, on page 0
    pos = jnp.asarray([0, 5, 17, 0], jnp.int32)
    done = jnp.asarray([False, False, False, True])
    args = (jnp.asarray(rng.integers(0, cfg.vocab, (slots, 1)), jnp.int32),
            pos, jnp.zeros(slots, jnp.int32), done,
            jnp.arange(slots, dtype=jnp.int32),
            jnp.full(slots, 8, jnp.int32), jnp.zeros((slots, 0), jnp.int32),
            jax.random.PRNGKey(2), jnp.float32(1.0), None)
    out = {}
    for mode in ("force", "off"):
        traced = dispatch(mode)
        c = jax.tree.map(jnp.copy, cache)  # the chunk donates its cache
        out[mode] = eng._decode_chunk_sharded(
            params, cfg, c, *args, mesh=make_decode_mesh(1), chunk=3,
            page_size=ps, greedy=True, top_k=0, pad_id=0)
        assert bool(traced) == (mode == "force")
    (ck, *rk), (cg, *rg) = out["force"], out["off"]
    np.testing.assert_array_equal(np.asarray(rk[4]), np.asarray(rg[4]))
    np.testing.assert_array_equal(np.asarray(rk[1]), np.asarray(rg[1]))
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(ck["layers"][key]),
                                   np.asarray(cg["layers"][key]),
                                   rtol=1e-5, atol=1e-5)
