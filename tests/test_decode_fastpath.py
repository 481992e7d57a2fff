"""Decode fast path: scan-compiled generation parity vs the seed per-token
loop, single-pass prefill vs forward/per-token caches, and the
epilogue-fused pim_matvec kernel vs its pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels import ref
from repro.kernels.pim_matmul import pim_matmul
from repro.kernels.pim_matvec import pim_matvec
from repro.models import decode_step, forward, init_cache, init_params, prefill
from repro.models.common import dq, linear, set_kernel_dispatch, weight_shape
from repro.quant import pack_int4, quantize_symmetric
from repro.serving import ServingEngine, quantize_tree


def _token_loop_cache(params, cfg, tokens, cache):
    """Per-token prefill oracle: feed the prompt one token at a time through
    decode_step (the seed-era reference path, now inlined here — the engine
    keeps a single oracle, ``ServingEngine.generate_reference``)."""
    for i in range(tokens.shape[1]):
        _, cache = decode_step(params, cfg, tokens[:, i : i + 1], cache,
                               jnp.int32(i))
    return cache


def _mk(m, k, n, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.normal(kx, (m, k)), jax.random.normal(kw, (k, n))


# --------------------------------------------------- scan-compiled generate -
@pytest.mark.parametrize("pim_bits", [0, 8, 4])
def test_generate_matches_seed_loop(pim_bits):
    """Greedy, batch > 1: the one-XLA-program generate must emit exactly the
    seed per-token loop's tokens (same argmax path, same cache layout)."""
    cfg = get_reduced("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_seq=32, pim_bits=pim_bits)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, cfg.vocab)
    fast = eng.generate(prompt, n_new=6)
    seed = eng.generate_reference(prompt, n_new=6)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(seed))


def test_generate_matches_seed_loop_ssm():
    """SSM family: chunked single-pass prefill state == per-token state."""
    cfg = get_reduced("falcon-mamba-7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_seq=32, pim_bits=8)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    np.testing.assert_array_equal(
        np.asarray(eng.generate(prompt, n_new=5)),
        np.asarray(eng.generate_reference(prompt, n_new=5)),
    )


def test_generate_prime_prompt_ssm():
    """Prime prompt length exercises the SSM prefill's masked pad-to-chunk
    path (chunk no longer degrades to 1 for indivisible lengths)."""
    cfg = get_reduced("falcon-mamba-7b")  # reduced chunk=16
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_seq=32, pim_bits=0)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
    np.testing.assert_array_equal(
        np.asarray(eng.generate(prompt, n_new=4)),
        np.asarray(eng.generate_reference(prompt, n_new=4)),
    )


def test_generate_sampling_modes():
    cfg = get_reduced("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_seq=32, pim_bits=8)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, cfg.vocab)
    a = eng.generate(prompt, n_new=6, greedy=False, temperature=0.7, top_k=8,
                     key=jax.random.PRNGKey(5))
    b = eng.generate(prompt, n_new=6, greedy=False, temperature=0.7, top_k=8,
                     key=jax.random.PRNGKey(5))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # same key
    assert a.shape == (2, 6) and int(a.max()) < cfg.vocab
    c = eng.generate(prompt, n_new=6, greedy=False, temperature=1.3,
                     key=jax.random.PRNGKey(6))
    assert c.shape == (2, 6)


# --------------------------------------------------------- single-pass prefill
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "deepseek-v2-lite-16b",
                                  "falcon-mamba-7b", "zamba2-1.2b"])
def test_prefill_matches_forward_and_token_loop(arch):
    """prefill() logits == forward() logits exactly, and the filled cache
    decodes the same next token as the per-token reference prefill."""
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    logits, cache = prefill(params, cfg, tokens, init_cache(cfg, b, s + 4))
    fwd, _ = forward(params, cfg, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(logits), np.asarray(fwd),
                               rtol=1e-5, atol=1e-5)
    ref_cache = _token_loop_cache(params, cfg, tokens, init_cache(cfg, b, s + 4))
    nt = jnp.zeros((b, 1), jnp.int32)
    l1, _ = decode_step(params, cfg, nt, cache, jnp.int32(s))
    l2, _ = decode_step(params, cfg, nt, ref_cache, jnp.int32(s))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-3, atol=1e-2)


def test_prefill_int8_kv_cache():
    """Quantized KV cache: prefill writes the same int8 codes the per-token
    path would (prompt attends against quantize->dequantize K/V)."""
    cfg = get_reduced("qwen2-1.5b").replace(kv_cache_bits=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    _, cache = prefill(params, cfg, tokens, init_cache(cfg, b, s + 4))
    ref_cache = _token_loop_cache(params, cfg, tokens, init_cache(cfg, b, s + 4))
    got = np.asarray(cache["layers"]["k"], np.int32)
    want = np.asarray(ref_cache["layers"]["k"], np.int32)
    # int8 codes of identical values; allow off-by-one rounding at the edge
    assert np.abs(got - want).max() <= 1


# --------------------------------------------------------------- pim_matvec -
@pytest.mark.parametrize(
    "m,k,n,bn,bk",
    [
        (1, 64, 32, 16, 16),
        (4, 128, 64, 64, 64),
        (8, 256, 128, 128, 512),
        (2, 96, 100, 32, 64),  # N not a multiple of bn -> pad-to-tile
        (3, 50, 30, 16, 16),   # M, K, N all non-multiples
    ],
)
def test_pim_matvec_int8_matches_ref(m, k, n, bn, bk):
    x, w = _mk(m, k, n, seed=m + k + n)
    q = quantize_symmetric(w, bits=8, axis=0)
    got = pim_matvec(x, q.codes, q.scale, bits=8, bn=bn, bk=bk, interpret=True)
    want = ref.pim_matvec_ref(x, q.codes, q.scale, bits=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize(
    "m,k,n,bk", [(1, 64, 16, 32), (4, 128, 32, 64), (2, 100, 48, 64)]
)
def test_pim_matvec_int4_matches_ref(m, k, n, bk):
    x, w = _mk(m, k, n, seed=7)
    q = quantize_symmetric(w, bits=4, axis=0)
    packed = pack_int4(q.codes)
    got = pim_matvec(x, packed, q.scale, bits=4, bn=16, bk=bk, interpret=True)
    want = ref.pim_matvec_ref(x, packed, q.scale, bits=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("activation", ["none", "relu", "silu", "gelu"])
def test_pim_matvec_fused_epilogue(activation):
    """scale x bias + activation + residual fused in the flush step."""
    m, k, n = 4, 64, 48
    x, w = _mk(m, k, n, seed=3)
    q = quantize_symmetric(w, bits=8, axis=0)
    bias = jax.random.normal(jax.random.PRNGKey(9), (n,))
    res = jax.random.normal(jax.random.PRNGKey(10), (m, n))
    got = pim_matvec(x, q.codes, q.scale, bits=8, bias=bias,
                     activation=activation, residual=res, bn=16, bk=32,
                     interpret=True)
    want = ref.pim_matvec_ref(x, q.codes, q.scale, bits=8, bias=bias,
                              activation=activation, residual=res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_pim_matvec_rejects_large_m():
    x, w = _mk(16, 32, 16)
    q = quantize_symmetric(w, bits=8, axis=0)
    with pytest.raises(ValueError, match="decode-shaped"):
        pim_matvec(x, q.codes, q.scale, bits=8, interpret=True)


def test_pim_matmul_pad_to_tile_and_epilogue():
    """Shapes that are not block multiples no longer assert; epilogue fused."""
    m, k, n = 12, 100, 70
    x, w = _mk(m, k, n, seed=5)
    q = quantize_symmetric(w, bits=8, axis=0)
    bias = jax.random.normal(jax.random.PRNGKey(9), (n,))
    got = pim_matmul(x, q.codes, q.scale, bits=8, bm=8, bn=32, bk=64,
                     bias=bias, activation="relu", interpret=True)
    want = ref.pim_matvec_ref(x, q.codes, q.scale, bits=8, bias=bias,
                              activation="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# --------------------------------------------------- linear kernel dispatch -
@pytest.mark.parametrize("rows", [2, 12])
@pytest.mark.parametrize("bits,kdim", [(8, 64), (4, 64), (4, 33)])
def test_linear_dispatches_to_matvec(bits, kdim, rows):
    """force mode: a quantized linear routes through pim_matvec (decode-
    shaped, 2 rows) or pim_matmul (12 rows), in interpret mode, and agrees
    with the XLA overlay path — including the odd-K int4 'nibbles_odd'
    packing."""
    w = {"w": jax.random.normal(jax.random.PRNGKey(2), (kdim, 24))}
    q = quantize_tree(w, bits=bits)["w"]
    assert isinstance(q, dict)
    x = jax.random.normal(jax.random.PRNGKey(4), (rows, 1, kdim))
    b = jax.random.normal(jax.random.PRNGKey(6), (24,))
    prev = set_kernel_dispatch("force")
    try:
        y_kernel = linear(x, q, b)
        set_kernel_dispatch("off")
        y_overlay = linear(x, q, b)
    finally:
        set_kernel_dispatch(prev)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_overlay),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m", [4, 40])
def test_kernel_column_shards_bit_identical(m, bits):
    """A column shard at least one tile wide gives exactly the matching
    columns of the full product: the kernels' tiles, and so each column's
    contraction order, do not depend on N.  Sharded decode and prefill rely
    on it."""
    x, w = _mk(m, 1024, 1024, seed=11)
    q = quantize_symmetric(w, bits=bits, axis=0)
    codes = q.codes if bits == 8 else pack_int4(q.codes)
    kernel = pim_matvec if m <= 8 else pim_matmul
    full = np.asarray(kernel(x, codes, q.scale, bits=bits, interpret=True))
    for j in range(4):
        cols = slice(j * 256, (j + 1) * 256)
        part = kernel(x, codes[:, cols], q.scale[:, cols], bits=bits,
                      interpret=True)
        np.testing.assert_array_equal(np.asarray(part), full[:, cols])


# ----------------------------------------------------- int4 odd-K quantize --
def test_quantize_tree_int4_odd_k_packs():
    """Odd K no longer silently ships INT8: one zero code row is padded and
    flagged via the 'nibbles_odd' marker; dq() drops it on unpack."""
    w = {"w": jax.random.normal(jax.random.PRNGKey(0), (33, 16))}
    q = quantize_tree(w, bits=4)["w"]
    assert "nibbles_odd" in q and "nibbles" not in q
    assert q["codes"].shape == (17, 16)  # (33+1)/2 packed rows
    assert weight_shape(q) == (33, 16)
    dense = dq(q)
    assert dense.shape == (33, 16)
    # quantization error bounded by half a step, as for even K
    err = jnp.abs(dense - w["w"])
    assert float(jnp.max(err / (q["scale"] / 2 + 1e-9))) <= 1.001


def test_quantize_tree_int4_even_k_unchanged():
    w = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 16))}
    q = quantize_tree(w, bits=4)["w"]
    assert "nibbles" in q and "nibbles_odd" not in q
    assert q["codes"].shape == (16, 16)
    assert weight_shape(q) == (32, 16)


def test_pack_int4_rejects_odd_k():
    from repro.quant import pack_int4 as pk
    with pytest.raises(ValueError, match="even K"):
        pk(jnp.zeros((33, 8), jnp.int8))
