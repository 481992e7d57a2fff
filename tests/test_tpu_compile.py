"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler that ships with jaxlib compiles for a
topology that is only described, and refuses what the chip would refuse
(layouts Mosaic cannot lower, too much VMEM).  Interpret mode cannot see
either, so these compiles guard the kernels at the published widths of the
benchmark models.  Each compile takes well under two seconds.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so under pytest-xdist
every worker must collect the same tests and only the worker that runs this
file loads it.  All such tests stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.pim_matmul import pim_matmul
from repro.kernels.pim_matvec import MAX_M, pim_matvec

# (K, N) of every quantized decode linear of qwen2-1.5b: q/o, k/v, gate/up,
# down, and the 151936-wide head pim_matvec is compiled for.
QWEN2_WIDTHS = [(1536, 1536), (1536, 256), (1536, 8960), (8960, 1536),
                (1536, 151936)]
# One device's shard on a 4-way "model" mesh (output dim split 4 ways).
QWEN2_SHARD_WIDTHS = [(1536, 384), (8960, 384), (1536, 64)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables can be written to the persistent cache
    # but never read back; keep these compiles out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_matvec(one_chip, k, n, bits, *, bias=False):
    kw = k if bits == 8 else k // 2
    extra = {"bias": _sds((n,), jnp.float32, one_chip)} if bias else {}
    lowered = pim_matvec.lower(
        _sds((MAX_M, k), jnp.bfloat16, one_chip),
        _sds((kw, n), jnp.int8, one_chip),
        _sds((1, n), jnp.float32, one_chip), bits=bits, **extra)
    return lowered.compile().as_text()


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("k,n", QWEN2_WIDTHS)
def test_pim_matvec_compiles_for_v5e_at_qwen2_widths(one_chip, k, n, bits):
    assert "tpu_custom_call" in _compile_matvec(one_chip, k, n, bits)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("k,n", QWEN2_SHARD_WIDTHS)
def test_pim_matvec_compiles_for_v5e_at_shard_widths(one_chip, k, n, bits):
    assert "tpu_custom_call" in _compile_matvec(one_chip, k, n, bits)


@pytest.mark.parametrize("bits", [8, 4])
def test_pim_matvec_compiles_for_v5e_with_fused_bias(one_chip, bits):
    """qwen2's q/k/v projections carry a bias fused into the epilogue."""
    assert "tpu_custom_call" in _compile_matvec(one_chip, 1536, 256, bits,
                                                bias=True)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m,k,n", [(256, 1536, 1536), (128, 4608, 128),
                                   (40, 1536, 256)])
def test_pim_matmul_compiles_for_v5e(one_chip, m, k, n, bits):
    """The prefill-shaped kernel (every quantized linear of a prompt on the
    chip) shares the int4 unpack with pim_matvec; (128, 4608, 128) is
    starcoder2-7b's k projection for a 128-token prompt on one device of a
    4-way mesh, and 40 rows (8 slots x 5 positions) a verify window, fewer
    rows than a bfloat16 tile holds."""
    kw = k if bits == 8 else k // 2
    text = pim_matmul.lower(
        _sds((m, k), jnp.bfloat16, one_chip),
        _sds((kw, n), jnp.int8, one_chip),
        _sds((1, n), jnp.float32, one_chip), bits=bits).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots,n_heads,n_kv,page,width", [
    (8, 12, 2, 8, 640),  # qwen2-1.5b chat: 8 slots, max_seq 5120, page 8
    (8, 48, 4, 8, 1024),  # starcoder2-15b's heads at an 8192-token row
])
def test_paged_decode_attention_compiles_for_v5e(one_chip, slots, n_heads,
                                                 n_kv, page, width):
    """The decode step's attention reads the pool's bfloat16 pages in place
    at the served shapes: the block table and lengths in SMEM, a page of
    every KV head per DMA."""
    pages = slots * width + 1
    pool = _sds((pages, n_kv, page, 128), jnp.bfloat16, one_chip)
    text = paged_decode_attention.lower(
        _sds((slots, n_heads, 128), jnp.bfloat16, one_chip), pool, pool,
        _sds((slots, width), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
