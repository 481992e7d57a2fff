"""Shared functional building blocks: init, norms, RoPE, PIM-aware linear."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def dense_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ------------------------------------------------------------ PIM linear ----
# Quantized 2-D linears can route through the epilogue-fused PIM kernels
# instead of the XLA overlay path: kernels.pim_matvec for decode-shaped
# calls (M <= MATVEC_MAX_M activation rows), kernels.pim_matmul for wider
# ones (prefill, verify windows):
#   "auto"  — dispatch only on real TPU (compiled Mosaic; CPU interpret mode
#             is orders of magnitude slower than XLA, so never auto on CPU)
#   "force" — dispatch everywhere (interpret mode off-TPU; used by tests)
#   "off"   — always use the XLA overlay path
MATVEC_MAX_M = 8
_KERNEL_DISPATCH = "auto"

# Named mesh axis the decode-sharding subsystem (serving.sharded) partitions
# quantized weights over.  A quantized leaf carrying the "tp" marker holds
# only this device's shard of codes/scale along the OUTPUT (last) dim;
# ``linear``/``dq`` must then run inside shard_map over a mesh with this axis.
TP_AXIS = "model"


def set_kernel_dispatch(mode: str) -> str:
    """Set the PIM kernel dispatch mode; returns the previous mode.

    The mode is read at trace time, so cached jitted programs would keep
    their baked-in path — clear the jit caches on a mode change so the next
    call re-traces under the new mode."""
    global _KERNEL_DISPATCH
    if mode not in ("auto", "off", "force"):
        raise ValueError(f"kernel dispatch must be auto|off|force, got {mode!r}")
    prev, _KERNEL_DISPATCH = _KERNEL_DISPATCH, mode
    if prev != mode:
        jax.clear_caches()
    return prev


def _kernels_enabled() -> bool:
    if _KERNEL_DISPATCH == "off":
        return False
    if _KERNEL_DISPATCH == "force":
        return True
    return jax.default_backend() == "tpu"


def _linear_kernel(x: jnp.ndarray, w: dict, b) -> jnp.ndarray:
    """Route a quantized linear through kernels.pim_matvec (decode-shaped)
    or kernels.pim_matmul, bias fused into the kernel epilogue — no HBM
    round-trip."""
    from repro.kernels.ops import _interpret
    from repro.kernels.pim_matmul import pim_matmul
    from repro.kernels.pim_matvec import pim_matvec

    bits = 4 if ("nibbles" in w or "nibbles_odd" in w) else 8
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if "nibbles_odd" in w:
        # The packed weight carries one zero pad row (odd true K); a zero
        # activation column keeps the contraction aligned and contributes 0.
        x2 = jnp.pad(x2, ((0, 0), (0, 1)))
    n = w["codes"].shape[-1]
    kernel = pim_matvec if x2.shape[0] <= MATVEC_MAX_M else pim_matmul
    y = kernel(x2, w["codes"], w["scale"].reshape(1, n), bits=bits, bias=b,
               interpret=_interpret())
    return y.reshape(lead + (n,)).astype(x.dtype)


def linear(x: jnp.ndarray, w, b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Matmul against a dense weight or a PIM-quantized leaf.

    A PIM leaf is ``{"codes": int8 (..., K, N), "scale": f32}`` produced by
    ``serving.quantize_tree``.  A 2-D leaf routes through the epilogue-fused
    PIM kernels (the 'overhaul' path: pim_matvec for <= MATVEC_MAX_M
    activation rows, pim_matmul otherwise) when the dispatch mode allows it
    — see ``set_kernel_dispatch``.  Otherwise the dequant happens at the
    matmul operand (XLA fuses it into the producing fusion — the 'overlay'
    path).

    A leaf carrying the ``"tp"`` marker (serving.sharded) holds only this
    device's columns: the contraction runs weight-stationary on the local
    shard (full K, N/devices outputs), adds this device's slice of the
    (replicated) bias, then ONE all-gather of the tiny activation tile
    along ``TP_AXIS`` reassembles the full output.  Gathering columns is a
    pure concatenation, and a K-sharded psum would reorder the float
    contraction, so sharded decode stays bit-identical to single-device
    decode as long as every output column is computed exactly as on one
    device.  The kernels guarantee that for shards at least one tile wide:
    their tiles, and so each column's contraction order, do not depend on
    N.  An XLA dot does not: the
    compiler picks its contraction windows per output width and fusion, so
    a column shard may sum in another order — which is why the TPU path
    sends prefill through pim_matmul too.
    """
    with jax.named_scope("linear"):
        if not (isinstance(w, dict) and "codes" in w):
            y = x @ w
            return y if b is None else y + b.astype(y.dtype)
        tp = "tp" in w
        if tp and b is not None:
            n_loc = w["codes"].shape[-1]
            b = jax.lax.dynamic_slice_in_dim(
                b, jax.lax.axis_index(TP_AXIS) * n_loc, n_loc)
        if w["codes"].ndim == 2 and _kernels_enabled():
            y = _linear_kernel(x, w, b)  # bias fused in the kernel epilogue
        else:
            y = x @ _dq_local(w, x.dtype)
            if b is not None:
                y = y + b.astype(y.dtype)
        # Pin the output at the activation dtype, as a tp leaf's all-gather
        # does by itself.  Otherwise the compiler may hand consumers the
        # float32 values from before the rounding (excess precision) on one
        # device but not across a gather, and sharded greedy tokens would
        # drift.
        y = jax.lax.optimization_barrier(y)
        if tp:
            y = jax.lax.all_gather(y, TP_AXIS, axis=y.ndim - 1, tiled=True)
        return y


def weight_kn(w) -> tuple[int, int]:
    """(K, N) of a dense or PIM-quantized weight leaf."""
    s = weight_shape(w)
    return s[-2], s[-1]


def weight_shape(w) -> tuple:
    if isinstance(w, dict) and "codes" in w:
        s = w["codes"].shape
        if "nibbles" in w:  # int4: two K rows per byte
            return s[:-2] + (2 * s[-2], s[-1])
        if "nibbles_odd" in w:  # int4, odd true K: last byte's high nibble is pad
            return s[:-2] + (2 * s[-2] - 1, s[-1])
        return s
    return w.shape


def dq(w, dtype=None) -> jnp.ndarray:
    """Densify a weight leaf (dequantize PIM codes) for matmul/einsum use.

    Handles nibble-packed int4 ('nibbles' marker): two K rows per byte,
    unpacked with sign extension at the compute boundary.  The
    'nibbles_odd' marker flags an odd true K — the zero pad row added by
    ``serving.quantize_tree`` before packing is dropped after unpack (a
    static slice, so this stays scan/jit-safe).

    A ``"tp"``-marked leaf (serving.sharded) dequantizes its local column
    shard and all-gathers the FULL dense weight along ``TP_AXIS`` — the
    exactness escape hatch for consumers that contract a quantized leaf in
    an einsum instead of ``linear`` (MoE expert stacks, MLA absorbed
    W_uk/W_uv): per-device HBM still streams only the 1/devices shard, and
    the gathered weight is a bit-exact concatenation, so the downstream
    einsum is identical to the single-device one.
    """
    out = _dq_local(w, dtype)
    if isinstance(w, dict) and "tp" in w:
        out = jax.lax.all_gather(out, TP_AXIS, axis=out.ndim - 1, tiled=True)
    return out


def _dq_local(w, dtype=None) -> jnp.ndarray:
    """``dq`` without the tensor-parallel gather: a tp-marked leaf yields its
    local column shard (what ``linear`` contracts before its activation
    all-gather)."""
    if isinstance(w, dict) and "codes" in w:
        codes = w["codes"]
        if "nibbles" in w or "nibbles_odd" in w:
            lo = ((codes & 0xF) ^ 8) - 8
            hi = (((codes >> 4) & 0xF) ^ 8) - 8
            k2 = codes.shape[-2]
            stacked = jnp.stack([lo, hi], axis=-2)  # (..., K//2, 2, N)
            codes = stacked.reshape(codes.shape[:-2] + (2 * k2, codes.shape[-1]))
            if "nibbles_odd" in w:
                codes = codes[..., :-1, :]
        out = codes.astype(w["scale"].dtype) * w["scale"]
        return out.astype(dtype) if dtype is not None else out
    return w.astype(dtype) if dtype is not None else w


# ------------------------------------------------------------------ norms ---
def rmsnorm_init(d: int, dtype) -> jnp.ndarray:
    return jnp.ones((d,), dtype)


def rmsnorm(x: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * g.astype(x.dtype)


# ------------------------------------------------------------------- RoPE ---
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B?, S, D/2)
    while ang.ndim < x.ndim:
        ang = ang[..., None, :] if ang.ndim == x.ndim - 1 else ang[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- MLP ----
def mlp_init(key, d: int, d_ff: int, dtype) -> dict:
    kg, ku, kd = split_keys(key, 3)
    return {
        "gate": dense_init(kg, (d, d_ff), dtype),
        "up": dense_init(ku, (d, d_ff), dtype),
        "down": dense_init(kd, (d_ff, d), dtype),
    }


def mlp_apply(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU MLP."""
    return linear(jax.nn.silu(linear(x, p["gate"])) * linear(x, p["up"]), p["down"])


# ------------------------------------------------------------- embeddings ---
def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return dense_init(key, (vocab, d), dtype, scale=0.02)


def embed_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, ids, axis=0)


def unembed(x: jnp.ndarray, table_or_w) -> jnp.ndarray:
    """Logits. ``table_or_w``: (V, D) tied table or (D, V) head weight."""
    with jax.named_scope("lm_head"):
        if isinstance(table_or_w, dict) and "codes" in table_or_w:
            return linear(x, table_or_w)
        if table_or_w.shape[0] > table_or_w.shape[1]:  # (V, D) tied
            return x @ table_or_w.T
        return x @ table_or_w


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean token cross-entropy in f32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
