"""Plain reference of the decoder that ``qwen2-1.5b.json`` and
``starcoder2-15b.json`` describe, as the repo's model computes it.

Pre-norm decoder: RMSNorm, grouped-query attention with RoPE on interleaved
pairs and an optional bias on q, k and v, a gated SiLU MLP, a final RMSNorm
and an output head (the embedding itself where tied).  Weights are the
benchmark's (``bench/weights.py``): int8 codes times a float32 scale per
output column, dequantized here to float32.  Everything is float32 with
matrix products at ``HIGHEST`` precision; no kernel, cache, page or batch:
one sequence, one full causal pass, layer by layer.  It imports nothing of
the program.

``act`` rounds the input of every matrix product with a weight (the place
where the configuration holds bfloat16 activations) to a lower precision:
the control of the comparison (``bench/check.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block
SEQ_BUCKET = 1024  # sequences are padded to a multiple (causal: no effect)
ROW_BUCKET = 256  # logits rows are read in multiples of this


def hyper(conf: dict) -> dict:
    """The reference's sizes from the configuration file's keys."""
    act = conf["hidden_act"]
    if act != "silu":
        raise ValueError(f"the reference runs a gated SiLU MLP, not {act!r}")
    if conf.get("norm_type", "rms_norm") != "rms_norm":
        raise ValueError("the reference runs RMSNorm")
    if conf.get("sliding_window") and conf.get("use_sliding_window", True):
        raise ValueError("the reference attends to the whole context")
    return {
        "layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "d_ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "qkv_bias": bool(conf.get("attention_bias", conf.get("use_bias", False))),
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf.get("rms_norm_eps", conf.get("norm_epsilon"))),
        "tied": bool(conf["tie_word_embeddings"]),
    }


def _dq(leaf):
    return leaf["codes"].astype(jnp.float32) * leaf["scale"].astype(jnp.float32)


def _mm(x, w, act):
    if act is not None:
        x = act(x)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    """x (S, H, D): rotate pair (2i, 2i+1) by position * theta^(-2i/D)."""
    s, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(q, k, v):
    """Causal attention, q (S, H, D), k/v (S, KV, D), in query blocks."""
    s, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, h, d)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) / np.sqrt(d)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        sc = jnp.where(rows >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb))
    return out.reshape(s, h * d)


@functools.partial(jax.jit, static_argnames=("hp", "act"))
def _layer(h, layers, i, *, hp, act):
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                      layers)
    at, ml = lw["attn"], lw["mlp"]
    s = h.shape[0]
    nh, nkv, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
    x = _norm(h, lw["ln1"], hp["norm_eps"])
    q, k, v = (_mm(x, _dq(at[n]), act) for n in ("wq", "wk", "wv"))
    if hp["qkv_bias"]:
        q, k, v = (t + at[b].astype(jnp.float32)
                   for t, b in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(s, nh, hd), hp["rope_theta"])
    k = _rope(k.reshape(s, nkv, hd), hp["rope_theta"])
    o = _attention(q, k, v.reshape(s, nkv, hd))
    h = h + _mm(o, _dq(at["wo"]), act)
    x = _norm(h, lw["ln2"], hp["norm_eps"])
    gate = jax.nn.silu(_mm(x, _dq(ml["gate"]), act))
    return h + _mm(gate * _mm(x, _dq(ml["up"]), act), _dq(ml["down"]), act)


@functools.partial(jax.jit, static_argnames=("hp", "act", "n"))
def _logits(h, weights, start, *, hp, act, n):
    h = jnp.concatenate([h, jnp.zeros((n,) + h.shape[1:], h.dtype)])
    x = _norm(jax.lax.dynamic_slice_in_dim(h, start, n), weights["ln_f"],
              hp["norm_eps"])
    if hp["tied"]:
        return _mm(x, weights["embed"].astype(jnp.float32).T, act)
    return _mm(x, _dq(weights["head"]), act)


def _placed(x, weights):
    """``x`` on the devices that hold the weights, replicated."""
    sh = weights["embed"].sharding
    if isinstance(sh, jax.sharding.NamedSharding):
        sh = jax.sharding.NamedSharding(sh.mesh, jax.sharding.PartitionSpec())
    return jax.device_put(x, sh)


def logits(weights, hp: dict, tokens, start: int, act=None):
    """Float32 logits at positions ``start .. len(tokens) - 1``, as the first
    rows of an array whose row count is a multiple of ``ROW_BUCKET`` (the
    rows past the sequence are of padding)."""
    tokens = np.asarray(tokens, np.int32)
    s = len(tokens)
    pad = -(-s // SEQ_BUCKET) * SEQ_BUCKET
    ids = _placed(np.pad(tokens, (0, pad - s)), weights)
    h = weights["embed"][ids].astype(jnp.float32)
    hpk = tuple(sorted(hp.items()))
    for i in range(hp["layers"]):
        h = _layer(h, weights["layers"], i, hp=_Hp(hpk), act=act)
    n = -(-(s - start) // ROW_BUCKET) * ROW_BUCKET
    return _logits(h, weights, start, hp=_Hp(hpk), act=act, n=n)


class _Hp(dict):
    """Hashable hyperparameters, for ``jit``'s static arguments."""

    def __init__(self, items):
        super().__init__(items)
        self._items = items

    def __hash__(self):
        return hash(self._items)

    def __eq__(self, other):
        return isinstance(other, _Hp) and self._items == other._items
