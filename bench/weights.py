"""Random served weights, made by the benchmark from the seed.

The benchmark makes the weights itself, so the reference (``bench/configs``)
and the program read the same numbers and the reference takes nothing the
program made.  The tree has the layout the serving engine takes (int8 codes
and a float32 scale per output column for every quantized linear, bfloat16
embedding, norms and biases); only its shape is read from the program, by
``jax.eval_shape`` of its parameter initializer.  Each leaf is drawn by the
role its name has (``LEAF_ROLES``, and a reference module's own table), from
a key of its path.  Every leaf is drawn on the device, one layer at a time,
in one jitted call, straight onto the placement the engine serves it from.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

CODE_STD = 32.0      # codes ~ N(0, 32^2), clipped to int8's symmetric range
WEIGHT_STD = 0.02    # scale * CODE_STD: the effective weight's spread
BIAS_STD = 0.05
NORM_STD = 0.1       # norm gains 1 + N(0, 0.1^2)


def base_key(seed: int):
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    seed = int(seed) & ((1 << 64) - 1)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _codes(k, s, dtype):
    z = jax.random.normal(k, s, jnp.float32) * CODE_STD
    return jnp.clip(jnp.round(z), -127, 127).astype(jnp.int8)


def _scale(k, s, dtype):
    u = jax.random.uniform(k, s, jnp.float32, 0.75, 1.25)
    return u * (WEIGHT_STD / CODE_STD)


def _embed(k, s, dtype):
    return (WEIGHT_STD * jax.random.normal(k, s, jnp.float32)).astype(dtype)


def _gain(k, s, dtype):
    g = 1.0 + NORM_STD * jax.random.normal(k, s, jnp.float32)
    return g.astype(dtype)


def _bias(k, s, dtype):
    return (BIAS_STD * jax.random.normal(k, s, jnp.float32)).astype(dtype)


# How each role is drawn: int8 codes, a per-column scale, the embedding, a
# norm's gain, and a norm's shift or a linear's bias.
ROLES = {"codes": _codes, "scale": _scale, "embed": _embed, "gain": _gain,
         "bias": _bias}
# The role of each leaf name of a dense decoder; besides these, a name that
# starts with ``ln`` is a norm's gain.  A reference module adds or overrides
# names with a ``LEAF_ROLES`` table of its own.
LEAF_ROLES = {"codes": "codes", "scale": "scale", "embed": "embed",
              "bq": "bias", "bk": "bias", "bv": "bias"}


def _role(path: str, name: str, roles: dict) -> str:
    role = roles.get(name) or LEAF_ROLES.get(name)
    if role is None and name.startswith("ln"):
        role = "gain"
    if role is None:
        raise ValueError(f"no rule to draw weight leaf {path!r}: give "
                         f"{name!r} a role in the reference's LEAF_ROLES")
    if role not in ROLES:
        raise ValueError(f"weight leaf {path!r} has role {role!r}, not one "
                         f"of {sorted(ROLES)}")
    return role


def _draw(key, path: str, name: str, shape, dtype, roles: dict):
    """One leaf; ``shape`` may carry a leading layer dim, drawn per layer."""
    draw = ROLES[_role(path, name, roles)]
    key = _leaf_key(key, path)
    if path.startswith("layers/"):
        n = shape[0]
        return jax.lax.map(
            lambda i: draw(jax.random.fold_in(key, i), shape[1:], dtype),
            jnp.arange(n))
    return draw(key, shape, dtype)


def _fill(abstract, key, roles: dict):
    def conv(path, leaf):
        names = [str(getattr(p, "key", p)) for p in path]
        return _draw(key, "/".join(names), names[-1], leaf.shape, leaf.dtype,
                     roles)

    return jax.tree_util.tree_map_with_path(conv, abstract)


def make(cfg, seed: int, *, mesh=None, roles=None):
    """The served parameter tree of ``cfg`` (a ``repro`` ModelConfig with
    ``pim_bits=8``) for ``seed``; over ``mesh`` placed as the engine shards
    it.  ``roles`` is the reference's ``LEAF_ROLES`` (leaf name -> role of
    ``ROLES``), looked up before the default table."""
    from repro.serving.engine import init_serving_params
    from repro.serving.sharded import decode_shardings

    abstract = jax.eval_shape(
        lambda k: init_serving_params(cfg, k, bits=8), jax.random.PRNGKey(0))
    out = None if mesh is None else decode_shardings(abstract, mesh)
    roles = dict(roles or {})
    build = jax.jit(lambda k: _fill(abstract, k, roles),
                    out_shardings=out)
    return jax.block_until_ready(build(base_key(seed)))
