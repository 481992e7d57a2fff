"""Tensor-sharded decode: distribute the quantized PIM weight tree over a
1-D ``"model"`` mesh and serve from every engine path.

PiCaSO's *Scalable* claim is that PIM throughput grows by replicating
processing elements next to the memory blocks that hold the weights.  The
decode-time analogue: decode is memory-bound on the weight stream, so
partitioning the ``quantize_tree`` output over N devices cuts the per-device
weight bytes per token N-fold, and only the tiny per-token activations cross
the interconnect — the "spread the array, keep compute next to its shard"
argument of the UPMEM study (arXiv:2105.03814).

Layout (one rule, every leaf):

* a quantized leaf dict (``codes``/``scale`` + optional int4 markers) whose
  train-time rule shards it somewhere (``quant.decode_partition_spec``,
  derived from ``launch.sharding.param_spec``) is split over ``TP_AXIS``
  along its OUTPUT (last) dim — codes and scale together, markers (leading
  stack dims only) replicated — and tagged with a ``"tp"`` marker leaf;
* everything else (embeddings, norms, biases, caches, block tables, token
  state) is replicated.

Inside ``shard_map`` the marker drives the collectives:

* ``models.common.linear`` contracts the local shard weight-stationary
  (the ``set_kernel_dispatch`` kernel path applies per-shard) and
  all-gathers the output columns — a pure concatenation, so sharded greedy
  decode is TOKEN-IDENTICAL to the single-device engines;
* einsum consumers (MoE expert stacks, MLA absorbed W_uk/W_uv) go through
  ``models.common.dq``, which all-gathers the dequantized shard instead —
  per-device HBM still streams 1/N of the bytes, exactness preserved.

A rule-shardable leaf whose output dim does not divide the mesh quietly
stays replicated, mirroring ``launch.sharding.sanitize`` (none of the
stock reduced configs hits this — their rule-sharded leaves all have
8-divisible outputs, and e.g. mamba1's N=12 ``x_proj`` is already
replicated by the rule itself — but externally-loaded trees can).

The engines (``serving.engine``) accept ``mesh=``: admit-prefill and the
chunked decode scan lower ONCE under ``shard_map`` with these specs; the
host-side scheduler (admit / retire / preemption / page accounting) never
sees a device count.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import TP_AXIS
from repro.quant import decode_partition_spec


def make_decode_mesh(n_devices: Optional[int] = None,
                     axis: str = TP_AXIS) -> Mesh:
    """A 1-D tensor-parallel mesh over the first ``n_devices`` devices
    (default: all).  CPU tests force virtual devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (axis,))


def is_qleaf(tree) -> bool:
    """A ``quantize_tree`` leaf dict (codes/scale + optional markers)."""
    return isinstance(tree, dict) and "codes" in tree


def _last_dim_spec(ndim: int, axis: str) -> P:
    return P(*((None,) * (ndim - 1) + (axis,)))


def _split_over(names, codes_shape, size: int, axis: str) -> bool:
    """Whether a quantized leaf at ``names`` splits its output dim over a
    ``size``-wide ``axis`` (rule-shardable and divisible)."""
    spec = decode_partition_spec(names, len(codes_shape), axis)
    n_out = codes_shape[-1]
    return axis in spec and n_out % size == 0 and n_out >= size


def decode_shardings(params, mesh: Mesh, axis: str = TP_AXIS):
    """The ``NamedSharding`` of every leaf of a quantized tree (arrays or
    ``jax.eval_shape`` shapes) in its decode placement: codes and scale of
    a splittable quantized leaf over ``axis`` on their last dim, everything
    else replicated.  Lets a ``jit`` write its outputs straight into place
    (``serving.init_serving_params``, ``quantize_tree(mesh=)``)."""
    size = mesh.shape[axis]

    def conv(tree, names):
        if tree is None:
            return None
        if is_qleaf(tree):
            tp = _split_over(names, tree["codes"].shape, size, axis)
            return {k: NamedSharding(
                        mesh, _last_dim_spec(v.ndim, axis)
                        if tp and k in ("codes", "scale") else P())
                    for k, v in tree.items()}
        if isinstance(tree, dict):
            return {k: conv(v, names + [k]) for k, v in tree.items()}
        return NamedSharding(mesh, P())

    return conv(params, [])


def shard_quantized_tree(params, mesh: Mesh, axis: str = TP_AXIS):
    """Distribute a (possibly ``quantize_tree``-converted) parameter tree
    over ``mesh``'s ``axis``.

    Shardable quantized leaves (``quant.decode_partition_spec``) whose
    output dim divides the axis get codes+scale split along their last dim
    and a ``"tp"`` marker leaf added; every other leaf is replicated.  All
    leaves are ``device_put`` with their ``NamedSharding``
    (``decode_shardings``; a leaf already in place is not copied), so
    per-device HBM holds only its shard and
    ``pim_bytes(..., per_device=True)`` reports the split.

    Raises if a multi-device mesh ends up distributing NOTHING (e.g. a
    dense tree passed without ``pim_bits``): silently replicating every
    weight N times while paying shard_map overhead is never what a caller
    asking for tensor-sharded decode meant."""
    size = mesh.shape[axis]
    n_marked = 0

    def conv(tree, shardings):
        nonlocal n_marked
        if tree is None:
            return None
        if is_qleaf(tree):
            out = {k: jax.device_put(v, shardings[k]) for k, v in tree.items()}
            if axis in shardings["codes"].spec:
                n_marked += 1
                # Like the int4 "nibbles" markers, the tag carries the
                # leading stack dims so lax.scan over stacked layers can
                # slice it alongside codes/scale.
                out["tp"] = jax.device_put(
                    jnp.zeros(tree["codes"].shape[:-2], jnp.int8),
                    NamedSharding(mesh, P()))
            return out
        if isinstance(tree, dict):
            return {k: conv(v, shardings[k]) for k, v in tree.items()}
        return jax.device_put(tree, shardings)

    out = conv(params, decode_shardings(params, mesh, axis))
    if size > 1 and n_marked == 0:
        raise ValueError(
            f"nothing to distribute over the {size}-device '{axis}' mesh: "
            "the tree has no shardable quantized leaves (pass pim_bits=4/8 "
            "to the engine, or quantize_tree the params first)")
    return out


def tree_pspecs(params, axis: str = TP_AXIS):
    """The ``shard_map`` in_specs tree for a (marker-annotated) parameter
    tree: ``"tp"``-marked codes/scale carry ``axis`` on their last dim,
    everything else is replicated.  Derived from the markers themselves so
    the specs can never disagree with what ``linear``/``dq`` will gather."""

    def conv(tree):
        if tree is None:
            return None
        if is_qleaf(tree):
            tp = "tp" in tree
            return {
                k: (_last_dim_spec(v.ndim, axis)
                    if tp and k in ("codes", "scale") else P())
                for k, v in tree.items()
            }
        if isinstance(tree, dict):
            return {k: conv(v) for k, v in tree.items()}
        return P()

    return conv(params)
