"""Production mesh definition (deliverable e).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated sharding),
    the mode the sharding rules in ``launch.sharding`` are written for."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_plan(plan):
    """Mesh for an ElasticPlan (runtime.plan_elastic_remesh)."""
    return make_mesh(plan.shape, ("pod", "data", "model")[-len(plan.shape):])


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh: ('pod','data') or ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
