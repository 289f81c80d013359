"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state -- the dry-run sets XLA_FLAGS before first init.

Axes:
  single-pod: ("data", "model")        = (16, 16)   -> 256 chips
  multi-pod:  ("pod", "data", "model") = (2, 16, 16) -> 512 chips

`fsdp_axes(mesh)` returns the axis names parameters are fully-sharded over
(the "pod" axis joins data-parallel sharding in the multi-pod mesh).

Every axis is `AxisType.Auto`: `jax.make_mesh` defaults to Explicit axes,
under which sharding-in-types rejects ops such as the embedding gather
whose output sharding it cannot infer.  The code here shards with
PartitionSpecs and shard_map, i.e. the Auto model.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / elastic restore onto different topology."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    """Axis names carrying the batch dimension."""
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))


def fsdp_axes(mesh) -> tuple:
    """Axis names parameters are fully sharded over (ZeRO-3 style)."""
    return dp_axes(mesh)
