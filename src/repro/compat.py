"""repro.compat — the handful of jax mesh/shard_map calls the repo makes,
in one place.

    make_mesh(shape, names)      jax.make_mesh with Auto axis types
    shard_map(fn, mesh, ...)     jax.shard_map
    axis_size(axis) -> int       static team size inside shard_map
    axis_index(axis)             traced rank (re-exported for symmetry)
"""
from __future__ import annotations

from typing import Sequence, Union

import jax

Axis = Union[str, Sequence[str]]


def _canon(axis: Axis):
    return axis if isinstance(axis, str) else tuple(axis)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None, explicit: bool = False) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with Auto axis types (jax defaults to Explicit,
    which breaks shard_map-with-manual-collectives code written for
    Auto)."""
    at = (jax.sharding.AxisType.Explicit if explicit
          else jax.sharding.AxisType.Auto)
    kw = {"axis_types": (at,) * len(axis_names)}
    if devices is not None:
        kw["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kw)


def shard_map(fn, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis: Axis) -> int:
    """Static size of a (possibly multi-) mesh axis, callable inside
    shard_map at trace time."""
    return int(jax.lax.axis_size(_canon(axis)))


def axis_index(axis: Axis):
    return jax.lax.axis_index(_canon(axis))
