"""Mesh and shard_map wrappers that fill in this codebase's defaults.

* ``make_mesh`` — ``jax.make_mesh`` with every axis ``AxisType.Auto``
  unless ``axis_types`` says otherwise (the only mode this codebase
  uses; jax's own default is Explicit).
* ``shard_map`` — ``jax.shard_map`` with ``check_vma=False`` and the
  manual axes accepted as any iterable (``None``: every mesh axis).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` with all-Auto axes by default."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types,
                         **kw)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=False):
    """``jax.shard_map`` with ``check_vma=False`` by default."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


__all__ = ["make_mesh", "shard_map"]
