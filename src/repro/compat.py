"""Mesh construction for the installed JAX (0.9).

``make_mesh`` is the one spelling the launchers, tests and benchmarks use to
build a device mesh: every axis is ``AxisType.Auto`` unless the caller says
otherwise, so the SPMD partitioner shards what the specs leave open and the
training step's ``shard_map`` regions choose their manual axes themselves.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, axis_types=None, devices=None):
    """``jax.make_mesh`` with Auto axis types by default."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(tuple(axis_shapes))
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types, **kwargs)
