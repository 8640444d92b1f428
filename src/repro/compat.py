"""Mesh construction with the axis types this repo relies on.

``make_mesh`` keeps every axis ``Auto`` (jax's default is ``Explicit``),
so sharding inside jit is left to the compiler as the solver expects.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)
