"""Mesh construction with Auto axes.

``jax.make_mesh`` gives every axis ``AxisType.Explicit`` unless told
otherwise.  The library's operands are placed with ``NamedSharding``
and its multiplies run in ``shard_map`` programs that expect Auto
(GSPMD) axes, so every mesh is built here.

Keep this module dependency-free (jax only) so anything may import it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh"]


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))
