"""Mesh, shard_map and tracer helpers over the installed jax (0.9).

Every call site builds meshes and shard_maps through these helpers, so an
API move in jax is fixed in one place; the sharding tests pin that.
"""

from __future__ import annotations

import jax
from jax._src.core import trace_state_clean  # noqa: F401  (re-export)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def is_tracer(x) -> bool:
    """True when ``x`` is a jax tracer."""
    return isinstance(x, jax.core.Tracer)
