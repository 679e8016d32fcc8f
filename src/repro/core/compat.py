"""One spelling of the mesh and ``shard_map`` calls for the installed JAX.

    from repro.core.compat import make_mesh, shard_map

``jax.make_mesh`` builds *Explicit* axes by default; the engines index and
push host packets into mesh-sharded state with plain array ops, which only
Auto axes allow, so every mesh here is built with Auto axes.  The engines'
``shard_map`` bodies mix per-granule state with collectives in ways the
varying-manual-axes checker rejects, so the check is off.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "shard_map"]


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Any = None,
):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )


def shard_map(f: Callable, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
