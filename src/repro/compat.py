"""The few jax calls this repo makes with fixed arguments, in one place.

Every mesh is GSPMD-auto on all axes, every shard_map is manual over the
worker axes only, and the Pallas kernel wrappers read the ambient abstract
mesh; these helpers spell those conventions once.
"""

from __future__ import annotations

from typing import Any, Iterable, Sequence, Tuple

import jax

PyTree = Any


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """jax.make_mesh with every axis GSPMD-auto."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def shard_map(f, *, mesh, in_specs, out_specs, manual_axes: Iterable[str]):
    """shard_map manual over ``manual_axes``, GSPMD-auto over the rest."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual_axes))


def pcast_varying(tree: PyTree, axes: Tuple[str, ...]) -> PyTree:
    """Mark a replicated value as varying over ``axes``."""
    return jax.lax.pcast(tree, tuple(axes), to="varying")


def cost_analysis(compiled) -> dict:
    """compiled.cost_analysis(), or {} where the backend reports none."""
    return compiled.cost_analysis() or {}


def abstract_mesh():
    """The ambient abstract mesh."""
    return jax.sharding.get_abstract_mesh()


def auto_axes_of(mesh, *, exclude: Tuple[str, ...] = ()) -> Tuple[str, ...]:
    """Names of GSPMD-auto axes of ``mesh`` minus ``exclude``; () outside
    any mesh."""
    if mesh is None or mesh.empty:
        return ()
    auto = jax.sharding.AxisType.Auto
    return tuple(n for n, t in zip(mesh.axis_names, mesh.axis_types)
                 if n not in exclude and t == auto)
