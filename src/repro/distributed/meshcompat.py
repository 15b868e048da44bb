"""The JAX mesh-context calls this repo uses, in one place.

Every mesh-context consumer goes through this module, so a change in
JAX's mesh surface is absorbed here.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def get_abstract_mesh():
    """The ambient AbstractMesh, or ``None`` when no mesh context is set."""
    am = jax.sharding.get_abstract_mesh()
    return am if am.axis_names else None


def set_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient (abstract) mesh."""
    return jax.sharding.set_mesh(mesh)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names, axis_types=(AxisType.Auto,) * len(axis_names)
    )


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes type check.

    The per-device bodies here (lane-sharded event scans, explicit
    collectives) start their scan carries from replicated constants and
    make them device-varying inside the loop, which the check rejects
    although every output is well defined per device.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def abstract_mesh(axis_shapes, axis_names) -> AbstractMesh:
    """An AbstractMesh of the given axis sizes and names."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))
