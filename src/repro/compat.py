"""One spelling for each jax mesh/sharding API this repo uses.

The codebase targets the current jax mesh/sharding surface
(``jax.make_mesh`` with axis types, ``jax.set_mesh``, ``jax.shard_map``,
``jax.sharding.get_abstract_mesh``); these helpers fix the arguments the
repo always passes.
"""
from __future__ import annotations

import contextlib

import jax

__all__ = [
    "AXIS_TYPE_AUTO",
    "make_mesh",
    "set_mesh",
    "shard_map",
    "get_abstract_mesh",
]

AXIS_TYPE_AUTO = jax.sharding.AxisType.Auto


def make_mesh(shape, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (explicit types are what
    shard_map interop expects)."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AXIS_TYPE_AUTO,) * len(axis_names),
                         **kwargs)


@contextlib.contextmanager
def set_mesh(mesh):
    """Ambient-mesh context (``jax.set_mesh``)."""
    with jax.set_mesh(mesh):
        yield mesh


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma`` named ``check``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def get_abstract_mesh():
    """The ambient abstract mesh, or None when no mesh with axes is set
    (callers treat None as "no ambient mesh, skip the hint")."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return None
    return mesh
