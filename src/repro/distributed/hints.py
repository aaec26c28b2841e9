"""Ambient-mesh sharding hints usable inside model code.

``constrain(x, spec...)`` applies ``with_sharding_constraint`` against the
ambient mesh (``repro.compat.set_mesh``), silently dropping axis names the
mesh doesn't have and becoming a no-op when there is no mesh (CPU smoke
tests). This lets model internals pin the few layouts GSPMD gets wrong
(split-K decode attention) without threading mesh objects through every
call. The bare PartitionSpec binds to the ambient abstract mesh.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

from repro import compat

__all__ = ["constrain"]

AxisEntry = Union[None, str, Tuple[str, ...]]


def constrain(x: jax.Array, *entries: AxisEntry) -> jax.Array:
    mesh = compat.current_abstract_mesh()
    names = set(getattr(mesh, "axis_names", ()) or ())
    if not names:
        return x

    def keep(e: AxisEntry) -> AxisEntry:
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    spec = [keep(e) for e in entries]
    # Drop axes whose mesh size does not divide the dim (jit-arg rule is
    # stricter than constraints, but keep it uniform and predictable).
    sizes = compat.mesh_axis_sizes(mesh)
    for i, (e, d) in enumerate(zip(spec, x.shape)):
        if e is None:
            continue
        n = 1
        for a in e if isinstance(e, tuple) else (e,):
            n *= sizes[a]
        if d % n:
            spec[i] = None
    return jax.lax.with_sharding_constraint(x, P(*spec))
