"""Mesh context: lets deep model code apply sharding constraints without
threading the mesh through every call signature.

Model code calls `constrain(x, 'model', None, ...)`; when a mesh has been
installed (`use_mesh`) this becomes a with_sharding_constraint, otherwise
it is a no-op (single-device smoke tests)."""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CURRENT: list = []


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    _CURRENT.append(mesh)
    try:
        yield mesh
    finally:
        _CURRENT.pop()


def current_mesh() -> Optional[Mesh]:
    return _CURRENT[-1] if _CURRENT else None


def data_axes() -> tuple:
    m = current_mesh()
    if m is None:
        return ()
    return tuple(a for a in m.axis_names if a != "model")


def dp_size() -> int:
    m = current_mesh()
    if m is None:
        return 1
    import numpy as np
    return int(np.prod([m.shape[a] for a in data_axes()]))


def constrain(x: jax.Array, *spec_parts):
    """with_sharding_constraint if a mesh is installed; else identity.
    Spec parts may name axes ('model'), the pseudo-axis 'data*' (all
    non-model axes), or None."""
    mesh = current_mesh()
    if mesh is None:
        return x
    parts = []
    for p in spec_parts:
        if p == "data*":
            parts.append(data_axes())
        else:
            parts.append(p)
    # drop axis names whose dimension size is not divisible
    fixed = []
    for dim, p in enumerate(parts):
        if p is None:
            fixed.append(None)
            continue
        names = p if isinstance(p, tuple) else (p,)
        size = 1
        for nm in names:
            size *= mesh.shape[nm]
        fixed.append(p if x.shape[dim] % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))
