"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run process must set XLA_FLAGS before any
jax initialization)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto(n: int) -> tuple:
    return (AxisType.Auto,) * n


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  The clustering plans place their
    arrays with NamedShardings and vmap or jit whole fits over them,
    leaving the sharding of every intermediate (gathers by sampled index
    above all) to the compiler's propagation; on an ``Explicit`` axis
    (what ``jax.make_mesh`` gives by default) each such gather would
    instead need an output sharding spelled out by hand.  The clustering
    mesh makers below build Auto meshes; ``repro.api`` passes a mesh a
    caller built some other way through this once."""
    return Mesh(mesh.devices, mesh.axis_names, axis_types=_auto(
        len(mesh.axis_names)))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: (16, 16) = 256 chips single pod;
    (2, 16, 16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 2):
    """Small CPU mesh for tests (uses however many devices exist)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model, model), ("data", "model"))


def make_cluster_mesh(model: int = 1):
    """Default data x model mesh for the sharded clustering plans: all
    devices on the data axis unless a model split is requested.  Used by
    ``repro.api`` when ``distribution='sharded'`` is asked for without an
    explicit mesh."""
    n = len(jax.devices())
    return jax.make_mesh((max(n // model, 1), model), ("data", "model"),
                         axis_types=_auto(2))


def make_restart_mesh(restarts: int, axis: str = "restart"):
    """1-axis mesh for the multi-restart clustering engine.

    The restart axis must DIVIDE the restart count (each device owns a
    whole number of restarts), so this picks the largest device count
    <= min(restarts, len(devices)) that divides ``restarts`` — e.g.
    R=4 on 8 devices -> a 4-device mesh; R=6 on 4 -> 3 devices;
    prime R=7 on 4 -> 1 device."""
    devs = jax.devices()
    size = next(d for d in range(min(restarts, len(devs)), 0, -1)
                if restarts % d == 0)
    return jax.make_mesh((size,), (axis,), devices=devs[:size],
                         axis_types=_auto(1))


def make_fused_mesh(restarts: int, model: int = 1,
                    axes: tuple = ("restart", "data", "model")):
    """3-axis mesh for the fused restart x data x model solver plan
    (``fused_restart_sharded``): the restart axis takes the largest device
    count <= min(restarts, n_devices) that DIVIDES ``restarts`` (each
    device owns a whole number of restart lanes, like
    :func:`make_restart_mesh`); the remaining devices split into
    data x model.  E.g. R=4 on 8 devices -> (4, 2, 1); R=2 on 8 with
    model=2 -> (2, 2, 2); 1 device -> (1, 1, 1) with all R restarts as
    sequential lanes on it."""
    devs = jax.devices()
    n = len(devs)
    r = next(d for d in range(min(restarts, n), 0, -1) if restarts % d == 0)
    rem = n // r
    if model < 1 or model > rem or rem % model:
        raise ValueError(
            f"model={model} does not divide the {rem} devices left after "
            f"the restart axis takes {r} of {n} (pick a model split that "
            f"divides {rem}, or shrink the restart count)")
    data = max(rem // model, 1)
    return jax.make_mesh((r, data, model), axes,
                         devices=devs[:r * data * model],
                         axis_types=_auto(len(axes)))


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a != "model")
