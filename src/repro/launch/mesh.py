"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device counts are locked on first jax init).
"""
from __future__ import annotations

from typing import Optional

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code places arrays with
    ``NamedSharding``s and leaves propagation to the compiler, which
    Explicit axes (``make_mesh``'s default) refuse for its gathers."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def make_slot_mesh(n_slot: Optional[int] = None, member: int = 1):
    """Serving mesh for the slot-sharded stream server.

    A 1-D ``("slot",)`` mesh over ``n_slot`` devices (default: all of
    them), or a 2-D ``("slot", "member")`` mesh when ``member > 1`` (an
    ensemble-of-slots serving fleet: both axes are embarrassingly
    parallel).  The axis names are what the ``slot`` / ``member`` logical
    rules in ``repro.distributed.sharding.DEFAULT_RULES`` resolve to, so
    ``guarded_spec(..., ("slot", ...))`` shards state trees over this mesh
    with no extra rule plumbing.

    Uses the first ``n_slot * member`` devices, so a sweep over device
    counts (the scaling bench) can build 1/2/4/8-device meshes inside one
    process with ``--xla_force_host_platform_device_count=8``.
    """
    avail = jax.device_count()
    if n_slot is None:
        n_slot = avail // member
    need = n_slot * member
    if need > avail:
        raise ValueError(
            f"make_slot_mesh: {n_slot} slot x {member} member devices "
            f"requested but only {avail} available"
        )
    devices = jax.devices()[:need]
    if member > 1:
        import numpy as _np

        return jax.sharding.Mesh(
            _np.asarray(devices).reshape(n_slot, member), ("slot", "member")
        )
    return jax.sharding.Mesh(devices, ("slot",))
