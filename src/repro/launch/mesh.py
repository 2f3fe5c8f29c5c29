"""Production meshes.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run pins the device count via XLA_FLAGS
*before* the first jax init; anything that forces an earlier init would
lock the real 1-device topology in).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    # Auto axes: the logical-rule sharding (with_sharding_constraint,
    # NamedSharding placements) and the manual-TP shard_map both expect
    # the partitioner to propagate shardings, not the Explicit-axis mode
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds the 2-pod DCI axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh over the real local device (smoke tests / examples)."""
    return make_mesh((1, 1), ("data", "model"))


def make_sim_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh over simulated host devices.

    The multi-device serving checks run TP/DP on one machine by asking XLA
    for virtual CPU devices. That only works if the device count was pinned
    BEFORE the first jax init, so this validates eagerly and names the knob
    instead of letting jax raise a shape error deep inside ``make_mesh``.
    """
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got ({data}, {model})")
    need = data * model
    have = jax.device_count()
    if need > have:
        raise RuntimeError(
            f"make_sim_mesh({data}, {model}) needs {need} devices but jax "
            f"sees {have}. Set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} in the "
            f"environment BEFORE the first jax import (the device count "
            f"locks at jax init; see scripts/sharded_serving_check.py).")
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:need])


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
