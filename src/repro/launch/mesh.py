"""Production mesh construction.

The ``model`` axis is the *lane* axis (paper C1): 16 lanes per pod, each
lane = 16 chips of the ``data`` ring.  A production pod is a 16×16 slice of
a TPU v5e torus (256 chips); the multi-pod mesh stacks 2 pods on the ``pod``
axis (512 chips), which is the axis the inter-pod (DCN/ICI) hierarchical
reduction (C4) crosses.

Defined as functions, not module constants, so importing this module never
touches jax device state (the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

SINGLE_POD_SHAPE = (16, 16)
MULTI_POD_SHAPE = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_test_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    """Small mesh over however many (CPU) devices the test process has."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def replica_mesh(replicas: int) -> Mesh:
    """The serving router's mesh: a ``data`` axis of one device per replica
    over the first ``min(replicas, device_count)`` devices, ``model`` = 1.
    ``data_shards`` then hands each replica exactly one device (cycling
    when replicas outnumber the devices)."""
    n = min(replicas, jax.device_count())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:n])


def chips(mesh: Mesh) -> int:
    return mesh.devices.size


def data_shards(mesh: Mesh, n: int) -> list:
    """Split ``mesh``'s ``data`` axis into ``n`` replica device groups.

    The serving router places engine replica *i* on ``shards[i]`` — each
    shard is a flat device list covering a contiguous slice of the data
    axis (all other axes included whole, so a shard is a full model's
    worth of chips).  When ``n`` exceeds the data-axis extent the shards
    cycle — replicas time-share devices, which is exactly the single-CPU
    test topology (every replica on the one host device).
    """
    if n < 1:
        raise ValueError(f"need n >= 1 replica shards, got {n}")
    axis = mesh.axis_names.index("data")
    extent = mesh.devices.shape[axis]
    groups = min(n, extent)
    # contiguous slices, first (extent % groups) slices one wider
    width, rem = divmod(extent, groups)
    shards, start = [], 0
    for g in range(groups):
        stop = start + width + (1 if g < rem else 0)
        idx = [slice(None)] * mesh.devices.ndim
        idx[axis] = slice(start, stop)
        shards.append(list(mesh.devices[tuple(idx)].flat))
        start = stop
    return [shards[i % groups] for i in range(n)]
