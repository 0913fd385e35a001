"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod = 16×16 = 256 chips (TPU v5e pod);
multi-pod adds a leading "pod" axis (2 pods = 512 chips). The dry-run
launcher sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import to build these meshes on CPU.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests/examples."""
    n = len(jax.devices())
    if n >= 2:
        return jax.make_mesh((n // 2, 2), ("data", "model"))
    return jax.make_mesh((1, 1), ("data", "model"))


def make_group_mesh(n_groups: int, *, n_devices: int | None = None,
                    axis_name: str = "group"):
    """1-D ``(axis_name,)`` mesh for device-sharded group execution.

    The engine's ``G`` ordering groups are independent per tick (only the
    round-robin merge crosses them), so they shard along one mesh axis.
    The mesh size clamps to the available devices and to ``n_groups`` (a
    device holding zero group rows would only idle in every collective);
    when the clamped size does not divide ``n_groups``, callers pad the
    group axis with inert SKIP groups — :func:`group_padding` gives the
    row count — so every device carries the same number of rows.
    """
    if n_groups < 1:
        raise ValueError(f"make_group_mesh needs n_groups >= 1, got "
                         f"{n_groups}")
    avail = len(jax.devices())
    n = avail if n_devices is None else min(int(n_devices), avail)
    n = max(1, min(n, int(n_groups)))
    # Auto, not make_mesh's Explicit default: the meshed engine returns
    # group-sharded arrays to callers who reshape them freely (the merge
    # commit gate flattens [L, G] logs), which Explicit typing refuses
    return jax.make_mesh((n,), (axis_name,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def group_padding(n_groups: int, mesh) -> int:
    """Inert rows to append so the group axis divides the mesh size.

    Padded rows are *fresh* (nothing admitted, zero traffic): they assign
    nothing, recycle nothing, and their merge rounds would be pure SKIP —
    the meshed engine slices them off before touching the merge log, so
    padding never changes the merged output by a bit."""
    n = int(mesh.devices.size)
    return (-int(n_groups)) % n


def mesh_axes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def fsdp_axes(mesh) -> tuple:
    """Axes used for fully-sharded parameter (and batch) placement."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
