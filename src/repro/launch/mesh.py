"""Production mesh construction.

Importing this module never touches JAX device state — meshes are built
inside functions only, so launchers can set ``XLA_FLAGS`` first.
"""

from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod ('data','model'); two pods add a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def process_grid(devices) -> list[list]:
    """Arrange `devices` as a rectangular (process x local-device) grid.

    Row p holds exactly the devices owned by process p — rows ordered by
    ``process_index``, devices within a row by ``id`` — so the grid is the
    physical topology: crossing rows crosses hosts (the slow network),
    crossing columns stays on one host's locally-attached devices. Raises
    when the processes own unequal device counts (a lame host cannot sit in
    a rectangular mesh; rebuild on the healthy subset instead).

    Pure function of the device list (only ``.process_index`` and ``.id``
    are read), so tests can drive it with stand-in device objects.
    """
    devs = list(devices)
    if not devs:
        raise ValueError("process_grid needs at least one device")
    procs = sorted({d.process_index for d in devs})
    rows = [sorted((d for d in devs if d.process_index == p),
                   key=lambda d: d.id) for p in procs]
    counts = {p: len(row) for p, row in zip(procs, rows)}
    if len(set(counts.values())) != 1:
        raise ValueError(
            f"uneven process topology {counts}: a multi-host mesh needs the "
            "same local device count on every process — drop the lame host "
            "and rebuild over the healthy subset "
            "(repro.distributed.elastic.build_mesh)")
    return rows


def make_process_mesh(devices=None) -> jax.sharding.Mesh:
    """Multi-host mesh keyed on the process topology.

    The device grid is `process_grid`: mesh row p is exactly the local
    device set of process p (``jax.process_index()`` order). `GridSharding`
    maps the 'data' axis to grid-z and 'model' to grid-y, so the deep-halo
    z exchange — the ppermute the overlapped super-step hides behind the
    interior advance — is the one crossing host boundaries, while the y
    exchange stays on each host's locally-attached devices. On a single
    process this degenerates to a (1, n_local) mesh, and this process's own
    row is ``mesh.devices[jax.process_index()]``.
    """
    rows = process_grid(jax.devices() if devices is None else devices)
    grid = np.empty((len(rows), len(rows[0])), dtype=object)
    for i, row in enumerate(rows):
        grid[i, :] = row
    return jax.sharding.Mesh(grid, ("data", "model"))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over however many (possibly forced-host) devices exist."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over (DP/FSDP axes)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh: jax.sharding.Mesh) -> str:
    """Mesh axis model-parallel (TP) parameters are sharded over."""
    return "model"
