"""Activation sharding constraints (MaxText-style logical activation axes).

constrain() is a no-op outside a mesh context (smoke tests), and drops any
axis the current mesh doesn't have, so the same model code serves 1-device
CPU tests, the 16x16 pod, and the 2x16x16 multi-pod.
"""

from __future__ import annotations

import jax


BATCH = ("pod", "data")
MODEL = "model"


def constrain(x, *axes):
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    names = set(mesh.axis_names)

    def resolve(a, dim):
        if isinstance(a, str):
            a = (a,)
        if isinstance(a, tuple):
            kept = tuple(n for n in a if n in names)
            if not kept:
                return None
            prod = 1
            for n in kept:
                prod *= mesh.shape[n]
            return kept if x.shape[dim] % prod == 0 else None
        return None

    parts = tuple(resolve(a, i) for i, a in enumerate(axes))
    if not any(parts):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*parts))
