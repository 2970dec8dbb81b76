"""The comparison that decides ``correct``.

The number compared is ``max_rel_gap``: over both returned levels, the
largest absolute difference between the program's output and the
reference's, divided by the largest magnitude of the reference's levels.
It is taken on the device, in the arrays' own sharding, and only the two
scalars come back to the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _gap(got, want):
    diff = jnp.float32(0.0)
    scale = jnp.float32(0.0)
    finite = jnp.bool_(True)
    for g, w in zip(got, want):
        g = g.astype(jnp.float32)
        diff = jnp.maximum(diff, jnp.max(jnp.abs(g - w)))
        scale = jnp.maximum(scale, jnp.max(jnp.abs(w)))
        finite = finite & jnp.all(jnp.isfinite(g))
    return diff, scale, finite


def max_rel_gap(got, want) -> float:
    """max |got - want| / max |want| over both levels; inf if got is not finite.

    A reference that is all zeros gives inf too: such a field cannot tell a
    right answer from a wrong one.
    """
    diff, scale, finite = (float(x) for x in _gap(tuple(got), tuple(want)))
    if not finite or scale == 0.0:
        return float("inf")
    return diff / scale
