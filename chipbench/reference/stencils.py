"""Plain reference of the configurations' stencils, in jax.numpy.

Written from the paper's listings (Malas et al., arXiv:1510.04995) and
independent of the program under test: it imports nothing from it and
takes no table, plan or coefficient the program made. Each op's step lives
in a file of its own, ``chipbench/reference/ops/<op>.py``, found by the
configuration's ``op`` and exposing ``step(cur, prev, arrays, scalars)``;
a new op needs a new file there and no edit here. Each step updates the
interior and keeps the R-deep Dirichlet frame of the state it was given; a
call of n steps returns the last two levels, as the program's entries do.

`advance` runs in the dtype it is given: float32 is the reference, and
bfloat16 is the lower-precision control that the comparison must reject.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench import cells


def core(a, r):
    """The interior of `a`: its R-deep frame cut away on every axis."""
    return a[r:-r, r:-r, r:-r]


def shift(a, r, axis, off):
    """Core-sized view of `a` displaced by `off` along `axis` (|off| <= r)."""
    idx = []
    for ax in range(3):
        d = off if ax == axis else 0
        idx.append(slice(r + d, a.shape[ax] - r + d or None))
    return a[tuple(idx)]


@partial(jax.jit, static_argnames=("op", "scalars", "n_steps", "dtype"))
def advance(op: str, state, arrays, scalars: tuple, n_steps: int, dtype):
    """n_steps of `op` from (cur, prev) in `dtype`; returns float32 levels.

    Returns (level n, level n-1), the pair the program's entries return.
    """
    step = cells.load_module("reference/ops", op).step
    dt = jnp.dtype(dtype)
    cur, prev = (s.astype(dt) for s in state)
    arrays = arrays.astype(dt) if arrays is not None else None
    sc = tuple(jnp.asarray(v, dt) for v in scalars)

    def body(_, carry):
        c, p = carry
        return step(c, p, arrays, sc), c

    cur, prev = jax.lax.fori_loop(0, n_steps, body, (cur, prev))
    return cur.astype(jnp.float32), prev.astype(jnp.float32)
