"""Plain reference of the configurations' stencils, in jax.numpy.

Written from the paper's listings (Malas et al., arXiv:1510.04995,
Listings 2 and 3) and independent of the program under test: it imports
nothing from it and takes no table, plan or coefficient the program made.
Each step updates the interior and keeps the R-deep Dirichlet frame of the
state it was given; a call of n steps returns the last two levels, as the
program's entries do.

`advance` runs in the dtype it is given: float32 is the reference, and
bfloat16 is the lower-precision control that the comparison must reject.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _core(a, r):
    return a[r:-r, r:-r, r:-r]


def _shift(a, r, axis, off):
    """Core-sized view of `a` displaced by `off` along `axis` (|off| <= r)."""
    idx = []
    for ax in range(3):
        d = off if ax == axis else 0
        idx.append(slice(r + d, a.shape[ax] - r + d or None))
    return a[tuple(idx)]


def step_7pt_var(cur, prev, arrays, scalars):
    """Listing 2: U = c0*V + sum over the six axis neighbours of c_k*V_k.

    arrays: (7, z, y, x) as [centre, z-, z+, y-, y+, x-, x+].
    """
    del prev, scalars
    r = 1
    out = _core(arrays[0], r) * _core(cur, r)
    k = 1
    for ax in range(3):
        for o in (-1, 1):
            out = out + _core(arrays[k], r) * _shift(cur, r, ax, o)
            k += 1
    return cur.at[r:-r, r:-r, r:-r].set(out)


def step_25pt_const(cur, prev, arrays, scalars):
    """Listing 3: U' = 2V - U + C * (c0*V + sum_d c_d * (6 neighbours at d)).

    arrays: (1, z, y, x) holding C; scalars: (c0, c1, c2, c3, c4).
    """
    r = 4
    c = scalars
    lap = c[0] * _core(cur, r)
    for d in range(1, 5):
        acc = None
        for ax in range(3):
            for o in (-1, 1):
                v = _shift(cur, r, ax, o * d)
                acc = v if acc is None else acc + v
        lap = lap + c[d] * acc
    out = 2.0 * _core(cur, r) - _core(prev, r) + _core(arrays[0], r) * lap
    return cur.at[r:-r, r:-r, r:-r].set(out)


STEPS = {"7pt-var": step_7pt_var, "25pt-const": step_25pt_const}


@partial(jax.jit, static_argnames=("op", "scalars", "n_steps", "dtype"))
def advance(op: str, state, arrays, scalars: tuple, n_steps: int, dtype):
    """n_steps of `op` from (cur, prev) in `dtype`; returns float32 levels.

    Returns (level n, level n-1), the pair the program's entries return.
    """
    step = STEPS[op]
    dt = jnp.dtype(dtype)
    cur, prev = (s.astype(dt) for s in state)
    arrays = arrays.astype(dt) if arrays is not None else None
    sc = tuple(jnp.asarray(v, dt) for v in scalars)

    def body(_, carry):
        c, p = carry
        return step(c, p, arrays, sc), c

    cur, prev = jax.lax.fori_loop(0, n_steps, body, (cur, prev))
    return cur.astype(jnp.float32), prev.astype(jnp.float32)
