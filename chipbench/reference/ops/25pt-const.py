"""25pt-const: Malas et al., arXiv:1510.04995, Listing 3.

The isotropic wave equation at radius 4, second order in time:
U' = 2V - U + C * (c0*V + sum over d of c_d * (the 6 neighbours at d)).
"""

from chipbench.reference.stencils import core, shift


def step(cur, prev, arrays, scalars):
    """One step. arrays: (1, z, y, x) holding C; scalars: (c0, ..., c4)."""
    r = 4
    c = scalars
    lap = c[0] * core(cur, r)
    for d in range(1, 5):
        acc = None
        for ax in range(3):
            for o in (-1, 1):
                v = shift(cur, r, ax, o * d)
                acc = v if acc is None else acc + v
        lap = lap + c[d] * acc
    out = 2.0 * core(cur, r) - core(prev, r) + core(arrays[0], r) * lap
    return cur.at[r:-r, r:-r, r:-r].set(out)
