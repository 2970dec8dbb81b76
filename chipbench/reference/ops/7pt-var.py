"""7pt-var: Malas et al., arXiv:1510.04995, Listing 2.

U = c0*V + the sum over the six axis neighbours of c_k*V_k, first order in
time, each c_k an array over the grid.
"""

from chipbench.reference.stencils import core, shift


def step(cur, prev, arrays, scalars):
    """One step. arrays: (7, z, y, x) as [centre, z-, z+, y-, y+, x-, x+]."""
    del prev, scalars
    r = 1
    out = core(arrays[0], r) * core(cur, r)
    k = 1
    for ax in range(3):
        for o in (-1, 1):
            out = out + core(arrays[k], r) * shift(cur, r, ax, o)
            k += 1
    return cur.at[r:-r, r:-r, r:-r].set(out)
