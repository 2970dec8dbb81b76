"""Draw kind ``wave_velocity``: squared Courant numbers of a velocity model.

arrays = c_max * (v / v_high)^2 with v ~ U(v_low, v_high) per cell: the
C of a wave with velocity v, at most c_max where v is fastest.
"""

import jax


def arrays(coef: dict, key, shape, dtype):
    """The (coef["arrays"], *shape) coefficient stack from `key`."""
    v = jax.random.uniform(key, (coef["arrays"],) + shape, dtype,
                           coef["v_low"], coef["v_high"])
    return coef["c_max"] * (v / coef["v_high"]) ** 2
