"""Draw kind ``diffusion``: a convex variable-coefficient heat step.

arrays[1:] ~ U(low, high) per cell and arrays[0] = 1 - their sum, so each
updated value is a convex combination of its neighbours and fields stay
bounded over any number of steps.
"""

import jax
import jax.numpy as jnp


def arrays(coef: dict, key, shape, dtype):
    """The (coef["arrays"], *shape) coefficient stack from `key`."""
    n = coef["arrays"]
    nb = jax.random.uniform(key, (n - 1,) + shape, dtype,
                            coef["low"], coef["high"])
    centre = 1.0 - jnp.sum(nb, axis=0, keepdims=True)
    return jnp.concatenate([centre, nb], axis=0)
