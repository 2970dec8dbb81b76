"""Seeded problem draw: the cell's state and coefficients, made on the device.

One jitted call per run turns ``--seed`` and the configuration's draw
parameters into ``(cur, prev)`` and the stacked coefficient arrays, already
in the layout (and, on several chips, the sharding) the timed entry reads.
Nothing is made on the host, so set-up does not grow with the grid.

The draw kinds are the configuration file's ``coefficients.draw``:

  diffusion      arrays[1:] ~ U(low, high) per cell, arrays[0] = 1 - their
                 sum: a convex update, so fields stay bounded forever
  wave_velocity  arrays[0] = c_max * (v / v_high)^2, v ~ U(v_low, v_high):
                 the squared Courant number of a wave with velocity v

The seed may exceed 32 bits: its low and high words both enter the key.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_words(seed: int) -> tuple[int, int]:
    """(low, high) 32-bit words of a non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return seed & 0xFFFFFFFF, seed >> 32


def _arrays(coef: dict, key, shape, dtype):
    n = coef["arrays"]
    if n == 0:
        return None
    kind = coef["draw"]
    if kind == "diffusion":
        nb = jax.random.uniform(key, (n - 1,) + shape, dtype,
                                coef["low"], coef["high"])
        centre = 1.0 - jnp.sum(nb, axis=0, keepdims=True)
        return jnp.concatenate([centre, nb], axis=0)
    if kind == "wave_velocity":
        v = jax.random.uniform(key, (n,) + shape, dtype, coef["v_low"],
                               coef["v_high"])
        return coef["c_max"] * (v / coef["v_high"]) ** 2
    raise ValueError(f"unknown coefficient draw {kind!r}")


def _draw(config: dict, shape, lo, hi):
    dtype = jnp.dtype(config["dtype"])
    key = jax.random.fold_in(jax.random.key(lo), hi)
    k_cur, k_prev, k_arr = jax.random.split(key, 3)
    scale = config["state"]["scale"]
    cur = scale * jax.random.normal(k_cur, shape, dtype)
    prev = (scale * jax.random.normal(k_prev, shape, dtype)
            if config["time_order"] == 2 else cur)
    return (cur, prev), _arrays(config["coefficients"], k_arr, shape, dtype)


def draw(config: dict, shape, seed: int, state_sharding=None,
         array_sharding=None):
    """((cur, prev), arrays or None) for `config` on grid `shape`, on device.

    The shardings place the outputs where the entry reads them (None: the
    default device). Scalars are the configuration's own list.
    """
    shape = tuple(shape)
    lo, hi = seed_words(seed)
    out_shardings = None
    if state_sharding is not None:
        out_shardings = ((state_sharding, state_sharding),
                         array_sharding if config["coefficients"]["arrays"]
                         else None)
    fn = jax.jit(partial(_draw, config, shape), out_shardings=out_shardings)
    return fn(jnp.uint32(lo), jnp.uint32(hi))
