"""Seeded problem draw: the cell's state and coefficients, made on the device.

One jitted call per run turns ``--seed`` and the configuration's draw
parameters into ``(cur, prev)`` and the stacked coefficient arrays, already
in the layout (and, on several chips, the sharding) the timed entry reads.
Nothing is made on the host, so set-up does not grow with the grid.

The draw kind is the configuration file's ``coefficients.draw``. Each kind
lives in a file of its own, ``chipbench/draws/<kind>.py``, which exposes
``arrays(coef, key, shape, dtype)``; a new kind needs a new file there and
no edit here.

The seed may exceed 32 bits: its low and high words both enter the key.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench import cells


def seed_words(seed: int) -> tuple[int, int]:
    """(low, high) 32-bit words of a non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return seed & 0xFFFFFFFF, seed >> 32


def _arrays(coef: dict, key, shape, dtype):
    if coef["arrays"] == 0:
        return None
    draw = cells.load_module("draws", coef["draw"])
    return draw.arrays(coef, key, shape, dtype)


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
