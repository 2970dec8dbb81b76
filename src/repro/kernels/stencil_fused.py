"""Ghost-zone (overlapped) fused temporal-blocking kernel.

Beyond-paper candidate: each (z,y) block DMAs a window haloed by g = R*T_b,
runs T_b time steps entirely in VMEM (ping-pong scratch), and writes the block
once. HBM code balance drops by ~T_b at the price of redundant halo compute —
the right trade at TPU's 0.004 B/F machine balance (see DESIGN.md), which is
why the paper's CPU-era rejection of overlapped tiling is revisited here.

The in-VMEM compute is the sweep generated from the operator IR; the VMEM
window set is derived from the op too: current level, previous level iff
`time_order == 2`, one stacked coefficient window iff the op has array
coefficients, and a ping-pong buffer iff first-order (a 2nd-order op
ping-pongs through its loaded prev window instead).

Validity shrinks by R per in-VMEM step, so after T_b steps exactly the
un-haloed block center is correct; everything else is clipped by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ir
from repro.core import stencils as st
from repro.kernels import config


def _kernel(spec: st.StencilSpec, t_block: int, bz: int, by: int,
            grid_shape, n_in: int, scalars, *refs):
    inputs = refs[:n_in]
    cur_out, prev_out = refs[n_in:n_in + 2]
    wins = refs[n_in + 2:-2]
    w_frame = refs[-2]
    sem = refs[-1]
    r = spec.radius
    g = r * t_block
    nz, ny, nx = grid_shape
    i, j = pl.program_id(0), pl.program_id(1)

    for src, dst in zip(inputs, wins):  # only real streams are DMA'd
        if len(src.shape) == 3:
            idx = (pl.ds(i * bz, bz + 2 * g), pl.ds(j * by, by + 2 * g))
        else:
            idx = (slice(None), pl.ds(i * bz, bz + 2 * g),
                   pl.ds(j * by, by + 2 * g))
        cp = pltpu.make_async_copy(src.at[idx], dst, sem)
        cp.start()
        cp.wait()

    # window layout: [cur] [+prev if 2nd order] [+coeff stack] [+ping-pong]
    k = 1
    if spec.time_order == 2:
        bufs = [wins[0], wins[k]]          # cur, prev (both loaded)
        k += 1
    else:
        bufs = [wins[0], wins[-1]]         # cur + un-loaded ping-pong buffer
    coeff_win = wins[k][...] if spec.n_coeff_arrays else None
    # Dirichlet frame mask in window coordinates: cells whose ORIGINAL grid
    # coordinate lies in the fixed boundary frame (or in the pad) must be
    # restored to their initial values after every in-VMEM step — the naive
    # sweep never updates them, so neither may the fused chain.
    wshape = wins[0].shape
    z_io = jax.lax.broadcasted_iota(jnp.int32, wshape, 0) + i * bz
    y_io = jax.lax.broadcasted_iota(jnp.int32, wshape, 1) + j * by
    x_io = jax.lax.broadcasted_iota(jnp.int32, wshape, 2)
    frame = ((z_io < g + r) | (z_io >= g + nz - r)
             | (y_io < g + r) | (y_io >= g + ny - r)
             | (x_io < g + r) | (x_io >= g + nx - r))
    w_frame[...] = bufs[0][...]

    sweep = ir.make_sweep(spec)
    for _ in range(t_block):  # static unroll: T_b in-VMEM steps
        new = sweep(bufs[0][...], bufs[1][...], coeff_win, scalars)
        bufs[1][...] = jnp.where(frame, w_frame[...], new)
        bufs = bufs[::-1]

    cur_out[...] = bufs[0][g:g + bz, g:g + by, :]
    prev_out[...] = bufs[1][g:g + bz, g:g + by, :]


def fused_pass(spec: st.StencilSpec, state, arrays, scalars, t_block: int, *,
               bz: int = 16, by: int = 16):
    """Advance t_block steps in one fused kernel pass: state -> state."""
    cur, prev = state
    r = spec.radius
    g = r * t_block
    nz, ny, nx = cur.shape
    nzp = -(-nz // bz) * bz
    nyp = -(-ny // by) * by
    pads = ((g, g + nzp - nz), (g, g + nyp - ny), (g, g))

    def pad(a):
        return jnp.pad(a, pads, mode="edge")

    nxp = nx + 2 * g
    win = (bz + 2 * g, by + 2 * g, nxp)
    inputs = [pad(cur)]
    win_shapes = [win]
    if spec.time_order == 2:
        inputs.append(pad(prev))
        win_shapes.append(win)
    if spec.n_coeff_arrays:
        inputs.append(jnp.pad(arrays, ((0, 0),) + pads, mode="edge"))
        win_shapes.append((spec.n_coeff_arrays,) + win)
    if spec.time_order != 2:
        win_shapes.append(win)                              # ping-pong buf

    kern = functools.partial(_kernel, spec, t_block, bz, by,
                             (nz, ny, nx), len(inputs), scalars)
    out_sds = jax.ShapeDtypeStruct((nzp, nyp, nxp), cur.dtype)
    blk = pl.BlockSpec((bz, by, nxp), lambda i, j: (i, j, 0))
    cur_o, prev_o = pl.pallas_call(
        kern,
        grid=(nzp // bz, nyp // by),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(inputs),
        out_specs=(blk, blk),
        out_shape=(out_sds, out_sds),
        scratch_shapes=[pltpu.VMEM(s, cur.dtype) for s in win_shapes]
        + [pltpu.VMEM(win, cur.dtype), pltpu.SemaphoreType.DMA],
        interpret=config.interpret(),
    )(*inputs)

    # splice: out (z,y) index == original index; x carries the g-pad offset
    sl_int = (slice(r, nz - r), slice(r, ny - r), slice(g + r, g + nx - r))
    new_cur = cur.at[r:-r, r:-r, r:-r].set(cur_o[sl_int])
    new_prev = cur.at[r:-r, r:-r, r:-r].set(prev_o[sl_int])
    return (new_cur, new_prev)


def run_fused(spec: st.StencilSpec, state, arrays, scalars, n_steps: int,
              t_block: int = 4, *, bz: int = 16, by: int = 16):
    """Advance n_steps in fused T_b-step ghost-zone passes (last may be short)."""
    done = 0
    while done < n_steps:
        tb = min(t_block, n_steps - done)
        state = fused_pass(spec, state, arrays, scalars, tb, bz=bz, by=by)
        done += tb
    return state
