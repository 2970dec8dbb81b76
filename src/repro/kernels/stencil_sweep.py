"""Spatially-blocked single-sweep stencil kernel (the paper's baseline).

One time step over the grid, z-slab blocked: each grid step manually DMAs an
overlapping (Bz + 2R) z-window of the (y,x)-padded arrays HBM->VMEM, applies
the stencil on the VMEM window (the sweep *generated* from the operator's IR
is the in-VMEM compute), and emits a Bz-thick output slab.  x is full-width
lanes (never tiled — paper Sec. 4.1); y is kept whole here (the slab
thickness Bz bounds the VMEM footprint).

The streamed inputs are fully IR-derived: the current level, the previous
level iff `spec.time_order == 2`, and one stacked (A, ...) coefficient
stream iff the op has array coefficients — no per-stencil branches.

This realizes "optimal spatial blocking": code balance = word*(N_D+1) B/LUP.
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


def _kernel(spec: st.StencilSpec, bz: int, n_in: int, scalars, *refs):
    """refs = (*inputs_hbm, out_ref, *windows_vmem, sem)."""
    inputs = refs[:n_in]
    out_ref = refs[n_in]
    wins = refs[n_in + 1:-1]
    sem = refs[-1]
    r = spec.radius
    i = pl.program_id(0)

    # DMA the overlapping window of every stream (z window rows
    # [i*bz, i*bz + bz + 2R) in padded coords).
    for src, dst in zip(inputs, wins):
        if len(src.shape) == 3:
            cp = pltpu.make_async_copy(src.at[pl.ds(i * bz, bz + 2 * r)], dst, sem)
        else:  # stacked coefficient streams (k, z, y, x)
            cp = pltpu.make_async_copy(
                src.at[:, pl.ds(i * bz, bz + 2 * r)], dst, sem)
        cp.start()
        cp.wait()

    w_cur = wins[0][...]
    k = 1
    w_prev = w_cur
    if spec.time_order == 2:
        w_prev = wins[k][...]
        k += 1
    w_arr = wins[k][...] if spec.n_coeff_arrays else None
    new = ir.make_sweep(spec)(w_cur, w_prev, w_arr, scalars)
    out_ref[...] = new[r:r + bz]


def sweep_step(spec: st.StencilSpec, state, arrays, scalars, *, bz: int = 8):
    """One interior-update time step via the Pallas kernel: state -> state."""
    cur, prev = state
    r = spec.radius
    nz, ny, nx = cur.shape
    nzp = -(-nz // bz) * bz  # round z up to slab multiple
    pads = ((r, r + nzp - nz), (r, r), (r, r))

    def pad(a):
        return jnp.pad(a, pads, mode="edge")

    cur_p = pad(cur)
    nyp, nxp = ny + 2 * r, nx + 2 * r
    win = (bz + 2 * r, nyp, nxp)
    inputs = [cur_p]
    win_shapes = [win]
    if spec.time_order == 2:
        inputs.append(pad(prev))
        win_shapes.append(win)
    if spec.n_coeff_arrays:
        inputs.append(jnp.pad(arrays, ((0, 0),) + pads, mode="edge"))
        win_shapes.append((spec.n_coeff_arrays,) + win)

    kern = functools.partial(_kernel, spec, bz, len(inputs), scalars)
    out = pl.pallas_call(
        kern,
        grid=(nzp // bz,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(inputs),
        out_specs=pl.BlockSpec((bz, nyp, nxp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nzp, nyp, nxp), cur.dtype),
        scratch_shapes=[pltpu.VMEM(s, cur.dtype) for s in win_shapes]
        + [pltpu.SemaphoreType.DMA],
        interpret=config.interpret(),
    )(*inputs)
    # splice the computed interior back into the Dirichlet frame:
    # out index == original z index; y/x are padded-coordinate (+r) offsets
    new = cur.at[r:-r, r:-r, r:-r].set(out[r:nz - r, 2 * r:ny, 2 * r:nx])
    return (new, cur)


def run_sweep(spec: st.StencilSpec, state, arrays, scalars, n_steps: int, *,
              bz: int = 8):
    """Advance n_steps as independent z-blocked single-sweep kernel passes."""
    for _ in range(n_steps):
        state = sweep_step(spec, state, arrays, scalars, bz=bz)
    return state
