"""MWD kernel: multi-threaded wavefront diamond blocking, TPU-native.

The paper's core technique (Sec. 4) as ONE Pallas launch for the whole
space-time schedule (the per-row launch mode is kept for comparison):

  grid = (diamond row, tile k, wavefront step j)   # sequential on TPU
  * the diamond tessellation is precompiled by core.tiling.compile_schedule
    into dense scalar-prefetch tables: per-(row, tile) window offsets,
    per-tau y-ranges, per-row buffer parity, and an active mask;
  * the two time-parity grids live in HBM for the whole launch — the kernel
    reads AND writes them through its (input-aliased) output refs, so no
    padded grid is ever materialized between diamond rows;
  * persistent VMEM scratch holds the live z-window of both parity buffers
    (+ coefficient streams) for one extruded diamond tile; every step j
    shifts the window down N_F z-rows ("pipelined" wavefront, Fig. 6c) and
    DMAs the next slab of every stream HBM->VMEM;
  * T = D_w/R in-tile time-step updates run at static z-offsets, each masked
    to the diamond's y-range at that local time (diamonds via masking:
    rectangular VMEM blocks, non-rectangular iteration space — see DESIGN.md);
  * one completed slab per parity DMAs back to HBM per step.

In-place safety: tiles of one row touch a same-row neighbor's cells only in
the R-wide interface margin, and only ever read the parity level that the
neighbor's single update of those cells does not overwrite (DESIGN.md,
"why row-major is a legal order"), so the row-major single launch is exact.

Intra-tile parallelization: x is the full-width lane dimension (never tiled,
paper's leading-dimension rule); y/z vectorize across sublanes. Each stream
crosses HBM once per D_w/(2R) time steps, in windows aligned to the
(sublane, lane) tile (`core.models.MWDWindow`), so every DMA is one Mosaic
accepts: y windows start and end on sublane-tile rows and cover the owned
rows plus one tile either side, and x is padded to whole lanes. x shifts
are lane rotations (`pltpu.roll`); y and z shifts are static offsets into
the VMEM windows. The fused launch skips the inactive edge tiles that the
per-row mode streams (repro/core/traffic.py counts both).

Geometry (see DESIGN.md): update tau processes padded z-rows
[N_F*j - (tau+1)R, N_F*(j+1) - (tau+1)R), i.e. buffer rows
[R*(T-tau), R*(T-tau)+N_F); final-level rows leave through buffer rows
[R, R+N_F) once j >= D_w/N_F.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ir, models
from repro.core import stencils as st
from repro.core import tiling
from repro.kernels import config


def sync_dirichlet_frame(cur, prev, r: int):
    """Copy cur's boundary frame into prev (all levels share the frame).

    Operates on the trailing (z, y, x) axes, so a leading batch axis — the
    batched serving path stacks B independent grids — passes through.
    The copies run under the scope ``mwd.frame_sync``.
    """
    with jax.named_scope("mwd.frame_sync"):
        for ax in range(3):
            lo = (...,) + tuple(slice(None) if a != ax else slice(0, r)
                                for a in range(3))
            hi = (...,) + tuple(slice(None) if a != ax else slice(-r, None)
                                for a in range(3))
            prev = prev.at[lo].set(cur[lo]).at[hi].set(cur[hi])
    return prev


def _mwd_kernel(spec: st.StencilSpec, d_w: int, n_f: int, scalars,
                n_in: int, fused: bool, batched: bool, acc_dtype,
                win: models.MWDWindow, n_tiles: int, *refs):
    """One (row, tile, j) grid step of the MWD schedule.

    refs = (bounds, p0s, ys, y0s, y1s, active,      # scalar prefetch, flat
            buf_e_in, buf_o_in, [coeff_in],         # HBM inputs
            buf_e, buf_o,                           # HBM outputs, aliased
                                                    #  to the parity inputs
            win_e, win_o, [coeff_win], sem, osem)   # VMEM scratch + DMA sems

    The (row, tile[, tau]) tables are flattened row-major: SMEM pads the
    minor axis of a multi-axis table, which would overflow it at 25-point
    step counts.

    The parity grids are read and written through the aliased output refs,
    so every tile sees the in-place writes of the tiles before it.
    fused=True skips the inactive edge tiles; fused=False streams every
    tile of its row (the legacy per-row pass).

    batched=True prepends a batch grid axis: grid (batch, row, tile, j), the
    HBM parity grids and coefficient stream carry a leading B axis, and every
    HBM-side DMA indexes the current batch entry. The VMEM window scratch is
    batch-free — the grid is sequential, so one live window serves every
    entry — and per-entry dataflow is identical to the B=1 kernel, which is
    what makes the batched launch bitwise-equal to a per-item loop.

    acc_dtype decouples the accumulator from the stream dtype: every HBM
    grid, VMEM window and DMA slab stays in the stream dtype (the bytes
    Eq. 5 counts — halving the word halves the code balance), while the T
    in-tile updates cast the operands they read up to `acc_dtype` and the
    result back down before the masked write. None accumulates natively in
    the stream dtype (the pre-dtype behavior, bitwise-preserving for f32
    problems).

    Layout (`models.MWDWindow`): the window holds padded y rows
    [ys, ys + wy) with ys a multiple of the sublane tile s; the tile's
    owned rows lie inside window rows [s, s + span), which the updates
    write and the emission copies out. The rows of the span the tile does
    not own are copied back unchanged: they hold what HBM held when the
    window loaded them, and no other tile runs in between.

    Each phase of an active grid step runs under a `jax.named_scope`, which
    Mosaic lowers to a profiler region (``tpu.trace_start``/``trace_stop``):
    ``mwd.shift``, ``mwd.fetch``, ``mwd.update`` and, on the steps that
    emit, ``mwd.emit``. A TPU profile shows them (line ``XLA TraceMe``)
    when libtpu runs with ``--xla_enable_custom_call_region_trace=true``.
    """
    bounds_ref, p0_ref, ys_ref, y0_ref, y1_ref, act_ref = refs[:6]
    inputs = refs[6:6 + n_in]
    out_e, out_o = refs[6 + n_in:8 + n_in]
    sem, osem = refs[-2], refs[-1]
    bufs = list(refs[8 + n_in:-2])

    r = spec.radius
    t_steps = d_w // r                  # T = 2H updates per tile
    s, span, wy, z_ws = win.s, win.span, win.wy, win.z_ws
    nb = 1 if batched else 0
    row, k, j = (pl.program_id(nb), pl.program_id(nb + 1),
                 pl.program_id(nb + 2))
    bsel = (pl.program_id(0),) if batched else ()
    tile = row * n_tiles + k
    ys = pl.multiple_of(ys_ref[tile], s)
    srcs = [out_e, out_o] + list(inputs[2:])

    def update_phase():
        """The T masked in-tile updates, with their iota and mask set-up."""
        coeff_buf = bufs[2] if spec.n_coeff_arrays else None
        nxp = win.nxp
        shape = (n_f, span, nxp)
        y_io = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + (ys + s)
        x_io = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        z_loc = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        # Dirichlet / shard-interior bounds, dynamic (padded coordinates)
        lo_z, hi_z = bounds_ref[0], bounds_ref[1]
        lo_y, hi_y = bounds_ref[2], bounds_ref[3]
        lo_x, hi_x = bounds_ref[4], bounds_ref[5]
        xy_mask = ((x_io >= lo_x) & (x_io < hi_x)
                   & (y_io >= lo_y) & (y_io < hi_y))

        def cast(v):
            return v if acc_dtype is None else v.astype(acc_dtype)

        # --- T in-tile updates at static buffer offsets -------------------
        def updates(p0: int):
            for tau in range(t_steps):
                zb = r * (t_steps - tau)    # buffer row of the N_F targets
                p = (p0 + tau) % 2
                src_b, dst_b = bufs[p], bufs[1 - p]

                def tap(off, src_b=src_b, zb=zb):
                    dz, dy, dx = off
                    v = cast(src_b[zb + dz:zb + dz + n_f,
                                   s + dy:s + dy + span])
                    # x is never sliced at an offset: a lane rotation reads
                    # x + dx, wrapping only within R of the lane edges,
                    # which hold no interior cell
                    return pltpu.roll(v, (-dx) % nxp, 2) if dx else v

                def coeff(c, zb=zb):
                    if c.kind == "const":
                        return scalars[c.index]
                    return cast(coeff_buf[c.index, zb:zb + n_f, s:s + span])

                old = dst_b[zb:zb + n_f, s:s + span]
                new = ir.update(spec, tap, coeff, lambda old=old: cast(old))
                if acc_dtype is not None:
                    new = new.astype(dst_b.dtype)

                y0 = y0_ref[tile * t_steps + tau]
                y1 = y1_ref[tile * t_steps + tau]
                z_io = z_loc + (j * n_f - (tau + 1) * r)  # padded z coord
                mask = ((y_io >= y0) & (y_io < y1)
                        & (z_io >= lo_z) & (z_io < hi_z) & xy_mask)
                dst_b[zb:zb + n_f, s:s + span] = jnp.where(mask, new, old)

        # buffer parity of the row's first time level is a prefetched scalar;
        # refs cannot be selected dynamically, so branch on it statically
        for p0 in (0, 1):
            @pl.when(p0_ref[row] == p0)
            def _upd(p0=p0):
                updates(p0)

    def tile_step():
        @pl.when(j == 0)
        def _init():
            for b in bufs:
                b[...] = jnp.zeros_like(b)

        # --- shift the wavefront window down by N_F, stream next slabs in --
        with jax.named_scope("mwd.shift"):
            for b in bufs:
                if len(b.shape) == 3:
                    b[0:z_ws - n_f] = b[n_f:z_ws]
                else:
                    b[:, 0:z_ws - n_f] = b[:, n_f:z_ws]
        with jax.named_scope("mwd.fetch"):
            for src, dst in zip(srcs, bufs):
                if len(dst.shape) == 3:   # solution window (scratch is 3-D)
                    idx = bsel + (pl.ds(j * n_f, n_f), pl.ds(ys, wy))
                    didx = (pl.ds(z_ws - n_f, n_f),)
                else:                     # stacked coefficient window
                    idx = bsel + (slice(None), pl.ds(j * n_f, n_f),
                                  pl.ds(ys, wy))
                    didx = (slice(None), pl.ds(z_ws - n_f, n_f))
                cp = pltpu.make_async_copy(src.at[idx], dst.at[didx], sem)
                cp.start()
                cp.wait()
        with jax.named_scope("mwd.update"):
            update_phase()

        # --- emit the completed slab (both parities) ----------------------
        @pl.when(j >= d_w // n_f)
        def _out():
            with jax.named_scope("mwd.emit"):
                zs = j * n_f - d_w
                for out, b in ((out_e, bufs[0]), (out_o, bufs[1])):
                    cp = pltpu.make_async_copy(
                        b.at[pl.ds(r, n_f), pl.ds(s, span)],
                        out.at[bsel + (pl.ds(zs, n_f), pl.ds(ys + s, span))],
                        osem)
                    cp.start()
                    cp.wait()

    if fused:
        # inactive edge tiles own no spans: skip their streams entirely
        @pl.when(act_ref[tile] == 1)
        def _active_tile():
            tile_step()
    else:
        tile_step()


def mwd_run(spec: st.StencilSpec, state, arrays, scalars, n_steps: int, *,
            d_w: int = 8, n_f: int = 2, fused: bool = True,
            interior=None, y_domain: tuple[int, int] | None = None,
            acc_dtype=None):
    """Advance n_steps with the MWD schedule: state -> state.

    `arrays` is the op's stacked (A, z, y, x) coefficient stream (or None);
    `scalars` the compile-time scalar tuple the kernel inlines (static).

    fused=True (default) executes the whole compiled schedule in ONE
    pallas_call with the parity grids aliased in place; fused=False launches
    one pass per diamond row, streaming every tile (the legacy mode, kept
    as the auto-tuner's comparison point).

    interior: optional (6,) int32 [lo_z, hi_z, lo_y, hi_y, lo_x, hi_x] in
    block coordinates — cells outside are held (Dirichlet / shard frame).
    May be a traced array (the distributed stepper passes per-shard bounds).
    Defaults to the R-deep frame of the block. x reads are lane rotations,
    so [lo_x, hi_x) must stay R cells inside the block's x extent.

    y_domain: (y_lo, y_hi) diamond tessellation extent; defaults to the
    interior [R, ny-R). The distributed stepper passes (0, ny) so halo cells
    advance intermediate levels too.

    acc_dtype: optional accumulator dtype for the in-tile updates (see
    `_mwd_kernel`); None accumulates natively in the stream dtype.
    """
    return _mwd_run_impl(spec, state, arrays, scalars, n_steps, d_w=d_w,
                         n_f=n_f, fused=fused, interior=interior,
                         y_domain=y_domain, batched=False,
                         acc_dtype=acc_dtype)


def mwd_run_batched(spec: st.StencilSpec, state, arrays, scalars,
                    n_steps: int, *, d_w: int = 8, n_f: int = 2,
                    fused: bool = True, acc_dtype=None):
    """Advance B independent same-shaped grids in ONE launch: state -> state.

    `state` is (cur, prev) with a leading batch axis ``(B, nz, ny, nx)``;
    `arrays` is the stacked coefficient stream with a leading batch axis
    ``(B, A, nz, ny, nx)`` (or None); `scalars` is ONE static scalar tuple
    shared by every entry (the kernel inlines scalars as compile-time
    constants, so a serving bucket must share them — the queue keys on the
    op fingerprint + scalars to guarantee it).

    The launch extends the compiled-schedule grid to (batch, row, tile, j)
    with the batch axis outermost: entry b runs the exact B=1 instruction
    sequence before entry b+1 starts, so the result is bitwise-equal to a
    per-item `mwd_run` loop while paying ONE dispatch + one jit trace for
    the whole batch.
    """
    cur = state[0]
    if cur.ndim != 4:
        raise ValueError(f"mwd_run_batched wants (B, nz, ny, nx) states, "
                         f"got shape {cur.shape}")
    return _mwd_run_impl(spec, state, arrays, scalars, n_steps, d_w=d_w,
                         n_f=n_f, fused=fused, interior=None, y_domain=None,
                         batched=True, acc_dtype=acc_dtype)


def _mwd_run_impl(spec: st.StencilSpec, state, arrays, scalars, n_steps: int,
                  *, d_w: int, n_f: int, fused: bool, interior, y_domain,
                  batched: bool, acc_dtype=None):
    if acc_dtype is not None:
        acc_dtype = jnp.dtype(acc_dtype)
        if acc_dtype == state[0].dtype:   # native accumulation: no casts
            acc_dtype = None
    r = spec.radius
    if d_w % (2 * r) or d_w % n_f:
        raise ValueError(f"need 2R | d_w and n_f | d_w (d_w={d_w}, R={r}, "
                         f"n_f={n_f})")
    cur, prev = state
    prev = sync_dirichlet_frame(cur, prev, r)
    nz, ny, nx = cur.shape[-3:]
    lead = cur.shape[:-3]                # (B,) when batched, () otherwise
    word = jnp.dtype(cur.dtype).itemsize
    win = models.mwd_window(r, d_w, n_f, nx, word)
    s, nxp = win.s, win.nxp

    y_lo, y_hi = y_domain if y_domain is not None else (r, ny - r)
    comp = tiling.compile_schedule(
        tiling.make_diamond_schedule(d_w, r, n_steps, y_lo, y_hi))
    if comp.n_rows == 0:                 # n_steps == 0: nothing to launch
        return cur, prev

    # y padding: every window starts at a non-negative multiple of s, and
    # (y_lo + py) % s == 0 keeps each tile's owned rows within its span
    own = comp.w0 + r                    # first owned row, domain coords
    py = s - int(own.min())
    py += (-(y_lo + py)) % s
    ys = (own + py) // s * s - s         # aligned window starts
    assert (own + py - ys - s + d_w).max() <= win.span, "span misses rows"
    nyp = -(-max(int(ys.max()) + win.wy, py + ny) // s) * s
    pz = r
    n_j = -(-(pz + nz + d_w) // n_f)
    nz_tot = n_j * n_f
    # x is not offset: lane rotations read the x halo, so the only x
    # padding rounds the lanes up to whole tiles
    pads = ((pz, nz_tot - nz - pz), (py, nyp - ny - py), (0, nxp - nx))

    def pad(a):
        with jax.named_scope("mwd.pad"):
            return jnp.pad(a, ((0, 0),) * (a.ndim - 3) + pads, mode="edge")

    bufs = [pad(cur), pad(prev)]         # parity 0 (even), parity 1 (odd)
    wshape = (win.z_ws, win.wy, nxp)
    scratch = [pltpu.VMEM(wshape, cur.dtype), pltpu.VMEM(wshape, cur.dtype)]
    coeff_in = []
    if spec.n_coeff_arrays:
        coeff_in = [pad(arrays)]
        scratch.append(pltpu.VMEM((spec.n_coeff_arrays,) + wshape,
                                  cur.dtype))
    scalars = tuple(float(x) for x in scalars)
    scratch += [pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]

    if interior is None:
        interior = jnp.asarray([r, nz - r, r, ny - r, r, nx - r], jnp.int32)
    bounds = (jnp.asarray(interior, jnp.int32)
              + jnp.asarray([pz, pz, py, py, 0, 0], jnp.int32))
    p0s = jnp.asarray(comp.parity, jnp.int32)
    ysp = jnp.asarray(ys.ravel(), jnp.int32)
    y0p = jnp.asarray((comp.y0 + py).ravel(), jnp.int32)
    y1p = jnp.asarray((comp.y1 + py).ravel(), jnp.int32)
    act = jnp.asarray(comp.active.ravel(), jnp.int32)
    nt, tt = comp.n_tiles, comp.n_tiles * comp.t_steps

    out_sds = jax.ShapeDtypeStruct(lead + (nz_tot, nyp, nxp), cur.dtype)
    n_in = 2 + len(coeff_in)
    vmem = models.mwd_vmem_bytes(spec, d_w, n_f, nx, word)

    def launch(fused_mode, tables, n_rows, bufs_in):
        kern = functools.partial(_mwd_kernel, spec, d_w, n_f, scalars,
                                 n_in, fused_mode, batched, acc_dtype, win,
                                 comp.n_tiles)
        # parity grids aliased in place: inputs 6/7 after the six
        # scalar-prefetch tables -> outputs 0/1
        return pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=6,
                grid=lead + (n_rows, comp.n_tiles, n_j),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_in,
                out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
                scratch_shapes=scratch,
            ),
            out_shape=(out_sds, out_sds),
            input_output_aliases={6: 0, 7: 1},
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
            interpret=config.interpret(),
            name=f"mwd_{spec.name}",
        )(*tables, *bufs_in, *coeff_in)

    if fused:
        bufs = list(launch(True, (bounds, p0s, ysp, y0p, y1p, act),
                           comp.n_rows, bufs))
    else:
        for i in range(comp.n_rows):
            tables = (bounds, p0s[i:i + 1], ysp[i * nt:(i + 1) * nt],
                      y0p[i * tt:(i + 1) * tt], y1p[i * tt:(i + 1) * tt],
                      act[i * nt:(i + 1) * nt])
            bufs = list(launch(False, tables, 1, bufs))

    core = (..., slice(pz, pz + nz), slice(py, py + ny), slice(0, nx))
    p = n_steps % 2
    with jax.named_scope("mwd.crop"):
        return bufs[p][core], bufs[1 - p][core]
