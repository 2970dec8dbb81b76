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
    (+ coefficient streams) for one extruded diamond tile as a ring of z_ws
    rows; every step j advances the ring by N_F z-rows ("pipelined"
    wavefront, Fig. 6c) without moving any data, and DMAs the next slab of
    every stream HBM->VMEM into the rows that fell off the window's top;
  * T = D_w/R in-tile time-step updates run at static logical z-rows. Each
    computes only the sublane tiles that cover the diamond's y-range at
    that local time, masked to the range, and is skipped where the range
    is empty or its slab lies outside the interior (diamonds via masking:
    rectangular VMEM blocks, non-rectangular iteration space — see
    DESIGN.md; `update_work` counts the rows);
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
the VMEM windows (in z, logical rows of the ring). The fused launch skips
the inactive edge tiles that the per-row mode streams
(repro/core/traffic.py counts both).

Geometry (see DESIGN.md), in logical window rows: update tau processes
padded z-rows [N_F*j - (tau+1)R, N_F*(j+1) - (tau+1)R), i.e. window rows
[R*(T-tau), R*(T-tau)+N_F); final-level rows leave through window rows
[R, R+N_F) once j >= D_w/N_F. At step j logical row q lives in physical
row (N_F*j + q) mod z_ws, so what step j writes at row q + N_F step j+1
reads at row q where it lies. The relabelling keeps every read and write,
and their order, of a window that moved: the in-place argument above holds
unchanged.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ir, models
from repro.core import stencils as st
from repro.core import tiling
from repro.core.precision import DEFAULT_WORD_BYTES
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


@dataclasses.dataclass(frozen=True)
class LaunchGeometry:
    """Static geometry of one MWD launch (see `launch_geometry`)."""

    win: models.MWDWindow
    comp: tiling.CompiledSchedule
    py: int                  # y padding before the grid's first row
    pz: int                  # z padding before the grid's first plane
    nyp: int                 # padded y extent
    n_j: int                 # wavefront steps per tile
    ys: np.ndarray           # (n_rows, n_tiles) window starts, padded y
    level_rows: tuple[int, ...]   # per tau: rows its update computes


@functools.lru_cache(maxsize=256)
def launch_geometry(r: int, d_w: int, n_f: int, shape: tuple[int, int, int],
                    word: int, n_steps: int,
                    y_domain: tuple[int, int] | None = None
                    ) -> LaunchGeometry | None:
    """The compiled schedule, padding and window placement of one launch.

    `shape` is the (nz, ny, nx) grid; None when n_steps is 0 (no launch).

    level_rows[tau] is the sublane-aligned number of window rows the
    update of in-tile level tau computes: the widest aligned cover of
    that level's y-range over every tile of the schedule, and 0 where no
    tile has rows at that level (the kernel emits no code for it). A
    tile's level then covers the rows [a, a + level_rows[tau]) of its
    window, a = its range start floored to the sublane tile and clamped
    into the span.
    """
    nz, ny, nx = shape
    win = models.mwd_window(r, d_w, n_f, nx, word)
    s = win.s
    y_lo, y_hi = y_domain if y_domain is not None else (r, ny - r)
    comp = tiling.compile_schedule(
        tiling.make_diamond_schedule(d_w, r, n_steps, y_lo, y_hi))
    if comp.n_rows == 0:
        return None

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

    # aligned cover of each (tile, tau) range, in window rows
    y0 = comp.y0 + py - ys[..., None]
    y1 = comp.y1 + py - ys[..., None]
    cover = np.where(comp.y1 > comp.y0, -(-y1 // s) * s - y0 // s * s, 0)
    level_rows = tuple(int(c) for c in cover.max(axis=(0, 1)))
    assert max(level_rows) <= win.span, "a level leaves the span"
    ys.setflags(write=False)
    return LaunchGeometry(win=win, comp=comp, py=py, pz=pz, nyp=nyp,
                          n_j=n_j, ys=ys, level_rows=level_rows)


@dataclasses.dataclass(frozen=True)
class UpdateWork:
    """Window rows the in-tile updates cover, per (row, tile, tau).

    Counted over the fused launch's active tiles, per wavefront step
    (every step of a tile runs the same updates): `computed` is what the
    kernel computes (level_rows[tau] where the tile's level is not empty),
    `full_span` what an update over the whole span at every level computed,
    `useful` the rows of the diamond's range. The kernel also skips the
    levels whose slab lies outside the interior, at the wavefront's head
    and tail; these counts leave that out.
    """

    computed: np.ndarray
    full_span: np.ndarray
    useful: np.ndarray

    def shares(self) -> dict[str, float]:
        """Non-empty levels and computed and useful rows, over full_span."""
        full = float(self.full_span.sum())
        return {"non_empty": float((self.useful > 0).sum()
                                   / (self.full_span > 0).sum()),
                "computed": float(self.computed.sum()) / full,
                "useful": float(self.useful.sum()) / full}


@functools.lru_cache(maxsize=64)
def update_work(spec: st.StencilSpec, shape: tuple[int, int, int],
                n_steps: int, d_w: int, n_f: int, *,
                y_domain: tuple[int, int] | None = None,
                word_bytes: int = DEFAULT_WORD_BYTES) -> UpdateWork | None:
    """The in-tile update work of `mwd_run` on a (nz, ny, nx) grid.

    Read from the same tables the launch runs (`launch_geometry`); None
    when n_steps is 0. Memoized.
    """
    geo = launch_geometry(spec.radius, d_w, n_f, shape, word_bytes, n_steps,
                          y_domain)
    if geo is None:
        return None
    comp = geo.comp
    act = comp.active.astype(bool)[..., None]
    useful = np.where(act, comp.y1 - comp.y0, 0)
    computed = np.where(useful > 0, np.asarray(geo.level_rows), 0)
    full_span = np.where(np.broadcast_to(act, useful.shape), geo.win.span, 0)
    for a in (computed, full_span, useful):
        a.setflags(write=False)
    return UpdateWork(computed=computed, full_span=full_span, useful=useful)


def _mwd_kernel(spec: st.StencilSpec, d_w: int, n_f: int, scalars,
                n_in: int, fused: bool, batched: bool, acc_dtype,
                win: models.MWDWindow, n_tiles: int,
                level_rows: tuple[int, ...], *refs):
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

    The windows are rings of z_ws rows (module docstring, "Geometry"):
    every access names logical window rows, and `ring` maps each plane to
    its physical row at this step.

    Each phase of an active grid step runs under a `jax.named_scope`, which
    Mosaic lowers to a profiler region (``tpu.trace_start``/``trace_stop``):
    ``mwd.shift`` (the ring's advance: the step's base row and fetch
    rows), ``mwd.fetch``, ``mwd.update`` and, on the steps that emit,
    ``mwd.emit``. A TPU profile shows them (line ``XLA TraceMe``) when
    libtpu runs with ``--xla_enable_custom_call_region_trace=true``.
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
    # Ring arithmetic on traced scalars uses lax directly: an operator on a
    # traced scalar traces a jitted function, and these run for every level.
    lax = jax.lax

    def ring(base, q: int):
        """Physical row of logical window row q, the step's `base` given."""
        t = lax.add(base, np.int32(q))          # base + q < 2 z_ws
        return lax.select(lax.ge(t, np.int32(z_ws)),
                          lax.sub(t, np.int32(z_ws)), t)

    def copy(pairs, dma_sem):
        """DMA each (src, dst) pair: start them all, then wait for each."""
        cps = [pltpu.make_async_copy(a, b, dma_sem) for a, b in pairs]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    def update_phase(base, jz):
        """The in-tile updates of the levels with rows, and their masks.

        Level tau computes the `level_rows[tau]` window rows from the
        aligned row `a` on, and only when its y-range and its z-slab meet
        the interior. Rows it does not compute keep `old`, which is what a
        masked write over the whole span would have stored there, so the
        result is unchanged.
        """
        nxp = win.nxp
        # Dirichlet / shard-interior bounds, dynamic (padded coordinates)
        lo_z, hi_z = bounds_ref[0], bounds_ref[1]
        lo_y, hi_y = bounds_ref[2], bounds_ref[3]
        lo_x, hi_x = bounds_ref[4], bounds_ref[5]

        def cast(v):
            return v if acc_dtype is None else v.astype(acc_dtype)

        def level(tau: int, rows: int, p: int, a, y_lo, y_hi, z_lo, z_hi):
            zb = r * (t_steps - tau)        # window row of the N_F targets
            src_b, dst_b = bufs[p], bufs[1 - p]
            phys = {}                       # logical row -> physical row
            halo = {}                       # dz -> rows [a - s, a + rows + s)

            def slab(b, c, y, n, lead=()):
                """Logical rows [c, c + N_F) and y rows [y, y + n) of window
                b[lead]: one plane per physical row, joined along z."""
                for q in range(c, c + n_f):
                    if q not in phys:
                        phys[q] = ring(base, q)
                return jnp.concatenate(
                    [b[lead + (pl.ds(phys[q], 1), pl.ds(y, n))]
                     for q in range(c, c + n_f)], axis=0)

            def tap(off):
                dz, dy, dx = off
                if dy:
                    # Mosaic loads dynamic sublane offsets only when
                    # aligned: load the aligned halo once per dz and shift
                    # by dy in registers
                    if dz not in halo:
                        halo[dz] = slab(src_b, zb + dz, a - s, rows + 2 * s)
                    v = jax.lax.slice_in_dim(halo[dz], s + dy, s + dy + rows,
                                             axis=1)
                else:
                    v = slab(src_b, zb + dz, a, rows)
                v = cast(v)
                # x is never sliced at an offset: a lane rotation reads
                # x + dx, wrapping only within R of the lane edges, which
                # hold no interior cell
                return pltpu.roll(v, (-dx) % nxp, 2) if dx else v

            def coeff(c):
                if c.kind == "const":
                    return scalars[c.index]
                return cast(slab(bufs[2], zb, a, rows, (c.index,)))

            old = slab(dst_b, zb, a, rows)
            new = ir.update(spec, tap, coeff, lambda: cast(old))
            if acc_dtype is not None:
                new = new.astype(old.dtype)

            def iota(axis):
                return jax.lax.broadcasted_iota(jnp.int32, old.shape, axis)

            y_i, x_i = iota(1), iota(2)
            mask = ((y_i >= y_lo) & (y_i < y_hi)
                    & (x_i >= lo_x) & (x_i < hi_x))
            if n_f > 1:                     # a one-row slab is live in z
                z_i = iota(0)
                mask &= (z_i >= z_lo) & (z_i < z_hi)
            res = jax.lax.select(mask, new, old)
            for i in range(n_f):
                dst_b[pl.ds(phys[zb + i], 1), pl.ds(a, rows)] = res[i:i + 1]

        # --- in-tile updates at static logical window rows ---------------
        first = tile * t_steps
        y_lo0, y_hi0 = lax.sub(lo_y, ys), lax.sub(hi_y, ys)
        z_lo0 = lax.sub(lo_z, np.int32(n_f))
        levels = []
        for tau, rows in enumerate(level_rows):
            if not rows:                    # no tile has rows at this level
                continue
            # the level's y-range in window rows and its slab's padded z;
            # a level with no rows or a slab outside the interior holds all
            at = lax.add(first, np.int32(tau))
            y0 = lax.sub(y0_ref[at], ys)
            y_lo = lax.max(y0, y_lo0)
            y_hi = lax.min(lax.sub(y1_ref[at], ys), y_hi0)
            z_at = lax.sub(jz, np.int32((tau + 1) * r))
            live = lax.bitwise_and(
                lax.lt(y_lo, y_hi),
                lax.bitwise_and(lax.lt(z_at, hi_z), lax.gt(z_at, z_lo0)))
            # the aligned rows [a, a + rows) of the window hold the range;
            # the mask is relative to row a and to the slab's first z
            a = pl.multiple_of(
                lax.min(lax.mul(lax.div(y0, np.int32(s)), np.int32(s)),
                        np.int32(s + span - rows)), s)
            levels.append((tau, rows, live, (
                a, lax.sub(y_lo, a), lax.sub(y_hi, a),
                lax.sub(lo_z, z_at), lax.sub(hi_z, z_at))))

        # buffer parity of the row's first time level is a prefetched scalar;
        # refs cannot be selected dynamically, so branch on it statically
        p_row = p0_ref[row]
        for p0 in (0, 1):
            @pl.when(lax.eq(p_row, np.int32(p0)))
            def _upd(p0=p0):
                for tau, rows, live, bounds in levels:
                    @pl.when(live)
                    def _level(tau=tau, rows=rows, bounds=bounds):
                        level(tau, rows, (p0 + tau) % 2, *bounds)

    def tile_step():
        @pl.when(j == 0)
        def _init():
            for b in bufs:
                b[...] = jnp.zeros_like(b)

        # --- advance the window ring by N_F, stream next slabs in --------
        with jax.named_scope("mwd.shift"):
            jz = lax.mul(j, np.int32(n_f))          # the slab's first plane
            base = lax.rem(jz, np.int32(z_ws))      # physical row of row 0
            # the rows that fell off the window's top take the new slab
            slots = [ring(base, q) for q in range(z_ws - n_f, z_ws)]
        with jax.named_scope("mwd.fetch"):
            # HBM plane jz + i of every stream lands in ring row slots[i]
            zsrc = [pl.ds(lax.add(jz, np.int32(i)), 1) for i in range(n_f)]
            for src, dst in zip(srcs, bufs):
                # the stacked coefficient window leads with its stream axis
                lead = () if len(dst.shape) == 3 else (slice(None),)
                copy([(src.at[bsel + lead + (z, pl.ds(ys, wy))],
                       dst.at[lead + (pl.ds(slot, 1),)])
                      for z, slot in zip(zsrc, slots)], sem)
        with jax.named_scope("mwd.update"):
            update_phase(base, jz)

        # --- emit the completed slab (both parities) ----------------------
        @pl.when(j >= d_w // n_f)
        def _out():
            with jax.named_scope("mwd.emit"):
                # ring row done[i] goes to HBM plane jz - d_w + i
                done = [ring(base, q) for q in range(r, r + n_f)]
                zdst = [pl.ds(lax.add(jz, np.int32(i - d_w)), 1)
                        for i in range(n_f)]
                for out, b in ((out_e, bufs[0]), (out_o, bufs[1])):
                    copy([(b.at[pl.ds(slot, 1), pl.ds(s, span)],
                           out.at[bsel + (z, pl.ds(ys + s, span))])
                          for z, slot in zip(zdst, done)], osem)

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
    geo = launch_geometry(r, d_w, n_f, (nz, ny, nx), word, n_steps,
                          None if y_domain is None else tuple(y_domain))
    if geo is None:                      # n_steps == 0: nothing to launch
        return cur, prev
    win, comp, py, pz, ys = geo.win, geo.comp, geo.py, geo.pz, geo.ys
    s, nxp, nyp, n_j = win.s, win.nxp, geo.nyp, geo.n_j
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
                                 comp.n_tiles, geo.level_rows)
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
