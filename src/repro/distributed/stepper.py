"""Distributed MWD time-stepper: the paper's MPI layer, ICI-native.

Domain decomposition (paper Sec. 4.2 / [Malas et al. 2015b]):
  z -> the data axes ('pod','data' flattened), y -> 'model', x never sharded.

Each super-step exchanges deep halos of depth g = R * t_block (one neighbor
exchange amortized over t_block local steps — communication-avoiding), then
advances t_block local steps. Two schedules exist per super-step:

  synchronous (overlap=False): exchange, then advance the whole extended
  block — communication sits on the critical path before any compute.

  overlapped (overlap=True): split each shard into an INTERIOR zone whose
  t_block advance reads only pre-exchange local data (its dataflow is
  independent of the ppermute pairs, so the XLA scheduler runs exchange and
  interior concurrently — the paper's Sec. 4.2 comm/compute overlap) and
  BOUNDARY zones of depth g per sharded axis that complete from the freshly
  landed double-buffered halos. Zone assembly is bitwise-equal to the
  synchronous answer (DESIGN.md §13 carries the correctness argument).

Locally the same computation is what the MWD/ghost-zone kernels realize per
device; the jnp path here is the portable executor the CPU tests validate
against single-device naive.

Elastic note: the stepper is a pure function of (mesh, spec, t_block); the
checkpointed state is mesh-agnostic (see distributed.checkpoint), so a resume
onto a different mesh just rebuilds the stepper (distributed.elastic drives
that protocol).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ir
from repro.core import stencils as st
from repro.core.mwd import MWDPlan
from repro.distributed import halo
from repro.kernels import stencil_mwd


@dataclasses.dataclass(frozen=True)
class GridSharding:
    """How the (z, y, x) stencil grid maps onto a mesh: z->data axes, y->model."""

    mesh: jax.sharding.Mesh

    @property
    def z_axes(self) -> tuple[str, ...]:
        """Mesh axes the grid's z dimension is sharded over (flattened)."""
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def y_axis(self) -> str:
        """Mesh axis the grid's y dimension is sharded over."""
        return "model"

    def counts(self) -> tuple[int, int]:
        """(n_z, n_y): shard counts along the grid's z and y dimensions."""
        n_z = 1
        for a in self.z_axes:
            n_z *= self.mesh.shape[a]
        return n_z, self.mesh.shape[self.y_axis]

    def spec(self, leading: int = 0) -> P:
        """PartitionSpec for a (..., z, y, x) array with `leading` extra dims."""
        return P(*((None,) * leading), self.z_axes, self.y_axis, None)

    def sharding(self, leading: int = 0) -> NamedSharding:
        """NamedSharding of `spec(leading)` on this mesh."""
        return NamedSharding(self.mesh, self.spec(leading))


# ---------------------------------------------------------------------------
# interior/boundary partition geometry (pure, static — unit-testable)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Zone:
    """One boundary zone of the overlapped super-step.

    `z`/`y` slice the halo-EXTENDED local block (extent + 2g on both axes);
    `kept` is the box of cells this zone contributes to the assembled output,
    in slab coordinates; `origin` is the LOCAL-grid coordinate of slab cell
    (0, 0) (add the shard's global offset for the Dirichlet-frame mask).
    """

    name: str
    z: slice
    y: slice
    kept: tuple[tuple[int, int], tuple[int, int]]
    origin: tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Partition:
    """Interior/boundary split of one local block for the overlapped step.

    The interior pass runs on the raw local block, padded by g only on axes
    that do NOT cross a shard boundary (x always; z/y when unsharded — the
    edge clamp is a local computation, so it costs no communication
    dependency). `interior_kept` / `interior_origin` follow the same
    conventions as `Zone.kept` / `Zone.origin` but in interior-block
    coordinates. Boundary `zones` exist only for sharded axes.
    """

    local_shape: tuple[int, int, int]
    g: int
    split_z: bool
    split_y: bool
    interior_kept: tuple[tuple[int, int], tuple[int, int]]
    interior_origin: tuple[int, int]
    zones: tuple[Zone, ...]


def partition_geometry(local_shape, g: int, split_z: bool,
                       split_y: bool) -> Partition:
    """Compute the interior/boundary split of one shard's local block.

    Sharded ("split") axes contribute two boundary zones of depth g each
    (slabs 3g thick: the kept g cells plus the g-deep support on either
    side); corners belong to the z zones, so the y zones keep only the z
    range the interior also keeps. Unsharded axes need no zones — their
    halo is an edge clamp the interior pass reproduces locally.
    """
    nz_l, ny_l, _ = local_shape
    nz_e, ny_e = nz_l + 2 * g, ny_l + 2 * g
    # kept range shared by the interior and the y zones (z) / interior (y),
    # in LOCAL coordinates
    kz = (g, nz_l - g) if split_z else (0, nz_l)
    ky = (g, ny_l - g) if split_y else (0, ny_l)
    zones = []
    if split_z:
        zones.append(Zone("z_lo", slice(0, 3 * g), slice(0, ny_e),
                          ((g, 2 * g), (g, g + ny_l)), (-g, -g)))
        zones.append(Zone("z_hi", slice(nz_e - 3 * g, nz_e), slice(0, ny_e),
                          ((g, 2 * g), (g, g + ny_l)), (nz_l - 2 * g, -g)))
    if split_y:
        zsl = slice(g, g + nz_l) if split_z else slice(0, nz_e)
        zo = 0 if split_z else -g
        zk = ((g, nz_l - g) if split_z else (g, g + nz_l))
        zones.append(Zone("y_lo", zsl, slice(0, 3 * g),
                          (zk, (g, 2 * g)), (zo, -g)))
        zones.append(Zone("y_hi", zsl, slice(ny_e - 3 * g, ny_e),
                          (zk, (g, 2 * g)), (zo, ny_l - 2 * g)))
    # interior-block coordinates: the block is padded by g on non-split axes
    ikz = kz if split_z else (g, g + nz_l)
    iky = ky if split_y else (g, g + ny_l)
    return Partition(tuple(local_shape), g, split_z, split_y,
                     (ikz, iky), (0 if split_z else -g, 0 if split_y else -g),
                     tuple(zones))


def overlap_work(local_shape, r: int, t_block: int, split_z: bool = True,
                 split_y: bool = True) -> dict:
    """Exact swept-cell counts per super-step: synchronous vs overlapped.

    The interior trapezoid over a kept box of extents (KZ, KY) computes
    (KZ + 2m)(KY + 2m)(nx + 2g - 2r) cells at sub-step t, m = r*(t_block-t)
    — the shrinking support of the kept cells. Each boundary zone sweeps its
    full 3g-thick slab every sub-step (`_advance_block`), the synchronous
    path the full extended block's interior. These counts feed
    `models.super_step_time`: interior compute is what the exchange hides.
    """
    nz_l, ny_l, nx_l = local_shape
    g = r * t_block
    x = nx_l + 2 * g - 2 * r
    sync = t_block * (nz_l + 2 * g - 2 * r) * (ny_l + 2 * g - 2 * r) * x

    def trap(kz, ky):
        return sum((kz + 2 * r * (t_block - t)) * (ky + 2 * r * (t_block - t))
                   for t in range(1, t_block + 1)) * x

    ikz = nz_l - 2 * g if split_z else nz_l
    iky = ny_l - 2 * g if split_y else ny_l
    interior = trap(ikz, iky)
    boundary = 0
    if split_z:
        boundary += 2 * t_block * (3 * g - 2 * r) * (ny_l + 2 * g - 2 * r) * x
    if split_y:
        yz = nz_l if split_z else nz_l + 2 * g
        boundary += 2 * t_block * (yz - 2 * r) * (3 * g - 2 * r) * x
    return {"sync_cells": sync, "interior_cells": interior,
            "boundary_cells": boundary}


def validate_super_step(spec: st.StencilSpec, mesh, grid_shape, t_block: int,
                        *, overlap: bool = False) -> None:
    """Check the decomposition geometry before tracing anything.

    Raises ValueError with an actionable message when the grid does not
    decompose evenly, when the deep-halo depth g = R * t_block exceeds a
    local shard extent (the single-hop exchange cannot source that), or —
    overlap=True — when the boundary zones would leave no halo-independent
    interior.
    """
    gs = GridSharding(mesh)
    n_z, n_y = gs.counts()
    nz, ny, _ = grid_shape
    if nz % n_z or ny % n_y:
        raise ValueError(
            f"grid {tuple(grid_shape)} does not decompose evenly over mesh "
            f"{dict(mesh.shape)}: z extent {nz} must divide by the {n_z} "
            f"z-shards and y extent {ny} by the {n_y} y-shards; pad the grid "
            f"or choose a mesh whose ('pod','data') x 'model' factors divide "
            f"(z, y)")
    r = spec.radius
    g = r * t_block
    nz_l, ny_l = nz // n_z, ny // n_y
    if g > nz_l or g > ny_l:
        raise ValueError(
            f"halo depth g = R*t_block = {r}*{t_block} = {g} exceeds the "
            f"local shard extent (nz_l={nz_l}, ny_l={ny_l}): the single-hop "
            f"deep-halo exchange can only source a neighbor's own cells. "
            f"Lower t_block to <= {min(nz_l, ny_l) // r} or use a coarser "
            f"decomposition.")
    if overlap:
        lims = ([nz_l] if n_z > 1 else []) + ([ny_l] if n_y > 1 else [])
        small = min(lims, default=None)
        if small is not None and small <= 2 * g:
            raise ValueError(
                f"interior/boundary overlap needs local shard extents "
                f"> 2g = {2 * g} on every sharded axis (got nz_l={nz_l}, "
                f"ny_l={ny_l}): boundary zones of depth g={g} would leave no "
                f"halo-independent interior. Use overlap=False or 'auto', "
                f"lower t_block to <= {max((small - 1) // (2 * r), 1)}, or "
                f"shard the grid more coarsely.")


def overlap_feasible(spec: st.StencilSpec, mesh, grid_shape,
                     t_block: int) -> bool:
    """True when the overlapped schedule is geometrically valid here."""
    try:
        validate_super_step(spec, mesh, grid_shape, t_block, overlap=True)
    except ValueError:
        return False
    return True


# ---------------------------------------------------------------------------
# local super-step bodies (run INSIDE shard_map)
# ---------------------------------------------------------------------------

def _extend_coeffs(spec: st.StencilSpec, t_block: int, gs: GridSharding,
                   coeffs):
    """One-time halo exchange + x-pad of the coefficients (inside shard_map).

    Coefficients travel in the canonical (stacked arrays, scalar vector)
    form for EVERY operator; they are time-invariant, so this exchange
    belongs at setup — `run_distributed` hoists it out of the super-step
    loop (exactly one coefficient ppermute set per run), and the overlapped
    schedule requires it (a per-step coefficient exchange would re-serialize
    the interior advance on the ppermute it is meant to hide).
    """
    arrays, svec = coeffs
    if not arrays.shape[0]:
        return (arrays, svec)
    g = spec.radius * t_block
    ext = halo.exchange_2d(arrays, g, axis_z=gs.z_axes, axis_y=gs.y_axis)
    return (jnp.pad(ext, [(0, 0)] * (ext.ndim - 1) + [(g, g)], mode="edge"),
            svec)


def _crop_hoisted(arrays_e, pad_g: int, g: int):
    """Crop pre-extended coefficients from their hoisted depth down to g.

    Lets a partial final super-step (t_block' < t_block, so g' < pad_g)
    reuse the coefficients extended once at setup instead of re-exchanging.
    """
    d = pad_g - g
    if d == 0:
        return arrays_e
    sl = slice(d, -d)
    return arrays_e[:, sl, sl, sl]


def _exchange_state(spec: st.StencilSpec, g: int, gs: GridSharding,
                    cur, prev, err):
    """Deep-halo exchange of the solution levels (inside shard_map).

    err=None runs the exact exchange; otherwise err is the per-stream
    error-feedback state ({"cur": faces[, "prev": faces]}) and the slabs
    ship int8-compressed (`halo.exchange_2d_compressed`). Coefficients
    always exchange exact — they are time-invariant, so compressing them
    would trade a one-time cost for a persistent bias.

    Returns (cur_e, prev_e, new_err).
    """
    zax, yax = gs.z_axes, gs.y_axis
    if err is None:
        cur_e = halo.exchange_2d(cur, g, axis_z=zax, axis_y=yax)
        prev_e = (halo.exchange_2d(prev, g, axis_z=zax, axis_y=yax)
                  if spec.time_order == 2 else cur_e)
        return cur_e, prev_e, None
    cur_e, e_cur = halo.exchange_2d_compressed(cur, g, err["cur"],
                                               axis_z=zax, axis_y=yax)
    if spec.time_order == 2:
        prev_e, e_prev = halo.exchange_2d_compressed(prev, g, err["prev"],
                                                     axis_z=zax, axis_y=yax)
        return cur_e, prev_e, {"cur": e_cur, "prev": e_prev}
    return cur_e, cur_e, {"cur": e_cur}


def _padx(a, g: int):
    """Edge-pad the trailing x axis by g (x is never sharded)."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(g, g)], mode="edge")


def _exchange_state_shared(spec: st.StencilSpec, g: int, gs: GridSharding,
                           cur, prev):
    """Exchange for the zone pipeline: extended block + shared interior core.

    Builds the x-padded local block FIRST, then concatenates halo slabs
    (edge clamps on unsharded axes, ppermute on sharded ones) around it.
    Pad-of-concat equals concat-of-pads — the values match
    `_exchange_state` + `_padx` exactly — but structurally the
    collective-free core the overlapped interior pass reads is now a
    literal concat operand of the extended block instead of a second,
    duplicated pad of the local state (on bandwidth-bound hosts that
    duplicate materialization was the overlapped schedule's entire
    overhead over the synchronous one).

    Returns (cur_e, prev_e, cur_i, prev_i): *_e the fully extended blocks,
    *_i the interior inputs — padded by g on x and on every UNSHARDED axis,
    raw local extent on sharded axes, no ppermute in their dataflow.
    """
    n_z, n_y = gs.counts()

    def one(b):
        core = _padx(b, g)
        zlo, zhi = halo.exchange_axis_parts(core, gs.z_axes, 0, g)
        extz = jnp.concatenate([zlo, core, zhi], axis=0)
        ylo, yhi = halo.exchange_axis_parts(extz, gs.y_axis, 1, g)
        ext = jnp.concatenate([ylo, extz, yhi], axis=1)
        # interior input, per sharding case (each mirrored op-for-op by the
        # synchronous schedule in _local_super_step_zones so the emitted
        # sweep fusions — and their FMA contraction — match):
        #   both axes sharded -> the raw shared core;
        #   y sharded only    -> the z-clamped node extz, already a concat
        #                        operand of the extended block (free);
        #   z sharded only    -> core + local y edge pad (the pad chain
        #                        inlines into the sweep fusion — a concat
        #                        here would inline ASYMMETRICALLY, XLA
        #                        elides optimization barriers late and
        #                        re-fuses, shifting LLVM's FMA choices).
        if n_z == 1:
            interior = ext if n_y == 1 else extz
        elif n_y == 1:
            interior = jnp.pad(core, [(0, 0), (g, g), (0, 0)], mode="edge")
        else:
            interior = core
        return ext, interior

    cur_e, cur_i = one(cur)
    if spec.time_order == 2:
        prev_e, prev_i = one(prev)
    else:
        prev_e, prev_i = cur_e, cur_i
    return cur_e, prev_e, cur_i, prev_i


def _frame_mask(shape, origin, grid_shape, r: int):
    """Dirichlet-frame mask of a block whose cell (0,0,0) sits at `origin`.

    `origin` holds GLOBAL grid coordinates (z, y, x); z/y may be traced
    (axis_index offsets), x is static.
    """
    nz_g, ny_g, nx_g = grid_shape
    oz, oy, ox = origin
    gz = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + oz
    gy = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + oy
    gx = jax.lax.broadcasted_iota(jnp.int32, shape, 2) + ox
    return ((gz < r) | (gz >= nz_g - r) | (gy < r) | (gy >= ny_g - r)
            | (gx < r) | (gx >= nx_g - r))


def _local_super_step(spec: st.StencilSpec, t_block: int, gs: GridSharding,
                      grid_shape, hoisted: bool, pad_g: int, cur, prev,
                      coeffs, err=None):
    """Synchronous local super-step: exchange, then advance the whole block.

    hoisted=True: coeffs arrive pre-extended at depth pad_g (see
    _extend_coeffs / make_coeff_extender) and are cropped down to this
    step's g. err (compressed mode) threads the int8 error-feedback faces;
    when given, the return gains a third element.
    """
    r = spec.radius
    g = r * t_block
    nz_g, ny_g, nx_g = grid_shape
    cur_e, prev_e, new_err = _exchange_state(spec, g, gs, cur, prev, err)
    cur_e, prev_e = _padx(cur_e, g), _padx(prev_e, g)
    if hoisted:
        arrays_e, svec = coeffs
        if arrays_e.shape[0]:
            arrays_e = _crop_hoisted(arrays_e, pad_g, g)
    else:
        arrays_e, svec = _extend_coeffs(spec, t_block, gs, coeffs)
    arrays_e = arrays_e if arrays_e.shape[0] else None

    # global coordinates of the extended block -> Dirichlet frame mask
    nz_l, ny_l, nx_l = cur.shape
    z0 = jax.lax.axis_index(gs.z_axes) * nz_l - g
    y0 = jax.lax.axis_index(gs.y_axis) * ny_l - g
    frame = _frame_mask(cur_e.shape, (z0, y0, -g), grid_shape, r)
    frame_vals = cur_e

    sweep = ir.make_sweep(spec)
    a, b = cur_e, prev_e
    for _ in range(t_block):
        new = sweep(a, b, arrays_e, svec)
        new = jnp.where(frame, frame_vals, new)
        a, b = new, a
    crop = (slice(g, g + nz_l), slice(g, g + ny_l), slice(g, g + nx_l))
    if err is not None:
        return a[crop], b[crop], new_err
    return a[crop], b[crop]


def _advance_trapezoid(sweep, a0, b0, arrays, svec, frame, kept,
                       t_block: int, r: int):
    """t_block frame-masked sweeps computing only the shrinking support of
    `kept`.

    At sub-step t (1-indexed) any cell farther than m = r*(t_block - t)
    from the kept box can no longer influence it, so the sweep runs on
    exactly kept ⊕ (m + r) and writes back kept ⊕ m; cells outside go stale
    but are never read again. Bitwise-equal to the full-block advance on
    the kept box at level t_block (a) and on kept ⊕ r at level
    t_block - 1 (b). Frame cells read back as the ORIGINAL a0 at every
    level, exactly like the synchronous path's frame_vals.
    """
    (kz0, kz1), (ky0, ky1) = kept
    a, b = a0, b0
    for t in range(1, t_block + 1):
        m = r * (t_block - t)
        z0, z1 = kz0 - m, kz1 + m
        y0, y1 = ky0 - m, ky1 + m
        sub = (slice(z0 - r, z1 + r), slice(y0 - r, y1 + r), slice(None))
        arr = arrays[(slice(None),) + sub] if arrays is not None else None
        new = sweep(a[sub], b[sub], arr, svec)
        new = jnp.where(frame[sub], a0[sub], new)
        core = new[r:r + (z1 - z0), r:r + (y1 - y0), :]
        a, b = a.at[z0:z1, y0:y1, :].set(core), a
    return a, b


def _advance_block(sweep, a0, b0, arrays, svec, frame, t_block: int):
    """t_block frame-masked full-block sweeps — the synchronous loop body.

    Used for the boundary slabs of the overlapped schedule: running the
    EXACT op sequence of the synchronous path (on a smaller array) keeps
    the compiled floating-point contraction identical to it, which the
    bitwise-equivalence guarantee rides on; the slabs are thin (3g), so
    skipping the trapezoid shrink costs little.
    """
    a, b = a0, b0
    for _ in range(t_block):
        new = sweep(a, b, arrays, svec)
        new = jnp.where(frame, a0, new)
        a, b = new, a
    return a, b


def _local_super_step_zones(spec: st.StencilSpec, t_block: int,
                            gs: GridSharding, grid_shape, pad_g: int,
                            overlap: bool, cur, prev, coeffs, err=None):
    """Zone-pipelined local super-step: interior trapezoid + boundary slabs.

    Both schedules of the split share this body; they differ ONLY in where
    the interior pass reads its input:

      overlap=True: from the pre-exchange local block (padded locally on x
      and on unsharded axes), so the interior advance's dataflow is
      independent of the ppermute pairs — XLA overlaps exchange and
      interior compute.

      overlap=False (synchronous): from the same-shaped slice of the
      freshly exchanged block — identical values (the halo of an
      unsharded axis is a local edge clamp), but the dependency puts the
      exchange on the critical path.

    Keeping every zone computation shape-identical between the schedules
    is what makes them bitwise-equal in practice: XLA's floating-point
    contraction choices are shape-dependent, so the equivalence guarantee
    pairs the exact-arithmetic argument (DESIGN.md §13) with identical
    per-zone compiled code. Boundary zones of depth g per sharded axis
    complete from the landed halos; coefficients must arrive hoisted
    (pre-extended at depth pad_g).
    """
    r = spec.radius
    g = r * t_block
    nz_l, ny_l, nx_l = cur.shape
    n_z, n_y = gs.counts()
    part = partition_geometry(cur.shape, g, n_z > 1, n_y > 1)
    sweep = ir.make_sweep(spec)
    xs = slice(g, g + nx_l)

    arrays_h, svec = coeffs
    arrays_e = (_crop_hoisted(arrays_h, pad_g, g) if arrays_h.shape[0]
                else None)
    z0l = jax.lax.axis_index(gs.z_axes) * nz_l
    y0l = jax.lax.axis_index(gs.y_axis) * ny_l

    if err is None:
        # the extended blocks are concatenated AROUND the collective-free
        # interior core, so the overlapped interior pass reuses it instead
        # of materializing a duplicate local pad
        cur_e, prev_e, cur_i, prev_i = _exchange_state_shared(
            spec, g, gs, cur, prev)
        new_err = None
    else:
        # compressed halos thread error-feedback state through the exchange;
        # no shared core there, so the interior input is a local re-pad (the
        # same values — unsharded-axis halos are edge clamps)
        cur_e, prev_e, new_err = _exchange_state(spec, g, gs, cur, prev, err)
        cur_e, prev_e = _padx(cur_e, g), _padx(prev_e, g)
        pads = [((0, 0) if part.split_z else (g, g)),
                ((0, 0) if part.split_y else (g, g)), (g, g)]
        cur_i = jnp.pad(cur, pads, mode="edge")
        prev_i = (jnp.pad(prev, pads, mode="edge")
                  if spec.time_order == 2 else cur_i)

    # ---- interior pass ----
    if overlap:
        # pre-exchange input: no ppermute result is in this pass's dataflow
        cur_l, prev_l = cur_i, prev_i
    else:
        # synchronous: the same-shaped, same-valued block sliced from the
        # exchanged state — the exchange is now on the critical path. The
        # barrier must come BEFORE the slice: the extended block is a
        # concat whose center operand is the collective-free core, and XLA
        # folds slice-of-concat back to that operand, which would silently
        # drop the exchange dependency and turn this schedule into the
        # overlapped one
        if spec.time_order == 2:
            cur_eb, prev_eb = jax.lax.optimization_barrier((cur_e, prev_e))
        else:
            cur_eb = jax.lax.optimization_barrier(cur_e)
            prev_eb = cur_eb
        # mirror the overlapped input's op sequence exactly per sharding
        # case (see _exchange_state_shared): a same-shaped slice of the
        # exchanged block, except z-sharded-only, where the overlapped
        # input is core + local y edge pad — there the slice takes the
        # core and repeats the IDENTICAL pad chain (same values: the
        # exchanged block's y halos ARE that edge clamp), which inlines
        # into the sweep fusion the same way on both schedules
        if part.split_z and not part.split_y:
            csl = (slice(g, g + nz_l), slice(g, g + ny_l), slice(None))
            wrap = lambda t: jnp.pad(t[csl], [(0, 0), (g, g), (0, 0)],
                                     mode="edge")
        else:
            isl = (slice(g, g + nz_l) if part.split_z else slice(None),
                   slice(g, g + ny_l) if part.split_y else slice(None),
                   slice(None))
            wrap = lambda t: t[isl]
        cur_l = wrap(cur_eb)
        prev_l = wrap(prev_eb) if spec.time_order == 2 else cur_l
    if arrays_e is not None:
        azs = slice(g, g + nz_l) if part.split_z else slice(None)
        ays = slice(g, g + ny_l) if part.split_y else slice(None)
        arrays_l = arrays_e[:, azs, ays, :]
    else:
        arrays_l = None
    # materialize the interior inputs before the sweeps: without the
    # barrier XLA fuses the producer (a local pad here, a slice of the
    # exchanged block there) into the first sweep loop, and the two
    # fusions contract FMAs differently — ulp-level divergence between
    # schedules that are exact-arithmetic-identical
    if arrays_l is None:
        cur_l, prev_l = jax.lax.optimization_barrier((cur_l, prev_l))
    else:
        cur_l, prev_l, arrays_l = jax.lax.optimization_barrier(
            (cur_l, prev_l, arrays_l))
    ioz, ioy = part.interior_origin
    frame_l = _frame_mask(cur_l.shape, (z0l + ioz, y0l + ioy, -g),
                          grid_shape, r)
    a_i, b_i = _advance_trapezoid(sweep, cur_l, prev_l, arrays_l, svec,
                                  frame_l, part.interior_kept, t_block, r)
    (ikz0, ikz1), (iky0, iky1) = part.interior_kept
    int_a = a_i[ikz0:ikz1, iky0:iky1, xs]
    int_b = b_i[ikz0:ikz1, iky0:iky1, xs]

    # ---- boundary completion from the landed halos ----
    outs = {}
    for zn in part.zones:
        blk = (zn.z, zn.y, slice(None))
        ca, pa = cur_e[blk], prev_e[blk]
        ar = arrays_e[(slice(None),) + blk] if arrays_e is not None else None
        # same producer isolation as the interior pass: zone inputs
        # materialize before the sweeps in BOTH schedules, so the zone
        # fusions compile identically whether or not the exchanged block
        # has the synchronous path's extra barrier consumer
        if ar is None:
            ca, pa = jax.lax.optimization_barrier((ca, pa))
        else:
            ca, pa, ar = jax.lax.optimization_barrier((ca, pa, ar))
        fr = _frame_mask(ca.shape, (z0l + zn.origin[0], y0l + zn.origin[1],
                                    -g), grid_shape, r)
        a_z, b_z = _advance_block(sweep, ca, pa, ar, svec, fr, t_block)
        (az0, az1), (ay0, ay1) = zn.kept
        outs[zn.name] = (a_z[az0:az1, ay0:ay1, xs],
                         b_z[az0:az1, ay0:ay1, xs])

    out_a, out_b = _assemble(part, (int_a, int_b), outs)
    if err is not None:
        return out_a, out_b, new_err
    return out_a, out_b


def _assemble(part: Partition, interior, outs):
    """Concatenate zone outputs back into the full local block (both levels)."""
    def one(level):
        mid = interior[level]
        if part.split_y:
            mid = jnp.concatenate([outs["y_lo"][level], mid,
                                   outs["y_hi"][level]], axis=1)
        if part.split_z:
            mid = jnp.concatenate([outs["z_lo"][level], mid,
                                   outs["z_hi"][level]], axis=0)
        return mid
    return one(0), one(1)


def _mwd_block(spec: st.StencilSpec, plan: MWDPlan, scalars, t_block: int,
               grid_shape, g: int, a, b, arrays, origin_zy):
    """One fused MWD launch on a (sub-)block of the extended local grid.

    `origin_zy` holds the (possibly traced) GLOBAL grid coordinates of block
    cell (0, 0); the global Dirichlet frame is clipped into the block and
    enforced by the kernel's dynamic write mask. The plan's diamond width is
    re-capped against this block's own y extent.
    """
    r = spec.radius
    nz_g, ny_g, nx_g = grid_shape
    bnz, bny = a.shape[0], a.shape[1]
    oz, oy = origin_zy
    lo_z = jnp.clip(r - oz, 0, bnz)
    hi_z = jnp.clip(nz_g - r - oz, 0, bnz)
    lo_y = jnp.clip(r - oy, 0, bny)
    hi_y = jnp.clip(ny_g - r - oy, 0, bny)
    interior = jnp.stack([lo_z, hi_z, lo_y, hi_y,
                          jnp.asarray(g + r), jnp.asarray(g + nx_g - r)]
                         ).astype(jnp.int32)
    if spec.time_order == 2:
        # frame cells must read back as cur at EVERY time parity (the jnp
        # path re-imposes them each step); sync the odd-parity buffer too
        fr = _frame_mask(a.shape, (oz, oy, -g), grid_shape, r)
        b = jnp.where(fr, a, b)
    pb = cap_plan_d_w(spec, plan, bny)
    return stencil_mwd.mwd_run(spec, (a, b), arrays, scalars, t_block,
                               d_w=pb.d_w, n_f=pb.n_f, fused=pb.fused,
                               interior=interior, y_domain=(0, bny))


def _local_super_step_mwd(spec: st.StencilSpec, plan: MWDPlan, t_block: int,
                          gs: GridSharding, grid_shape, hoisted: bool,
                          pad_g: int, scalars, cur, prev, coeffs, err=None):
    """MWD-kernel local super-step: ONE fused pallas_call per halo exchange.

    Same deep-halo contract as _local_super_step, but the t_block local steps
    run as a single compiled-schedule MWD launch instead of t_block jnp
    sweeps. The diamond tessellation spans the full extended block so halo
    cells advance the intermediate levels the interior needs.  `scalars`
    carries the op's compile-time scalar coefficients as static Python
    floats (the kernel inlines them; the traced scalar vector in `coeffs`
    is ignored here).
    """
    r = spec.radius
    g = r * t_block
    cur_e, prev_e, new_err = _exchange_state(spec, g, gs, cur, prev, err)
    cur_e, prev_e = _padx(cur_e, g), _padx(prev_e, g)
    if hoisted:
        arrays_e, _ = coeffs
        if arrays_e.shape[0]:
            arrays_e = _crop_hoisted(arrays_e, pad_g, g)
    else:
        arrays_e, _ = _extend_coeffs(spec, t_block, gs, coeffs)
    arrays_e = arrays_e if arrays_e.shape[0] else None

    nz_l, ny_l, nx_l = cur.shape
    z0 = jax.lax.axis_index(gs.z_axes) * nz_l - g
    y0 = jax.lax.axis_index(gs.y_axis) * ny_l - g
    a, b = _mwd_block(spec, plan, scalars, t_block, grid_shape, g,
                      cur_e, prev_e, arrays_e, (z0, y0))
    crop = (slice(g, g + nz_l), slice(g, g + ny_l), slice(g, g + nx_l))
    if err is not None:
        return a[crop], b[crop], new_err
    return a[crop], b[crop]


def _local_super_step_overlap_mwd(spec: st.StencilSpec, plan: MWDPlan,
                                  t_block: int, gs: GridSharding, grid_shape,
                                  pad_g: int, scalars, cur, prev, coeffs,
                                  err=None):
    """Overlapped MWD-kernel super-step: one fused launch per zone.

    The interior launch's dataflow is independent of the exchange (it reads
    the pre-exchange local block); each boundary zone gets its own launch on
    its 3g-thick slab once the halos land. Full-block (not trapezoid)
    advancement inside each launch — the kernel's diamond schedule already
    skews time internally — with the kept-box crop making assembly bitwise.
    """
    r = spec.radius
    g = r * t_block
    nz_l, ny_l, nx_l = cur.shape
    n_z, n_y = gs.counts()
    part = partition_geometry(cur.shape, g, n_z > 1, n_y > 1)
    xs = slice(g, g + nx_l)

    arrays_h, _ = coeffs
    arrays_e = (_crop_hoisted(arrays_h, pad_g, g) if arrays_h.shape[0]
                else None)
    z0l = jax.lax.axis_index(gs.z_axes) * nz_l
    y0l = jax.lax.axis_index(gs.y_axis) * ny_l

    pads = [((0, 0) if part.split_z else (g, g)),
            ((0, 0) if part.split_y else (g, g)), (g, g)]
    padl = lambda t: jnp.pad(t, [(0, 0)] * (t.ndim - 3) + pads, mode="edge")
    cur_l = padl(cur)
    prev_l = padl(prev) if spec.time_order == 2 else cur_l
    if arrays_e is not None:
        azs = slice(None) if not part.split_z else slice(g, g + nz_l)
        ays = slice(None) if not part.split_y else slice(g, g + ny_l)
        arrays_l = arrays_e[:, azs, ays, :]
    else:
        arrays_l = None
    ioz, ioy = part.interior_origin
    a_i, b_i = _mwd_block(spec, plan, scalars, t_block, grid_shape, g,
                          cur_l, prev_l, arrays_l, (z0l + ioz, y0l + ioy))
    (ikz0, ikz1), (iky0, iky1) = part.interior_kept
    interior = (a_i[ikz0:ikz1, iky0:iky1, xs], b_i[ikz0:ikz1, iky0:iky1, xs])

    cur_e, prev_e, new_err = _exchange_state(spec, g, gs, cur, prev, err)
    cur_e, prev_e = _padx(cur_e, g), _padx(prev_e, g)
    outs = {}
    for zn in part.zones:
        blk = (zn.z, zn.y, slice(None))
        ar = arrays_e[(slice(None),) + blk] if arrays_e is not None else None
        a_z, b_z = _mwd_block(spec, plan, scalars, t_block, grid_shape, g,
                              cur_e[blk], prev_e[blk], ar,
                              (z0l + zn.origin[0], y0l + zn.origin[1]))
        (az0, az1), (ay0, ay1) = zn.kept
        outs[zn.name] = (a_z[az0:az1, ay0:ay1, xs],
                         b_z[az0:az1, ay0:ay1, xs])

    out_a, out_b = _assemble(part, interior, outs)
    if err is not None:
        return out_a, out_b, new_err
    return out_a, out_b


# ---------------------------------------------------------------------------
# public builders
# ---------------------------------------------------------------------------

def _coeff_specs(spec: st.StencilSpec, gs: GridSharding) -> tuple:
    """PartitionSpecs of the canonical (stacked arrays, scalar vector) pair.

    Uniform for every operator: the stacked stream shards like the grid
    (leading slot axis unsharded), the scalar vector replicates.
    """
    del spec
    return (gs.spec(leading=1), P())


def make_super_step(spec: st.StencilSpec, mesh: jax.sharding.Mesh,
                    grid_shape, t_block: int, *, hoisted: bool = False,
                    pad_g: int | None = None, plan: MWDPlan | None = None,
                    scalars=None, compress: bool = False,
                    overlap: bool | str = False):
    """Build the jitted distributed super-step: (cur, prev, coeffs) -> state.

    `coeffs` is the canonical (stacked arrays, scalar vector) pair — see
    `canonical_coeffs` — for every operator, first- or second-order.

    hoisted=True expects coefficients pre-extended by make_coeff_extender
    at depth `pad_g` (default: this step's own g = R * t_block; pass the
    FULL run's depth to let a partial final super-step crop them instead of
    re-exchanging).

    plan: when given, each device advances its t_block local steps with
    fused MWD kernel launches (the compiled diamond schedule) instead of
    t_block jnp sweeps. `scalars` carries the op's scalar coefficients as
    static Python floats (the kernel inlines them); required for
    scalar-coefficient operators.

    compress=True ships the solution halos int8-compressed with error
    feedback: the step becomes (cur, prev, coeffs, err) -> (cur, prev,
    err'), where `err` is the sharded residual-face pytree from
    `init_halo_error_global` (thread the returned err' into the next
    super-step — dropping it forfeits the telescoping). Coefficients still
    exchange exact. Composes with overlap: the residual faces ride the
    same double-buffered exchange the boundary zones consume.

    overlap=True splits each shard into a halo-independent interior (advanced
    concurrently with the ppermute exchange) and boundary zones completed
    from the landed halos — bitwise-equal to the synchronous schedule.
    Pass "auto" to fall back to synchronous when the shards are too small
    (see `validate_super_step`). Requires hoisted coefficients.
    """
    if overlap == "auto":
        overlap = overlap_feasible(spec, mesh, grid_shape, t_block)
    validate_super_step(spec, mesh, grid_shape, t_block, overlap=bool(overlap))
    if overlap and not hoisted:
        raise ValueError(
            "overlap=True requires hoisted coefficients (make_coeff_extender)"
            ": a per-super-step coefficient exchange would re-serialize the "
            "interior advance on the ppermute it is meant to hide")
    if pad_g is None:
        pad_g = spec.radius * t_block
    gs = GridSharding(mesh)
    kwargs = {}
    if plan is not None:
        if overlap:
            local = partial(_local_super_step_overlap_mwd, spec, plan,
                            t_block, gs, grid_shape, pad_g, scalars)
        else:
            local = partial(_local_super_step_mwd, spec, plan, t_block, gs,
                            grid_shape, hoisted, pad_g, scalars)
        kwargs["check_vma"] = False     # no replication rule for pallas_call
    elif hoisted and overlap_feasible(spec, mesh, grid_shape, t_block):
        # both schedules share the zone pipeline so every zone computation
        # compiles at the same shape — bitwise equality between them then
        # follows from dataflow alone (see _local_super_step_zones)
        local = partial(_local_super_step_zones, spec, t_block, gs,
                        grid_shape, pad_g, bool(overlap))
    else:
        local = partial(_local_super_step, spec, t_block, gs, grid_shape,
                        hoisted, pad_g)
    if compress:
        # one gs.spec() per err subtree: PartitionSpecs act as pytree
        # prefixes, and every residual face shards exactly like the grid
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(gs.spec(), gs.spec(), _coeff_specs(spec, gs),
                      gs.spec()),
            out_specs=(gs.spec(), gs.spec(), gs.spec()),
            **kwargs,
        )
    else:
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(gs.spec(), gs.spec(), _coeff_specs(spec, gs)),
            out_specs=(gs.spec(), gs.spec()),
            **kwargs,
        )
    return jax.jit(fn)


def init_halo_error_global(spec: st.StencilSpec, mesh, grid_shape,
                           t_block: int):
    """Sharded zero error-feedback faces for the compressed super-step.

    Global face arrays shaped so `GridSharding.spec()` shards each one into
    exactly the local faces `halo.exchange_2d_compressed` expects: z faces
    stack the per-shard (g, ny_l, nx) slabs along z, y faces stack the
    per-shard (nz_l + 2g, g, nx) slabs along both z and y. One entry per
    exchanged stream: {"cur": faces} (+ "prev" for second-order ops).
    """
    gs = GridSharding(mesh)
    g = spec.radius * t_block
    nz, ny, nx = grid_shape
    n_z, n_y = gs.counts()
    nz_l = nz // n_z
    z_face = (g * n_z, ny, nx)
    y_face = ((nz_l + 2 * g) * n_z, g * n_y, nx)
    sh = gs.sharding()

    def faces():
        return {"z_lo": jax.device_put(jnp.zeros(z_face, jnp.float32), sh),
                "z_hi": jax.device_put(jnp.zeros(z_face, jnp.float32), sh),
                "y_lo": jax.device_put(jnp.zeros(y_face, jnp.float32), sh),
                "y_hi": jax.device_put(jnp.zeros(y_face, jnp.float32), sh)}

    err = {"cur": faces()}
    if spec.time_order == 2:
        err["prev"] = faces()
    return err


def make_coeff_extender(spec: st.StencilSpec, mesh: jax.sharding.Mesh,
                        t_block: int):
    """One-time coefficient halo exchange; output feeds hoisted super-steps."""
    gs = GridSharding(mesh)
    fn = jax.shard_map(
        partial(_extend_coeffs, spec, t_block, gs),
        mesh=mesh,
        in_specs=(_coeff_specs(spec, gs),),
        out_specs=_coeff_specs(spec, gs),
    )
    return jax.jit(fn)


def local_extended_shape(spec: st.StencilSpec, mesh, grid_shape,
                         t_block: int) -> tuple[int, int, int]:
    """Shape of the extended local block ONE device's MWD kernel launches on.

    The fused super-step runs the kernel on each shard's halo-extended block
    — local extent plus the deep halo g = R * t_block on z and y and the
    edge-padded g on x — NOT on the global grid.  Plan resolution must key
    on this shape: a plan tuned for the global grid can prescribe a diamond
    width larger than the shard's whole y extent.
    """
    gs = GridSharding(mesh)
    g = spec.radius * t_block
    nz, ny, nx = grid_shape
    n_z, n_y = gs.counts()
    return (nz // n_z + 2 * g, ny // n_y + 2 * g, nx + 2 * g)


def cap_plan_d_w(spec: st.StencilSpec, plan: MWDPlan, ny_local: int) -> MWDPlan:
    """Clamp a plan's diamond width to a shard's y extent.

    A D_w wider than the local block only inflates the launch padding (the
    kernel pads y by 2*D_w + R per side) without ever tiling anything — the
    global-grid optimum is meaningless on a shard a fraction its height.
    Returns a kernel-valid plan: D_w a multiple of 2R capped at `ny_local`,
    N_F re-clamped to divide it.
    """
    step = 2 * spec.radius
    cap = max(step, ny_local // step * step)
    if plan.d_w <= cap:
        return plan
    n_f = min(max(plan.n_f, 1), cap)
    while cap % n_f:
        n_f -= 1
    return dataclasses.replace(plan, d_w=cap, n_f=n_f)


def canonical_coeffs(spec: st.StencilSpec, coeffs, grid_shape, dtype):
    """Packed coefficients -> the canonical (stacked arrays, scalar vector).

    Both halves always exist (possibly zero-length along their leading axis,
    shaped over `grid_shape` so the grid sharding applies) so one shard_map
    signature covers every operator.
    """
    arrays, scalars = ir.split_coeffs(spec, coeffs)
    if arrays is None:
        arrays = jnp.zeros((0,) + tuple(grid_shape), dtype)
    if scalars:
        svec = jnp.stack([jnp.asarray(v, dtype) for v in scalars])
    else:
        svec = jnp.zeros((0,), dtype)
    return arrays, svec


def coeff_sds(spec: st.StencilSpec, grid_shape, dtype=jnp.float32):
    """ShapeDtypeStructs of the canonical coefficient pair on `grid_shape`."""
    return (jax.ShapeDtypeStruct((spec.n_coeff_arrays,) + tuple(grid_shape),
                                 dtype),
            jax.ShapeDtypeStruct((spec.n_scalars,), dtype))


def extended_coeff_sds(spec: st.StencilSpec, mesh, grid_shape, t_block: int,
                       dtype=jnp.float32):
    """Global ShapeDtypeStruct of the hoisted (pre-extended) coefficients."""
    gs = GridSharding(mesh)
    g = spec.radius * t_block
    nz, ny, nx = grid_shape
    n_z, n_y = gs.counts()
    ext = (nz + 2 * g * n_z, ny + 2 * g * n_y, nx + 2 * g)
    if spec.n_coeff_arrays:
        return (jax.ShapeDtypeStruct((spec.n_coeff_arrays,) + ext, dtype),
                jax.ShapeDtypeStruct((spec.n_scalars,), dtype))
    return coeff_sds(spec, grid_shape, dtype)


def run_distributed(spec: st.StencilSpec, mesh, state, coeffs, n_steps: int,
                    t_block: int = 2, *, plan: MWDPlan | str | None = None,
                    compress: bool = False, overlap: bool | str = False):
    """Place the problem on the mesh and advance n_steps (super-stepped).

    Coefficients are ALWAYS hoisted: one exchange at setup
    (make_coeff_extender) feeds every super-step — including a partial
    final one (t_block does not divide n_steps), which crops the
    pre-extended arrays from the full depth down to its own instead of
    re-exchanging. Exactly one coefficient ppermute set per run.

    overlap=True runs the interior/boundary-split schedule (see
    make_super_step) — bitwise-equal to the synchronous path with the
    exchange hidden behind the interior advance; "auto" falls back to
    synchronous when the shards are too small for the split. Overlap
    engages for full-depth super-steps with t_block >= 2; a t_block=1 run
    or the trailing partial step executes the synchronous schedule (a
    one-step halo leaves nearly nothing to hide, and the shared sync step
    keeps the composed run bitwise-identical in both modes).

    compress=True ships solution halos int8-compressed with error feedback
    (`halo.exchange_2d_compressed`): ~word_size x less ICI halo traffic per
    super-step at a quantization error the per-op budget test harness
    bounds. The residual state threads through the whole run; a partial
    final super-step restarts it at zero because the residual faces are
    shaped by the halo depth g = R * tb.

    plan: run each super-step as fused MWD kernel launches per device
    (see make_super_step) instead of t_block jnp sweeps. Pass "auto" to
    resolve the tuned plan registry-first from repro.core.registry
    (model-scored fallback on a miss) — repeat runs after one
    `python -m repro.launch.tune` skip the search entirely. The plan is
    resolved against the PER-SHARD extended block shape the kernel actually
    launches on (see `local_extended_shape`), with the mesh's real x-axis
    device count, and its D_w is capped at the shard's y extent; an
    explicit `MWDPlan` whose D_w exceeds the local y extent is rejected.
    """
    gs = GridSharding(mesh)
    cur, prev = state
    shape_e = local_extended_shape(spec, mesh, cur.shape, t_block)
    if isinstance(plan, str):
        if plan != "auto":
            raise ValueError(f"plan must be an MWDPlan or 'auto', got {plan!r}")
        from repro.core import registry
        # the kernel runs on each shard's halo-extended local block, so the
        # tuned plan is keyed on that shape — NOT the global grid, whose
        # optimum can be wider than the whole shard. GridSharding never
        # shards grid-x, so devices_x is 1 on every mesh this stepper
        # builds; the lookup (rather than a hard-coded 1) keeps the key
        # honest if a future mesh adds an explicit "x" axis
        devices_x = mesh.shape.get("x", 1)
        plan, _source = registry.resolve_plan(
            spec, shape_e, word_bytes=cur.dtype.itemsize,
            devices_x=devices_x)
        plan = cap_plan_d_w(spec, plan, shape_e[1])
    elif plan is not None and plan.d_w > shape_e[1]:
        raise ValueError(
            f"plan d_w={plan.d_w} exceeds the per-shard extended y extent "
            f"{shape_e[1]} (global ny={cur.shape[1]} over "
            f"{mesh.shape[gs.y_axis]} shards); tune against "
            f"local_extended_shape() or pass plan='auto'")
    prev = (jax.device_put(prev, gs.sharding()) if spec.time_order == 2
            else jax.device_put(cur, gs.sharding()))
    cur = jax.device_put(cur, gs.sharding())
    arrays, svec = canonical_coeffs(spec, coeffs, cur.shape, cur.dtype)
    # the MWD kernel bakes scalar coefficients in as compile-time constants;
    # hoist them to static Python floats while they are still concrete
    scalars = tuple(float(x) for x in svec) if plan is not None else None
    if spec.n_coeff_arrays:
        arrays = jax.device_put(arrays, gs.sharding(leading=1))
    coeffs = make_coeff_extender(spec, mesh, t_block)((arrays, svec))
    pad_g = spec.radius * t_block
    ovl = overlap if t_block > 1 else False
    step = make_super_step(spec, mesh, cur.shape, t_block, hoisted=True,
                           pad_g=pad_g, plan=plan, scalars=scalars,
                           compress=compress, overlap=ovl)
    err = (init_halo_error_global(spec, mesh, cur.shape, t_block)
           if compress else None)
    done = 0
    while done < n_steps:
        tb = min(t_block, n_steps - done)
        if tb != t_block:
            # trailing partial super-step: synchronous schedule (see above)
            step = make_super_step(spec, mesh, cur.shape, tb, hoisted=True,
                                   pad_g=pad_g, plan=plan, scalars=scalars,
                                   compress=compress, overlap=False)
            if compress:    # residual faces are g-shaped: restart at zero
                err = init_halo_error_global(spec, mesh, cur.shape, tb)
        if compress:
            cur, prev, err = step(cur, prev, coeffs, err)
        else:
            cur, prev = step(cur, prev, coeffs)
        done += tb
    return cur, prev
