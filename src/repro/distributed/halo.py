"""Halo exchange primitives for the distributed stencil stepper.

Deep halos (depth g = R * t_block) amortize one neighbor exchange over
t_block local time steps — the ICI-scale version of the paper's
bandwidth-vs-synchronization-frequency knob. The exchange is two-phase
(z-axis first, then y-axis over the z-extended block) so corner halos arrive
transitively, which multi-step star-stencil composition requires.

All functions run INSIDE shard_map: arrays are local blocks, communication is
jax.lax.ppermute. The permute pairs and the interior compute are independent
dataflow, letting the XLA scheduler overlap them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed import compression


def _edge_clamp(block, depth: int, axis: int, lo: bool):
    """Edge-replicated stand-in halo at the global domain boundary."""
    idx = [slice(None)] * block.ndim
    idx[axis] = slice(0, 1) if lo else slice(-1, None)
    edge = block[tuple(idx)]
    reps = [1] * block.ndim
    reps[axis] = depth
    return jnp.tile(edge, reps)


def exchange_axis_parts(block, axis_name: str, axis: int, depth: int):
    """The two halo slabs of `exchange_axis`, NOT yet concatenated.

    Exposed so the zone-split super-step can assemble the extended block
    around a shared, collective-free core (`stepper._exchange_state_shared`)
    instead of re-padding the local block for the interior pass.
    Returns (lo_halo, hi_halo), each `depth` thick along `axis`.
    """
    if depth > block.shape[axis]:
        raise ValueError(
            f"halo depth {depth} exceeds local block extent "
            f"{block.shape[axis]} on axis {axis}: lower t_block or use a "
            f"coarser decomposition (single-hop exchange only)")
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return (_edge_clamp(block, depth, axis, lo=True),
                _edge_clamp(block, depth, axis, lo=False))
    i = jax.lax.axis_index(axis_name)
    ndim = block.ndim
    lo_idx = [slice(None)] * ndim
    hi_idx = [slice(None)] * ndim
    lo_idx[axis] = slice(0, depth)
    hi_idx[axis] = slice(block.shape[axis] - depth, block.shape[axis])
    fwd = [(r, (r + 1) % n) for r in range(n)]
    bwd = [(r, (r - 1) % n) for r in range(n)]
    # halo arriving at my low side = neighbor (i-1)'s high slab
    lo_halo = jax.lax.ppermute(block[tuple(hi_idx)], axis_name, fwd)
    hi_halo = jax.lax.ppermute(block[tuple(lo_idx)], axis_name, bwd)
    lo_halo = jnp.where(i == 0, _edge_clamp(block, depth, axis, True), lo_halo)
    hi_halo = jnp.where(i == n - 1, _edge_clamp(block, depth, axis, False),
                        hi_halo)
    return lo_halo, hi_halo


def exchange_axis(block, axis_name: str, axis: int, depth: int):
    """Return block extended by `depth` halo slabs on both sides of `axis`.

    Neighbors communicate via ppermute (ring); the global-edge ranks replace
    the wrapped halo with an edge clamp (the Dirichlet frame makes the actual
    values irrelevant — interior updates only ever read true frame cells).
    """
    lo_halo, hi_halo = exchange_axis_parts(block, axis_name, axis, depth)
    return jnp.concatenate([lo_halo, block, hi_halo], axis=axis)


def exchange_2d(block, depth: int, *, axis_z: str, axis_y: str,
                z_dim: int = -3, y_dim: int = -2):
    """Two-phase deep-halo exchange: z, then y over the z-extended block.

    Corner halos arrive transitively through the second phase.
    """
    ndim = block.ndim
    ext = exchange_axis(block, axis_z, z_dim % ndim, depth)
    ext = exchange_axis(ext, axis_y, y_dim % ndim, depth)
    return ext


def exchange_axis_compressed(block, axis_name: str, axis: int, depth: int,
                             err_send_lo, err_send_hi):
    """`exchange_axis` shipping int8 payloads + f32 scales with error feedback.

    Each rank quantizes the slabs it SENDS (`distributed.compression.
    quantize_slab`: local-max scale, no collective) and ships the int8
    payload plus one f32 scale per slab; the receiver dequantizes into the
    stream dtype. `err_send_lo` / `err_send_hi` are this rank's f32
    error-feedback residuals for its low-/high-side sent slabs — the
    quantization error of super-step k is added back before quantizing at
    super-step k+1, so the per-exchange bias telescopes instead of
    accumulating (same scheme as `compressed_pmean`).

    Returns (extended_block, new_err_send_lo, new_err_send_hi). With a
    single rank on the axis the exchange degenerates to the exact edge
    clamp and the residuals pass through unchanged.
    """
    if depth > block.shape[axis]:
        raise ValueError(
            f"halo depth {depth} exceeds local block extent "
            f"{block.shape[axis]} on axis {axis}: lower t_block or use a "
            f"coarser decomposition (single-hop exchange only)")
    n = jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    ndim = block.ndim
    lo_idx = [slice(None)] * ndim
    hi_idx = [slice(None)] * ndim
    lo_idx[axis] = slice(0, depth)
    hi_idx[axis] = slice(block.shape[axis] - depth, block.shape[axis])
    if n == 1:
        lo_halo = _edge_clamp(block, depth, axis, lo=True)
        hi_halo = _edge_clamp(block, depth, axis, lo=False)
        return (jnp.concatenate([lo_halo, block, hi_halo], axis=axis),
                err_send_lo, err_send_hi)

    q_hi, s_hi, new_err_hi = compression.quantize_slab(
        block[tuple(hi_idx)], err_send_hi)
    q_lo, s_lo, new_err_lo = compression.quantize_slab(
        block[tuple(lo_idx)], err_send_lo)
    fwd = [(r, (r + 1) % n) for r in range(n)]
    bwd = [(r, (r - 1) % n) for r in range(n)]
    # halo arriving at my low side = neighbor (i-1)'s high slab + its scale
    lo_q = jax.lax.ppermute(q_hi, axis_name, fwd)
    lo_s = jax.lax.ppermute(s_hi, axis_name, fwd)
    hi_q = jax.lax.ppermute(q_lo, axis_name, bwd)
    hi_s = jax.lax.ppermute(s_lo, axis_name, bwd)
    lo_halo = compression.dequantize_slab(lo_q, lo_s, block.dtype)
    hi_halo = compression.dequantize_slab(hi_q, hi_s, block.dtype)
    lo_halo = jnp.where(i == 0, _edge_clamp(block, depth, axis, True), lo_halo)
    hi_halo = jnp.where(i == n - 1, _edge_clamp(block, depth, axis, False),
                        hi_halo)
    return (jnp.concatenate([lo_halo, block, hi_halo], axis=axis),
            new_err_lo, new_err_hi)


def exchange_2d_compressed(block, depth: int, err, *, axis_z: str,
                           axis_y: str, z_dim: int = -3, y_dim: int = -2):
    """Two-phase compressed deep-halo exchange; returns (ext, new_err).

    `err` is the per-stream error-feedback state: a dict with f32 residual
    faces ``z_lo``/``z_hi`` (shaped like the z slabs this rank sends) and
    ``y_lo``/``y_hi`` (shaped like the y slabs of the z-EXTENDED block).
    Build the initial zeros with `init_halo_error`.
    """
    ndim = block.ndim
    ext, e_zlo, e_zhi = exchange_axis_compressed(
        block, axis_z, z_dim % ndim, depth, err["z_lo"], err["z_hi"])
    ext, e_ylo, e_yhi = exchange_axis_compressed(
        ext, axis_y, y_dim % ndim, depth, err["y_lo"], err["y_hi"])
    return ext, {"z_lo": e_zlo, "z_hi": e_zhi, "y_lo": e_ylo, "y_hi": e_yhi}


def init_halo_error(local_shape, depth: int):
    """Zero error-feedback faces for one LOCAL block (inside shard_map)."""
    nz, ny, nx = local_shape[-3:]
    lead = tuple(local_shape[:-3])
    z_face = lead + (depth, ny, nx)
    y_face = lead + (nz + 2 * depth, depth, nx)
    return {"z_lo": jnp.zeros(z_face, jnp.float32),
            "z_hi": jnp.zeros(z_face, jnp.float32),
            "y_lo": jnp.zeros(y_face, jnp.float32),
            "y_hi": jnp.zeros(y_face, jnp.float32)}


def halo_bytes(local_shape, depth: int, word_bytes: int, n_streams: int,
               compress: bool = False) -> int:
    """Per-super-step ICI bytes per device (both axes, both directions).

    compress=True counts the int8 wire format of the compressed exchange:
    1 byte per halo cell plus one f32 scale per sent slab (4 slabs per
    stream), independent of the stream word size.
    """
    nz, ny, nx = local_shape[-3:]
    z_face = depth * ny * nx
    y_face = depth * (nz + 2 * depth) * nx
    if compress:
        return 2 * (z_face + y_face) * 1 * n_streams + 4 * 4 * n_streams
    return 2 * (z_face + y_face) * word_bytes * n_streams
