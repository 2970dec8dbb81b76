"""Analytic performance models from the paper, adapted to TPU v5e.

* Eq. 2/3  — cache-block-size model  -> exact VMEM footprint constraint.
* Eq. 4/5  — memory-traffic / code-balance model (bytes per LUP).
* ECM-TPU  — {T_compute || T_vmem || T_hbm} phenomenological model (Sec. 2.2),
             with TPU's software-managed memory making the transfer terms exact.
* Roofline — the graded terms (compute / memory / collective / latency).
* Energy   — Fig. 19 analog: E = P_static*T + e_flop*F + e_byte*B_hbm.
* Calibration — Sec. 7-8 analog: `fit_ecm` fits the phenomenological
             constants to measured sweep points (repro.launch.sweep) and
             `model_residuals` confronts model with measurement; fits are
             persisted as per-spec artifacts (`save_calibration`).

All models are pure functions of the stencil spec + tiling plan + the
machine model (a declarative `repro.core.specs.DeviceSpec`; ``chip=None``
resolves the process default — ``--spec`` / ``$REPRO_DEVICE_SPEC``), so the
auto-tuner and the benchmarks share one source of truth. Launches whose
HBM traffic falls under the spec's derived ``latency_bytes`` crossover are
reported latency-bound instead of being mis-modeled as bandwidth-bound.
"""

from __future__ import annotations

import dataclasses
import math

from repro.core import specs as devspecs
from repro.core.precision import DEFAULT_WORD_BYTES
from repro.core.stencils import StencilSpec
from repro.core.tiling import wavefront_width


# ---------------------------------------------------------------------------
# Eq. 2/3: cache (VMEM) block size
# ---------------------------------------------------------------------------

def cache_block_bytes(spec: StencilSpec, d_w: int, n_f: int, n_xb: int) -> float:
    """Eq. 3 (general R): bytes of one wavefront-diamond cache block.

    n_xb: bytes along the leading dimension held per (y,z) cell — in the paper
    the full x line; on TPU the (possibly x-sharded) lane-padded extent.
    N_D here is the paper's stream count for block sizing: the solution
    levels + coefficient arrays resident per cell.
    """
    r = spec.radius
    n_d = spec.bytes_per_cell
    w_w = wavefront_width(d_w, r, n_f)
    return n_xb * (n_d * d_w * (d_w / 2.0 - r + n_f) + 2.0 * r * (d_w + w_w))


LANES = 128                 # TPU vector lane width (the x tile)
COMPILER_RESERVE = 2 << 20  # VMEM bytes left to Mosaic's own scratch


def sublanes(word_bytes: int) -> int:
    """Rows of one (sublane, lane) VMEM tile: 8 for 32-bit words, 16 for 16-bit."""
    return 8 * max(1, 4 // word_bytes)


@dataclasses.dataclass(frozen=True)
class MWDWindow:
    """Tile-aligned VMEM window of the MWD kernel (`kernels/stencil_mwd`).

    Along y a tile owns D_w rows that start at any offset below one sublane
    tile ``s`` (the diamond rows alternate by D_w/2).  The kernel updates the
    aligned `span` rows that cover them, reads `s` rows either side (R <= s)
    and emits the whole span, so every DMA start and length along y is a
    multiple of `s`.  x is padded to whole lanes, `nxp`.
    """

    s: int          # sublane tile rows
    span: int       # updated / emitted rows per tile, a multiple of s
    wy: int         # window rows: span + 2s
    z_ws: int       # window z-rows: N_F + D_w + R
    nxp: int        # lane-padded x extent


def mwd_window(radius: int, d_w: int, n_f: int, nx: int,
               word_bytes: int = DEFAULT_WORD_BYTES) -> MWDWindow:
    """Aligned window geometry of one MWD tile (see `MWDWindow`).

    Owned rows start at offsets ``k*D_w - e*D_w/2`` modulo `s` from an
    aligned origin, i.e. at multiples of ``gcd(D_w/2, s)``, so the largest
    offset is ``s - gcd(D_w/2, s)``.
    """
    s = sublanes(word_bytes)
    if radius > s:
        raise ValueError(f"radius {radius} exceeds the {s}-row sublane tile")
    span = -(-(s - math.gcd(d_w // 2, s) + d_w) // s) * s
    return MWDWindow(s=s, span=span, wy=span + 2 * s,
                     z_ws=n_f + d_w + radius,
                     nxp=-(-nx // LANES) * LANES)


def mwd_vmem_bytes(spec: StencilSpec, d_w: int, n_f: int, nx: int,
                   word_bytes: int = DEFAULT_WORD_BYTES) -> int:
    """VMEM bytes one MWD launch needs; the kernel's `vmem_limit_bytes`.

    The tile-padded windows of both parity levels and every coefficient
    stream, plus one f32 (N_F, span, nxp) value per tap and four more for
    the live operands of an in-tile update, plus `COMPILER_RESERVE`.
    """
    w = mwd_window(spec.radius, d_w, n_f, nx, word_bytes)
    windows = (2 + spec.n_coeff_arrays) * w.z_ws * w.wy * w.nxp * word_bytes
    values = (len(spec.taps) + 4) * n_f * w.span * w.nxp * 4
    return windows + values + COMPILER_RESERVE


def vmem_fits(spec: StencilSpec, d_w: int, n_f: int, nx: int,
              chip: devspecs.DeviceSpec | None = None,
              word_bytes: int = DEFAULT_WORD_BYTES) -> bool:
    """VMEM-fit constraint for the auto-tuner: `mwd_vmem_bytes` vs the chip.

    `nx` is the x extent one device holds.  The same byte count is the
    kernel's `vmem_limit_bytes`, so a plan that passes also compiles.
    """
    chip = chip or devspecs.current_spec()
    return mwd_vmem_bytes(spec, d_w, n_f, nx, word_bytes) <= chip.vmem_bytes


# ---------------------------------------------------------------------------
# Eq. 4/5: code balance (bytes / LUP) of the wavefront-diamond pass
# ---------------------------------------------------------------------------

def code_balance(spec: StencilSpec, d_w: int,
                 word_bytes: int = DEFAULT_WORD_BYTES) -> float:
    """Eq. 5: B_C = word*R*[(2*D_w - 2R) + (N_D*D_w + 2R)] / D_w**2  bytes/LUP.

    (The paper's 16 = 2*word at double precision: the extruded diamond volume
    per z-slab is D_w^2/(2R) LUPs and transfers (2D_w-2R)+ (N_D*D_w+2R) words.)
    """
    r = spec.radius
    n_d = spec.n_streams
    lups = d_w * d_w / (2.0 * r)
    words = (2.0 * d_w - 2.0 * r) + (n_d * d_w + 2.0 * r)
    return word_bytes * words / lups


def spatial_code_balance(spec: StencilSpec,
                         word_bytes: int = DEFAULT_WORD_BYTES) -> float:
    """Optimal spatial-blocking code balance, bytes/LUP (the MWD baseline)."""
    return spec.spatial_code_balance(word_bytes)


# Host -> accelerator dispatch latency per pallas_call. The per-row MWD mode
# pays it once per diamond row; the fused single-launch schedule pays it once
# per n_steps advance. Priced into the auto-tuner like the sync term.
T_DISPATCH_S = 5e-6


def batch_amortized_time(t_item_s: float, batch: int,
                         t_dispatch_s: float = T_DISPATCH_S) -> float:
    """Wall time of ONE fused launch advancing `batch` independent grids.

    The B grids of a serving batch share no data, so the steady-state terms
    (compute, VMEM, HBM — the arithmetic-intensity part of the model) scale
    linearly with B; the host dispatch is paid ONCE instead of once per
    request. This is the batched-serving analogue of the paper's intra-tile
    sharing argument: the shared resource here is the launch itself, and the
    per-request overhead drops from T_d to T_d/B.  Sequential serving of the
    same B requests costs ``batch * (t_item_s + t_dispatch_s)``.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    return batch * t_item_s + t_dispatch_s


def batch_amortization(t_item_s: float, batch: int,
                       t_dispatch_s: float = T_DISPATCH_S) -> float:
    """Modeled throughput multiplier of one B-batch launch over B launches.

    ``B*(t + T_d) / (B*t + T_d)`` — >= 1, -> 1 as t dominates and -> B as
    the dispatch dominates (tiny per-request grids).
    """
    return (batch * (t_item_s + t_dispatch_s)
            / batch_amortized_time(t_item_s, batch, t_dispatch_s))


def mwd_tile_bytes(spec: StencilSpec, d_w: int, n_f: int, nz: int, nx: int,
                   word_bytes: int = DEFAULT_WORD_BYTES) -> float:
    """Exact DMA bytes ONE tile moves over its full wavefront sweep.

    Window streams in (both parity buffers + coefficient streams, one
    (N_F, wy, nxp) slab per wavefront step) plus span emissions out (both
    parities, (N_F, span, nxp) per step once the pipeline fills), with the
    tile-aligned extents of `mwd_window`. This is the single source of
    truth for the kernel's per-tile traffic; the repro.core.traffic
    counters and the auto-tuner overhead term below both multiply it by
    their tile counts.
    """
    r = spec.radius
    w = mwd_window(r, d_w, n_f, nx, word_bytes)
    n_j = -(-(r + nz + d_w) // n_f)          # wavefront steps along z
    n_streams_in = 2 + spec.n_coeff_arrays   # both parities + coeff streams
    per_step_in = n_streams_in * n_f * w.wy * w.nxp * word_bytes
    out_steps = max(0, n_j - d_w // n_f)
    per_step_out = 2 * n_f * w.span * w.nxp * word_bytes
    return float(n_j * per_step_in + out_steps * per_step_out)


def mwd_row_overhead_bytes(spec: StencilSpec, d_w: int, n_f: int,
                           grid_shape,
                           word_bytes: int = DEFAULT_WORD_BYTES) -> float:
    """Extra HBM bytes ONE per-row launch moves vs the fused schedule.

    The per-row kernel streams and re-emits every tile of the row, including
    the (at least two) inactive edge tiles that own no diamond spans; the
    fused kernel's active-tile gating skips them, and its aliased parity
    buffers never materialize fresh padded grids between rows. Exact per-run
    counts live in repro.core.traffic.mwd_run_traffic; this closed form is
    the Eq. 5-style term the auto-tuner scores with.
    """
    nz, ny, nx = grid_shape
    n_inactive = 2                           # edge columns -1 and ny//D_w + 1
    return n_inactive * mwd_tile_bytes(spec, d_w, n_f, nz, nx, word_bytes)


def ghostzone_code_balance(spec: StencilSpec, t_b: int, block_y: int,
                           block_z: int,
                           word_bytes: int = DEFAULT_WORD_BYTES) -> float:
    """Code balance of the ghost-zone (overlapped) fused kernel.

    Each T_b-step block reads (block + 2*R*T_b halo)*N_D streams and writes the
    block once; redundant halo cells are re-read by neighbors.
    """
    r, n_d = spec.radius, spec.n_streams
    g = 2 * r * t_b
    reads = n_d * (block_y + g) * (block_z + g)
    writes = 2.0 * block_y * block_z
    lups = t_b * block_y * block_z
    return word_bytes * (reads + writes) / lups


def ghostzone_redundancy(radius: int, t_b: int, block_y: int, block_z: int) -> float:
    """Redundant-compute multiplier of the ghost-zone kernel (>= 1)."""
    total = 0.0
    for t in range(t_b):
        g = 2 * radius * (t_b - 1 - t)
        total += (block_y + g) * (block_z + g)
    return total / (t_b * block_y * block_z)


def super_step_time(t_interior_s: float, t_boundary_s: float,
                    t_exchange_s: float, *, overlap: bool) -> float:
    """Predicted wall time of ONE distributed super-step (Sec. 4.2 analog).

    Both schedules run the same interior/boundary zone split (the swept-cell
    counts come from `stepper.overlap_work`); they differ only in where the
    halo exchange sits in the dataflow:

      synchronous: the exchange is a barrier before any dependent compute,
        so the terms serialize -> t_exchange + t_interior + t_boundary.

      overlapped: the interior advance is dataflow-independent of the
        ppermute pairs, so it proceeds concurrently with the exchange and
        only the boundary-zone completion waits on the landed halos
        -> max(t_interior, t_exchange) + t_boundary.

    The overlapped win saturates at min(t_interior, t_exchange) — exchange
    fully hidden when the interior is the bigger term, which is the
    memory-starved regime the paper targets.
    """
    if overlap:
        return max(t_interior_s, t_exchange_s) + t_boundary_s
    return t_exchange_s + t_interior_s + t_boundary_s


# ---------------------------------------------------------------------------
# ECM-TPU model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EcmPrediction:
    """ECM-TPU runtime terms for one LUP batch (all in seconds)."""

    t_compute: float          # s per LUP batch: vector execution
    t_vmem: float             # s: VMEM<->VREG traffic (overlappable on TPU)
    t_hbm: float              # s: HBM<->VMEM traffic at code balance B_C
    lups: float
    t_latency: float = 0.0    # s: first-access HBM latency floor
    hbm_bytes: float = 0.0    # HBM traffic the prediction priced

    @property
    def t_total(self) -> float:
        """Steady-state runtime bound: max of the overlapped terms."""
        # TPU DMA engines overlap VMEM traffic with compute; HBM DMA overlaps
        # too, so the steady-state bound is the max of the terms (roofline
        # limit); the paper's non-overlapping T_nOL has no TPU analogue
        # because loads don't retire through the scalar pipe. The latency
        # floor joins the max: a launch cannot finish before its first HBM
        # access lands, however little it streams.
        return max(self.t_compute, self.t_vmem, self.t_hbm, self.t_latency)

    @property
    def dominant(self) -> str:
        """The binding term: "compute", "vmem", "hbm" or "latency".

        Small grids whose traffic falls under the spec's ``latency_bytes``
        crossover report "latency" here — the detection that stops them
        being mis-modeled (and mis-tuned) as bandwidth-bound.
        """
        terms = {"compute": self.t_compute, "vmem": self.t_vmem,
                 "hbm": self.t_hbm, "latency": self.t_latency}
        return max(terms, key=terms.get)

    @property
    def glups(self) -> float:
        """Predicted throughput in giga lattice updates per second."""
        return self.lups / self.t_total / 1e9


def ecm_predict(spec: StencilSpec, code_balance_bytes: float, lups: float,
                chip: devspecs.DeviceSpec | None = None,
                word_bytes: int = DEFAULT_WORD_BYTES,
                redundancy: float = 1.0) -> EcmPrediction:
    """ECM-TPU prediction for `lups` updates at the given code balance.

    `redundancy` > 1 prices overlapped (ghost-zone) kernels, which recompute
    halo cells; the memory terms scale with it too since redundant cells are
    streamed through VMEM like real ones. `chip=None` resolves the process
    default device spec.
    """
    chip = chip or devspecs.current_spec()
    flops = spec.flops_per_lup * lups * redundancy
    # VMEM traffic: every LUP streams its stencil reads once through VREGs;
    # approximate with (n_streams + 1) words per LUP (in-VMEM reuse of
    # neighbor loads is handled by the register rotation in the kernel).
    vmem_bytes = (spec.n_streams + 1) * word_bytes * lups * redundancy
    hbm_bytes = code_balance_bytes * lups
    return EcmPrediction(
        t_compute=flops / chip.peak_flops_vpu_f32,
        t_vmem=vmem_bytes / chip.vmem_bw,
        t_hbm=hbm_bytes / chip.hbm_bw,
        lups=lups,
        t_latency=chip.hbm_latency_s if hbm_bytes > 0 else 0.0,
        hbm_bytes=hbm_bytes,
    )


# ---------------------------------------------------------------------------
# Roofline terms (the graded three-term analysis)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """All terms in seconds; inputs are PER-DEVICE quantities."""
    t_compute: float
    t_memory: float
    t_collective: float
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    t_latency: float = 0.0

    @property
    def dominant(self) -> str:
        """Binding term: "compute", "memory", "collective" or "latency"."""
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective, "latency": self.t_latency}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Roofline-limited runtime: the largest of the terms."""
        return max(self.t_compute, self.t_memory, self.t_collective,
                   self.t_latency)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the binding roofline achievable with perfect overlap.

        1.0 means the dominant term fully hides the others (at the roof).
        The latency floor is not summed — it is a floor under the memory
        phase, not an extra serialized phase.
        """
        s = self.t_compute + self.t_memory + self.t_collective
        s = max(s, self.t_latency)
        return self.t_bound / s if s else 0.0


def roofline(flops_per_device: float, bytes_per_device: float,
             coll_bytes_per_device: float,
             chip: devspecs.DeviceSpec | None = None) -> RooflineTerms:
    """The graded roofline terms for per-device FLOPs/bytes/collective.

    Includes the launch latency floor: when ``bytes_per_device`` falls under
    the spec's ``latency_bytes`` crossover the latency term exceeds the
    memory term and `dominant` reports "latency" instead of "memory".
    """
    chip = chip or devspecs.current_spec()
    return RooflineTerms(
        t_compute=flops_per_device / chip.peak_flops_bf16,
        t_memory=bytes_per_device / chip.hbm_bw,
        t_collective=coll_bytes_per_device / chip.ici_bw_per_link,
        flops_per_device=flops_per_device,
        bytes_per_device=bytes_per_device,
        coll_bytes_per_device=coll_bytes_per_device,
        t_latency=chip.hbm_latency_s if bytes_per_device > 0 else 0.0,
    )


# ---------------------------------------------------------------------------
# Calibration / validation (paper Sec. 7-8: confront model with measurement)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EcmCalibration:
    """Per-machine effective ECM constants fitted from measured sweep points.

    The a-priori ECM-TPU model is parameterized by a declarative device
    spec (``specs/*.json``); the machine actually measured (this container:
    CPU interpret mode, elsewhere: a real TPU) realizes different effective
    throughputs.
    The paper's Sec. 7 validation therefore *fits* the phenomenological
    constants to the sweep — the shape of the model (work terms plus a fixed
    dispatch) is the claim under test, the constants are per-machine:

        t(F, B_hbm) = F / flops_per_s + B_hbm / hbm_bytes_per_s + t_dispatch_s

    An additive combination (no overlap) is the conservative ECM composition;
    on machines that do overlap, the fit absorbs the overlap into the
    effective rates. Rates can be ``math.inf`` when the fit finds a term
    contributes nothing (its coefficient went to zero).
    """

    flops_per_s: float         # effective compute throughput (FLOP/s)
    hbm_bytes_per_s: float     # effective memory throughput (B/s)
    t_dispatch_s: float        # fixed per-launch overhead (s)
    n_points: int              # sweep points the fit consumed
    max_rel_err: float         # worst |pred - meas| / meas over the fit set
    spec: str = ""             # device-spec name the fit was taken under

    def predict_s(self, flops: float, hbm_bytes: float) -> float:
        """Calibrated runtime (s) of a launch doing `flops` and `hbm_bytes`."""
        t = self.t_dispatch_s
        if self.flops_per_s != math.inf:
            t += flops / self.flops_per_s
        if self.hbm_bytes_per_s != math.inf:
            t += hbm_bytes / self.hbm_bytes_per_s
        return t


def fit_ecm(points, spec: str | None = None) -> EcmCalibration:
    """Least-squares fit of the ECM constants from measured sweep points.

    `points` is an iterable of ``(flops, hbm_bytes, measured_s)`` triples
    (one per measured launch, e.g. from `repro.launch.sweep`). Solves
    ``t = a*F + b*B + c`` for non-negative ``a, b, c``; a coefficient the
    unconstrained solution drives negative is clamped to zero (that term is
    not observable in the sweep — e.g. all points memory-bound) and the
    remaining terms are re-fitted.  Raises ValueError on an empty point set;
    a single point degenerates to a pure-dispatch fit.  `spec` names the
    device spec the measurements were taken under (default: the process
    default spec); it is recorded on the calibration so persisted artifacts
    (`save_calibration`) stay attributable.
    """
    import numpy as np

    pts = [(float(f), float(b), float(t)) for f, b, t in points]
    if not pts:
        raise ValueError("fit_ecm needs at least one (flops, bytes, t) point")
    design = np.array([[f, b, 1.0] for f, b, _ in pts])
    target = np.array([t for _, _, t in pts])
    active = [0, 1, 2]
    coef = np.zeros(3)
    for _ in range(3):              # clamp-and-refit (at most 3 rounds)
        sol, *_ = np.linalg.lstsq(design[:, active], target, rcond=None)
        coef = np.zeros(3)
        coef[active] = sol
        neg = [i for i in active if coef[i] < 0.0]
        if not neg:
            break
        coef[neg] = 0.0
        active = [i for i in active if i not in neg]
        if not active:
            break
    a, b, c = (max(float(x), 0.0) for x in coef)
    calib = EcmCalibration(
        flops_per_s=(1.0 / a) if a > 0.0 else math.inf,
        hbm_bytes_per_s=(1.0 / b) if b > 0.0 else math.inf,
        t_dispatch_s=c,
        n_points=len(pts),
        max_rel_err=0.0,
        spec=spec if spec is not None else devspecs.current_spec().name,
    )
    worst = 0.0
    for f, bb, t in pts:
        if t > 0.0:
            worst = max(worst, abs(calib.predict_s(f, bb) - t) / t)
    return dataclasses.replace(calib, max_rel_err=worst)


def model_residuals(points, calibration: EcmCalibration | None = None) -> dict:
    """Model-vs-measured residual report over sweep points (Sec. 7 analog).

    `points` is an iterable of dicts with keys ``flops``, ``hbm_bytes``,
    ``measured_s`` and optionally ``key`` (a label) and ``model_s`` (the
    a-priori datasheet prediction).  When `calibration` is None it is fitted
    from the points themselves (`fit_ecm`).

    Returns ``{"n", "calibration", "mean_abs_rel_err", "max_abs_rel_err",
    "bias", "per_point"}`` where residuals are calibrated-vs-measured
    relative errors ``(pred - meas) / meas``, `bias` is their mean (signed),
    and each per-point entry carries ``{key, measured_s, calibrated_s,
    rel_err[, model_s]}``.
    """
    pts = list(points)
    if calibration is None:
        calibration = fit_ecm(
            (p["flops"], p["hbm_bytes"], p["measured_s"]) for p in pts)
    per_point = []
    rels = []
    for p in pts:
        pred = calibration.predict_s(p["flops"], p["hbm_bytes"])
        meas = float(p["measured_s"])
        rel = (pred - meas) / meas if meas > 0.0 else 0.0
        entry = {"key": p.get("key", ""), "measured_s": meas,
                 "calibrated_s": pred, "rel_err": rel}
        if "model_s" in p:
            entry["model_s"] = float(p["model_s"])
        per_point.append(entry)
        rels.append(rel)
    return {
        "n": len(pts),
        "calibration": dataclasses.asdict(calibration),
        "mean_abs_rel_err": (sum(abs(r) for r in rels) / len(rels)
                             if rels else 0.0),
        "max_abs_rel_err": max((abs(r) for r in rels), default=0.0),
        "bias": (sum(rels) / len(rels)) if rels else 0.0,
        "per_point": per_point,
    }


# ---------------------------------------------------------------------------
# Energy model (Fig. 19 analog)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EnergyEstimate:
    """Energy split of one run: incremental core + HBM plus static draw."""

    core_j: float
    hbm_j: float
    static_j: float

    @property
    def total_j(self) -> float:
        """Total energy in joules."""
        return self.core_j + self.hbm_j + self.static_j


def energy(flops: float, hbm_bytes: float, runtime_s: float,
           chip: devspecs.DeviceSpec | None = None) -> EnergyEstimate:
    """Fig. 19 energy model: E = P_static*T + e_flop*F + e_byte*B_hbm."""
    chip = chip or devspecs.current_spec()
    return EnergyEstimate(
        core_j=chip.joules_per_flop * flops,
        hbm_j=chip.joules_per_hbm_byte * hbm_bytes,
        static_j=chip.static_power_w * runtime_s,
    )


# ---------------------------------------------------------------------------
# Per-spec calibration artifacts
# ---------------------------------------------------------------------------

def calibration_path(results_dir: str, spec_name: str) -> str:
    """Canonical artifact path for a spec's calibration: ``ecm-<spec>.json``."""
    import os
    return os.path.join(results_dir, f"ecm-{spec_name}.json")


def save_calibration(calib: EcmCalibration, results_dir: str) -> str:
    """Persist a fitted calibration as the per-spec artifact; returns path.

    The artifact is keyed by the calibration's recorded spec name so fits
    taken under different machine models never clobber each other.
    """
    import json
    import os
    if not calib.spec:
        raise ValueError("calibration has no spec name; fit with fit_ecm(points, spec=...)")
    os.makedirs(results_dir, exist_ok=True)
    path = calibration_path(results_dir, calib.spec)
    with open(path, "w") as f:
        json.dump(dataclasses.asdict(calib), f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def load_calibration(results_dir: str, spec_name: str) -> EcmCalibration | None:
    """Load the persisted calibration for `spec_name`, or None if absent."""
    import json
    import os
    path = calibration_path(results_dir, spec_name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        raw = json.load(f)
    return EcmCalibration(**raw)
