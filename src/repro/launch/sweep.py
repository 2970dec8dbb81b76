"""Grid-size sweep harness: the paper's Sec. 7-8 performance study as a CLI.

The paper's core evidence is performance behavior at *varying grid size*,
explained by phenomenological modeling (ECM) and an energy analysis. This
module runs that study against the real kernels: for every point of a
(stencil x grid x execution mode x batch size) lattice it

* resolves the MWD plan registry-first (``plan="auto"`` semantics; pass
  ``--tune measured`` to run the measured auto-tuner per point first,
  warming the persistent plan registry in bulk),
* wall-clock-times the real fused/per-row `ops.mwd` (or `ops.mwd_batched`)
  launch with the same timing primitive the measured auto-tuner uses
  (`repro.core.autotune.time_mwd_launch`),
* records the exact kernel DMA traffic (`repro.core.traffic`, B/LUP), the
  a-priori ECM-TPU model prediction and the Fig. 19 energy split
  (`repro.core.models`), and
* appends the point to a versioned JSON file under ``results/``.

Sweeps are resumable: a point whose key already exists in any
``results/sweep*.json`` next to the target file — measured under the current
hardware fingerprint — is skipped, so an interrupted sweep continues where
it stopped and a finished sweep re-run measures nothing (``--expect-cached``
turns that into a hard exit code for CI). An optional ``--distributed`` leg
times the deep-halo super-stepper (`repro.distributed.stepper`) on the
local mesh for each (stencil, grid).

Render the study with ``python -m benchmarks.experiments``, which turns the
recorded points into the committed ``docs/REPRODUCTION.md`` report.

  PYTHONPATH=src python -m repro.launch.sweep --smoke          # CI profile
  PYTHONPATH=src python -m repro.launch.sweep --sizes 16,32,48 \
      --stencil 7pt-var --modes fused,row --batches 1,4
  PYTHONPATH=src python -m repro.launch.sweep --grid 12,40,16 \
      --tune measured                     # warm the plan registry in bulk

Output: one ``key,cached|measured,t_s,glups,b_per_lup,model_glups`` row per
point plus a summary line (points measured / skipped / total seconds).
"""

from __future__ import annotations

import argparse
import dataclasses
import glob as _glob
import json
import os
import tempfile
import time

from repro import compile_cache
from repro.core import autotune, ir, models, precision, registry as reg
from repro.core import specs as devspecs
from repro.core import stencils as st
from repro.core import traffic
from repro.core.mwd import MWDPlan

SCHEMA_VERSION = 1
DEFAULT_RESULTS = os.path.join("results", "sweep.json")
SMOKE_RESULTS = os.path.join("results", "sweep-smoke.json")
SCALING_RESULTS = os.path.join("results", "sweep-scaling.json")

# CI-scale smoke ladder (interpret mode pays Python per cell, so these are
# deliberately tiny N^3 cubes; pass --sizes/--grid for production scales).
# Keyed by stencil radius: the radius-4 (25-point) operators need y room for
# a D_w = 2R = 8 diamond.
SMOKE_SIZES = {1: (8, 12), 4: (16, 20)}


def point_key(spec: st.StencilSpec, grid_shape, n_steps: int, fused: bool,
              batch: int, word_bytes: int = 4, distributed: bool = False,
              dtype_name: str = "f32", n_devices: int | None = None,
              overlap: bool = False, scaling: str | None = None) -> str:
    """Stable identity of one sweep point (resume skips existing keys).

    Embeds the operator's structural IR fingerprint (same convention as the
    plan registry), the grid, the step count, the execution mode, the batch
    size, and the word size; the optional ``|dist`` suffix separates the
    distributed super-stepper leg from the single-launch point on the same
    problem, and a non-f32 stream dtype appends its short name (``|bf16``)
    so a same-grid-different-dtype point is a distinct key even at an equal
    word size (bf16 vs fp16 are both w2 but different contracts). A scaling
    leg extends the ``|dist`` suffix with its pinned device count, schedule
    and scaling regime (``|dist|d4|ovl|strong``) — the legacy whole-machine
    distributed point's key is unchanged. The hardware fingerprint is NOT
    part of the key — it is stored on the point, and resume treats a
    fingerprint mismatch as a miss.
    """
    nz, ny, nx = grid_shape
    key = (f"{spec.name}@{spec.fingerprint}|{nz}x{ny}x{nx}|s{n_steps}"
           f"|{'fused' if fused else 'row'}|b{batch}|w{word_bytes}")
    if distributed:
        key += "|dist"
        if n_devices is not None:
            key += f"|d{n_devices}"
        if overlap:
            key += "|ovl"
        if scaling:
            key += f"|{scaling}"
    if dtype_name != "f32":
        key += f"|{dtype_name}"
    return key


def ladder(sizes) -> list[tuple[int, int, int]]:
    """Paper-style N^3 grid ladder: one cubic grid per requested size."""
    return [(int(n),) * 3 for n in sizes]


@dataclasses.dataclass(frozen=True)
class PointSpec:
    """One cell of the sweep lattice, before any measurement.

    `n_devices`/`overlap`/`scaling` describe the distributed scaling legs:
    a pinned mesh size (instead of the whole local machine), the overlapped
    vs synchronous super-step schedule, and whether the leg belongs to the
    strong- (fixed global grid) or weak- (fixed per-shard grid) scaling
    ladder. Scaling legs run the jnp super-step path (no MWD plan), so the
    sync/overlap pair differs ONLY in schedule.
    """

    spec: st.StencilSpec
    grid: tuple[int, int, int]
    n_steps: int
    fused: bool
    batch: int
    word_bytes: int
    distributed: bool = False
    dtype_name: str = "f32"
    n_devices: int | None = None
    overlap: bool = False
    scaling: str | None = None

    @property
    def key(self) -> str:
        """The point's identity under `point_key`."""
        return point_key(self.spec, self.grid, self.n_steps, self.fused,
                         self.batch, self.word_bytes, self.distributed,
                         self.dtype_name, self.n_devices, self.overlap,
                         self.scaling)


def model_point(spec: st.StencilSpec, grid, n_steps: int, plan: MWDPlan,
                batch: int, word_bytes: int,
                chip: devspecs.DeviceSpec | None = None) -> dict:
    """Model-side columns of one sweep point (no measurement).

    Returns the exact kernel DMA accounting (`repro.core.traffic`), the
    Eq. 5 idealized code balance, the ECM-TPU time/throughput prediction at
    the *exact* traffic (the implementation's true B/LUP, batch-amortized
    for B > 1), the per-term ECM breakdown with the binding term named
    (``ecm.dominant`` — "latency" for points under the spec's
    ``latency_bytes`` crossover), and the Fig. 19 energy split at the
    predicted runtime. `chip=None` resolves the process default spec.
    """
    import numpy as np

    chip = chip or devspecs.current_spec()
    lups_item = float(np.prod(grid)) * n_steps
    lups = lups_item * batch
    tr = traffic.mwd_run_traffic(spec, grid, n_steps, plan.d_w, plan.n_f,
                                 word_bytes, fused=plan.fused)
    hbm_bytes = tr["bytes"] * batch          # each grid streams its windows
    flops = spec.flops_per_lup * lups
    pred = models.ecm_predict(spec, tr["code_balance"], lups_item, chip,
                              word_bytes)
    t_model = models.batch_amortized_time(pred.t_total, batch)
    energy = models.energy(flops, hbm_bytes, t_model, chip)
    return {
        "lups": lups,
        "flops": flops,
        "traffic": {
            "hbm_bytes": hbm_bytes,
            "b_per_lup": tr["code_balance"],
            "launches": tr["launches"],
        },
        "model": {
            "bc_eq5": models.code_balance(spec, plan.d_w, word_bytes),
            "bc_spatial": models.spatial_code_balance(spec, word_bytes),
            "t_s": t_model,
            "glups": lups / t_model / 1e9,
            "ecm": {
                "t_compute": pred.t_compute,
                "t_vmem": pred.t_vmem,
                "t_hbm": pred.t_hbm,
                "t_latency": pred.t_latency,
                "dominant": pred.dominant,
                "latency_bytes": chip.latency_bytes,
            },
            "energy_j": {
                "core": energy.core_j,
                "hbm": energy.hbm_j,
                "static": energy.static_j,
                "total": energy.total_j,
            },
        },
    }


def _distributed_model(ps: PointSpec, plan: MWDPlan, measured: dict) -> dict:
    """Model columns of a distributed point, COHERENT with its measurement.

    The measured side is the whole run on the global grid (``n_super``
    super-steps, all devices in parallel); the model side must describe the
    same run: total FLOPs/HBM bytes summed over every device's extended
    block and every super-step (the halo redundancy is real work and is
    included), total model time = ``n_super`` serial super-steps (devices
    run concurrently), useful LUPs = the global grid's. Energy is the
    Fig. 19 split of those totals at the model runtime.
    """
    import numpy as np

    shape_e = tuple(measured["local_extended_shape"])
    n_super, n_dev = measured["n_super_steps"], measured["n_devices"]
    per_super = model_point(ps.spec, shape_e, measured["t_block"], plan, 1,
                            ps.word_bytes)
    lups = float(np.prod(ps.grid)) * n_super * measured["t_block"]
    flops = per_super["flops"] * n_super * n_dev
    hbm_bytes = per_super["traffic"]["hbm_bytes"] * n_super * n_dev
    t_model = per_super["model"]["t_s"] * n_super
    energy = models.energy(flops, hbm_bytes, t_model)
    return {
        "lups": lups,
        "flops": flops,
        "traffic": {"hbm_bytes": hbm_bytes,
                    "b_per_lup": hbm_bytes / lups,
                    "launches": per_super["traffic"]["launches"] * n_super},
        "model": {
            "bc_eq5": per_super["model"]["bc_eq5"],
            "bc_spatial": per_super["model"]["bc_spatial"],
            "t_s": t_model,
            "glups": lups / t_model / 1e9,
            "energy_j": {"core": energy.core_j, "hbm": energy.hbm_j,
                         "static": energy.static_j,
                         "total": energy.total_j},
        },
    }


def _scaling_model(ps: PointSpec, measured: dict) -> dict:
    """Model columns of a jnp-path scaling leg, coherent with its schedule.

    The zone-split jnp super-step sweeps interior + boundary cells per
    device per super-step (`stepper.overlap_work` — both schedules sweep
    the same cells; only the exchange dependency differs), each swept cell
    streaming the operator's reads and one write through HBM. The model
    t_s here is the active device spec's roofline of that work; the
    overlap-model
    residuals in the report are instead computed by the renderer from the
    recorded cell/halo columns, calibrated against the measured sync legs
    (`models.super_step_time`).
    """
    import numpy as np

    w = measured["overlap_work"]
    n_super, n_dev = measured["n_super_steps"], measured["n_devices"]
    cells_dev = w["interior_cells"] + w["boundary_cells"]
    lups = float(np.prod(ps.grid)) * n_super * measured["t_block"]
    flops = ps.spec.flops_per_lup * cells_dev * n_super * n_dev
    hbm_bytes = ((ps.spec.n_streams + 1) * ps.word_bytes
                 * cells_dev * n_super * n_dev)
    chip = devspecs.current_spec()
    t_model = n_super * max(
        ps.spec.flops_per_lup * cells_dev / chip.peak_flops_vpu_f32,
        (ps.spec.n_streams + 1) * ps.word_bytes * cells_dev / chip.hbm_bw)
    energy = models.energy(flops, hbm_bytes, t_model)
    return {
        "lups": lups,
        "flops": flops,
        "traffic": {"hbm_bytes": hbm_bytes,
                    "b_per_lup": hbm_bytes / lups,
                    "launches": n_super},
        "model": {
            "bc_eq5": models.spatial_code_balance(ps.spec, ps.word_bytes),
            "bc_spatial": models.spatial_code_balance(ps.spec,
                                                      ps.word_bytes),
            "t_s": t_model,
            "glups": lups / t_model / 1e9,
            "energy_j": {"core": energy.core_j, "hbm": energy.hbm_j,
                         "static": energy.static_j,
                         "total": energy.total_j},
        },
    }


def measure_point(ps: PointSpec, plan: MWDPlan, *, reps: int = 2,
                  warmup: int = 1, seed: int = 0) -> dict:
    """Wall-clock one sweep point: median seconds + GLUP/s of the launch."""
    import numpy as np

    dt = precision.parse_dtype(ps.dtype_name)
    probs = [st.make_problem(ps.spec, ps.grid, dtype=dt, seed=seed + i)
             for i in range(ps.batch)]
    t = autotune.time_mwd_launch(
        ps.spec, [p[0] for p in probs], [p[1] for p in probs], ps.n_steps,
        plan, reps=reps, warmup=warmup)
    lups = float(np.prod(ps.grid)) * ps.n_steps * ps.batch
    return {"t_s": t, "glups": lups / t / 1e9}


def measure_distributed_point(ps: PointSpec, registry: reg.PlanRegistry, *,
                              t_block: int = 2, reps: int = 2,
                              warmup: int = 1,
                              seed: int = 0) -> tuple[dict, MWDPlan | None,
                                                      str]:
    """Time the deep-halo super-stepper leg of one (stencil, grid) point.

    Builds the local mesh (`repro.distributed.elastic.build_mesh`, sized by
    ``ps.n_devices`` when the point pins one), hoists the time-invariant
    coefficient exchange out of the timed loop (`make_coeff_extender` —
    coefficients cross the wire exactly once, same as `run_distributed`),
    compiles the super-step once, and times ``ceil(n_steps / t_block)``
    super-step launches back to back under the shared
    `autotune.time_callable` policy — the steady-state serving cost, with
    compilation excluded by the warmup.

    A legacy distributed point resolves its MWD plan from `registry`
    against the PER-SHARD extended block (the same resolution
    `stepper.run_distributed(plan="auto")` performs); a scaling leg
    (``ps.scaling``) runs the jnp super-step path instead and records the
    swept-cell split (`stepper.overlap_work`) plus the per-super-step halo
    bytes the overlap model consumes. Returns ``(measured, plan, source)``
    — plan is None on the jnp path.
    """
    import jax
    import numpy as np

    from repro.distributed import elastic, halo, stepper

    mesh = elastic.build_mesh(ps.n_devices)
    if ps.scaling:
        # the gate compares overlap/sync pairs of adjacent points; a median
        # of few reps is too jittery for a ratio threshold on a contended
        # host, so scaling legs take extra samples, a second warmup launch,
        # and the min-of-reps statistic (see autotune.time_callable)
        reps, warmup = max(reps, 7), max(warmup, 2)
    state, coeffs = st.make_problem(ps.spec, ps.grid,
                                    dtype=precision.parse_dtype(
                                        ps.dtype_name), seed=seed)
    cur, prev = state
    gs = stepper.GridSharding(mesh)
    shape_e = stepper.local_extended_shape(ps.spec, mesh, ps.grid, t_block)
    if ps.scaling:
        plan, source, scalars = None, "none-jnp", None
    else:
        plan, source = registry.resolve(ps.spec, shape_e,
                                        word_bytes=cur.dtype.itemsize)
        plan = stepper.cap_plan_d_w(ps.spec, plan, shape_e[1])
    prev = jax.device_put(prev if ps.spec.time_order == 2 else cur,
                          gs.sharding())
    cur = jax.device_put(cur, gs.sharding())
    arrays, svec = stepper.canonical_coeffs(ps.spec, coeffs, ps.grid,
                                            cur.dtype)
    if plan is not None:
        scalars = tuple(float(x) for x in svec)
    if ps.spec.n_coeff_arrays:
        arrays = jax.device_put(arrays, gs.sharding(leading=1))
    # one-time coefficient exchange OUTSIDE the timed loop: the timed
    # super-steps ppermute only the solution state
    coeffs_h = stepper.make_coeff_extender(ps.spec, mesh, t_block)(
        (arrays, svec))
    step = stepper.make_super_step(ps.spec, mesh, ps.grid, t_block,
                                   hoisted=True, plan=plan, scalars=scalars,
                                   overlap=ps.overlap)
    n_super = -(-ps.n_steps // t_block)

    def make_launch(fn):
        def launch():
            a, b = cur, prev
            for _ in range(n_super):
                a, b = fn(a, b, coeffs_h)
            jax.block_until_ready((a, b))
        return launch

    launch = make_launch(step)
    paired_sync_t = None
    if ps.scaling and ps.overlap:
        # the gate's ratio needs drift-free pairing: time the overlapped
        # program and its synchronous twin in the same interleaved session
        # (autotune.time_callable_paired) instead of trusting two
        # separately-measured points on a contended host
        step_sync = stepper.make_super_step(ps.spec, mesh, ps.grid, t_block,
                                            hoisted=True, plan=plan,
                                            scalars=scalars, overlap=False)
        t, paired_sync_t = autotune.time_callable_paired(
            launch, make_launch(step_sync), reps=reps, warmup=warmup)
    else:
        t = autotune.time_callable(launch, reps=reps, warmup=warmup,
                                   stat="min" if ps.scaling else "median")
    lups = float(np.prod(ps.grid)) * n_super * t_block
    n_z, n_y = gs.counts()
    local_shape = (ps.grid[0] // n_z, ps.grid[1] // n_y, ps.grid[2])
    g = ps.spec.radius * t_block
    measured = {"t_s": t, "glups": lups / t / 1e9,
                "n_devices": int(mesh.devices.size), "t_block": t_block,
                "n_super_steps": n_super,
                "local_extended_shape": list(shape_e),
                "overlap": ps.overlap,
                "overlap_work": stepper.overlap_work(
                    local_shape, ps.spec.radius, t_block,
                    split_z=n_z > 1, split_y=n_y > 1),
                "halo_bytes": halo.halo_bytes(
                    local_shape, g, cur.dtype.itemsize,
                    2 if ps.spec.time_order == 2 else 1)}
    if ps.scaling:
        measured["scaling"] = ps.scaling
    if paired_sync_t is not None:
        measured["paired_sync_t_s"] = paired_sync_t
    return measured, plan, source


# ---------------------------------------------------------------------------
# Results files: versioned JSON, atomic writes, resume
# ---------------------------------------------------------------------------

def load_results(path: str) -> dict:
    """Load one results file; corrupt/missing/mismatched reads as empty."""
    try:
        with open(path) as f:
            raw = json.load(f)
        if raw.get("version") != SCHEMA_VERSION:
            return {"version": SCHEMA_VERSION, "points": {}}
        raw.setdefault("points", {})
        return raw
    except (OSError, ValueError):
        return {"version": SCHEMA_VERSION, "points": {}}


def save_results(path: str, results: dict) -> None:
    """Atomically persist a results file (tmp + rename, like the registry)."""
    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        os.unlink(tmp)
        raise


def done_keys(results_path: str) -> dict[str, str]:
    """Map of point key -> hw fingerprint over every sweep file in the dir.

    Resume consults the whole ``results/`` directory (any ``sweep*.json``
    sibling of the target file), not just the target: a point measured by an
    earlier differently-named sweep run is still done.
    """
    out: dict[str, str] = {}
    pattern = os.path.join(os.path.dirname(results_path) or ".",
                           "sweep*.json")
    for path in sorted(_glob.glob(pattern)):
        for key, point in load_results(path)["points"].items():
            out[key] = point.get("hw_fingerprint", "")
    return out


# ---------------------------------------------------------------------------
# The sweep driver
# ---------------------------------------------------------------------------

def iter_points(specs, grids, modes, batches, n_steps: int, word_bytes: int,
                distributed: bool = False,
                dtype_name: str = "f32") -> list[PointSpec]:
    """Deterministic sweep lattice: stencil-major, then grid, mode, batch."""
    points = []
    for spec in specs:
        for grid in grids:
            for mode in modes:
                for batch in batches:
                    points.append(PointSpec(spec, tuple(grid), n_steps,
                                            mode == "fused", batch,
                                            word_bytes,
                                            dtype_name=dtype_name))
            if distributed:
                points.append(PointSpec(spec, tuple(grid), n_steps, True, 1,
                                        word_bytes, distributed=True,
                                        dtype_name=dtype_name))
    return points


def run_point(ps: PointSpec, registry: reg.PlanRegistry, *, reps: int,
              warmup: int, tune: str = "none", tune_max_evals: int = 12,
              seed: int = 0) -> dict:
    """Measure one sweep point end to end and return the recorded dict.

    Plan resolution is registry-first (``plan="auto"`` semantics). With
    ``tune="measured"`` / ``tune="model"`` the point first runs the
    measured / analytic auto-tuner through `repro.launch.tune.tune_one`,
    persisting the winner — the bulk registry-warming path.
    """
    from repro.launch import tune as tune_cli

    if ps.distributed:
        measured, plan, source = measure_distributed_point(
            ps, registry, reps=reps, warmup=warmup, seed=seed)
        modeled = (_scaling_model(ps, measured) if ps.scaling
                   else _distributed_model(ps, plan, measured))
        plan_source = source
    else:
        if tune != "none":
            rep = tune_cli.tune_one(ps.spec, ps.grid, registry,
                                    word_bytes=ps.word_bytes,
                                    measured=tune == "measured",
                                    max_evals=tune_max_evals,
                                    batch=ps.batch)
            plan, plan_source = rep["plan"], f"tuned:{rep['source']}"
        else:
            plan, plan_source = registry.resolve(
                ps.spec, ps.grid, word_bytes=ps.word_bytes, batch=ps.batch)
        plan = dataclasses.replace(plan, fused=ps.fused)
        modeled = model_point(ps.spec, ps.grid, ps.n_steps, plan, ps.batch,
                              ps.word_bytes)
        measured = measure_point(ps, plan, reps=reps, warmup=warmup,
                                 seed=seed)
    point = {
        "key": ps.key,
        "stencil": ps.spec.name,
        "op_fingerprint": ps.spec.fingerprint,
        "grid": list(ps.grid),
        "n_steps": ps.n_steps,
        "mode": "fused" if ps.fused else "row",
        "batch": ps.batch,
        "word_bytes": ps.word_bytes,
        "dtype": ps.dtype_name,
        "distributed": ps.distributed,
        "plan": dataclasses.asdict(plan) if plan is not None else None,
        "plan_source": plan_source,
        "measured": measured,
        "spec": devspecs.current_spec().name,
        "hw_fingerprint": devspecs.fingerprint(),
    }
    point.update(modeled)
    return point


def run_sweep(specs, grids, *, modes=("fused",), batches=(1,),
              n_steps: int = 2, reps: int = 2, warmup: int = 1,
              results_path: str = DEFAULT_RESULTS, resume: bool = True,
              tune: str = "none", distributed: bool = False,
              word_bytes: int = 4, registry: reg.PlanRegistry | None = None,
              verbose: bool = True, dtype_name: str = "f32") -> dict:
    """Run (or resume) a sweep and persist every point as it completes.

    Returns a summary dict: ``n_measured``, ``n_skipped``, ``seconds``,
    ``results_path`` and the target file's full point map. Points already
    present under the current hardware fingerprint in any sibling
    ``results/sweep*.json`` are skipped when `resume`; stale points (other
    fingerprint) are re-measured and overwritten.

    dtype_name: stream dtype of every point (``--dtype``); the problems are
    generated at that dtype and `word_bytes` should be its word size so the
    plan registry and the traffic/model columns see the reduced word.
    """
    points = iter_points(specs, grids, modes, batches, n_steps, word_bytes,
                         distributed, dtype_name)
    return run_sweep_points(points, registry=registry or
                            reg.default_registry(),
                            results_path=results_path, resume=resume,
                            reps=reps, warmup=warmup, tune=tune,
                            verbose=verbose)


def calibration_summary(points) -> str:
    """One-line `fit_ecm` summary over measured points ("" if too few)."""
    pts = [(p["flops"], p["traffic"]["hbm_bytes"], p["measured"]["t_s"])
           for p in points if not p.get("distributed")]
    if len(pts) < 3:
        return ""
    c = models.fit_ecm(pts)
    return (f"flops/s={c.flops_per_s:.3e} hbm_B/s={c.hbm_bytes_per_s:.3e} "
            f"dispatch={c.t_dispatch_s * 1e3:.2f}ms "
            f"max_rel_err={c.max_rel_err:.0%}")


def smoke_profile() -> dict:
    """The CI smoke sweep: all four paper stencils on tiny N^3 ladders.

    Both execution modes per grid, one batched (B=2) point and one
    distributed super-stepper point for the radius-1 constant stencil, so
    every results-schema variant appears in the committed smoke file.
    """
    return {
        "specs": list(st.SPECS.values()),
        "modes": ("fused", "row"),
        "batches": (1,),
        "n_steps": 2,
        "reps": 2,
    }


def _smoke_points(word_bytes: int) -> list[PointSpec]:
    prof = smoke_profile()
    points = []
    for spec in prof["specs"]:
        grids = ladder(SMOKE_SIZES.get(spec.radius, SMOKE_SIZES[4]))
        points += iter_points([spec], grids, prof["modes"], prof["batches"],
                              prof["n_steps"], word_bytes)
    seven = st.SPECS["7pt-const"]
    n0 = SMOKE_SIZES[1][0]
    points.append(PointSpec(seven, (n0,) * 3, prof["n_steps"], True, 2,
                            word_bytes))
    points.append(PointSpec(seven, (n0,) * 3, prof["n_steps"], True, 1,
                            word_bytes, distributed=True))
    # reduced-precision leg: one bf16 fused point per stencil at the first
    # ladder size — the bf16-vs-f32 B/LUP rows the report's comparison
    # table and the CI precision gate consume
    bf16_w = precision.word_bytes("bf16")
    for spec in prof["specs"]:
        n = SMOKE_SIZES.get(spec.radius, SMOKE_SIZES[4])[0]
        points.append(PointSpec(spec, (n,) * 3, prof["n_steps"], True, 1,
                                bf16_w, dtype_name="bf16"))
    return points


SCALING_DEVICE_LADDER = (1, 2, 4, 8)


def scaling_points(word_bytes: int = 4, *,
                   device_ladder=SCALING_DEVICE_LADDER,
                   n_steps: int = 8) -> list[PointSpec]:
    """The strong/weak scaling lattice (``--scaling``).

    For each case stencil: a strong leg (global grid fixed at the ladder's
    top weak grid, shards shrink as devices grow) and a weak leg (per-shard
    grid fixed, the global grid grows with the ladder), each measured under
    BOTH super-step schedules so every (stencil, grid, devices) rung yields
    an overlapped/synchronous throughput pair — the ratio
    `benchmarks.scaling_gate` enforces and the overlap-model residual
    section of the report explains.

    `plan_mesh` keeps 'model' (grid-y) as the minor axis at these counts,
    so every rung splits y only; the per-shard grids are sized so the zone
    split stays feasible at the top rung (local ny > 2g at t_block=2) AND
    large enough that a super-step costs well above timer resolution — at
    toy sizes the sync/overlap pair ratio is pure noise.
    """
    cases = [(st.SPECS["7pt-const"], (32, 32, 32)),
             (st.SPECS["25pt-const"], (32, 32, 32))]
    n_max = max(device_ladder)
    points = []
    for spec, per_dev in cases:
        nz, ny, nx = per_dev
        strong = (nz, ny * n_max, nx)
        for n in device_ladder:
            for scaling, grid in (("strong", strong),
                                  ("weak", (nz, ny * n, nx))):
                for overlap in (False, True):
                    points.append(PointSpec(
                        spec, grid, n_steps, True, 1, word_bytes,
                        distributed=True, n_devices=n, overlap=overlap,
                        scaling=scaling))
    return points


def main(argv=None) -> dict:
    """CLI entry point; returns the sweep summary (tested directly)."""
    compile_cache.enable()
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.sweep",
        description="Grid-size sweep: measured GLUP/s + exact B/LUP + "
                    "model predictions into versioned results/ JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: a FIXED lattice (all four paper "
                         "stencils on tiny N^3 ladders, both modes, one "
                         "batched + one distributed point, 2 steps); "
                         "lattice flags (--stencil/--sizes/--grid/--modes/"
                         "--batches/--steps/--distributed) are rejected, "
                         "timing flags (--reps/--warmup) apply")
    ap.add_argument("--scaling", action="store_true",
                    help="FIXED strong/weak scaling lattice: overlapped vs "
                         "synchronous super-step pairs over the "
                         f"{'x'.join(map(str, SCALING_DEVICE_LADDER))} "
                         "device ladder (jnp path; results default "
                         f"{SCALING_RESULTS}); lattice flags are rejected "
                         "as with --smoke")
    ap.add_argument("--stencil", action="append",
                    help="stencil(s) to sweep: paper op, registered custom "
                         "op, or module.path:ATTR (default: all four)")
    ap.add_argument("--op-module", default=None,
                    help="import this module first (it registers custom "
                         "StencilOps via repro.core.ir.register)")
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma list of N for an N^3 grid ladder "
                         "(paper-style), e.g. 16,32,48")
    ap.add_argument("--grid", action="append",
                    help="explicit Z,Y,X grid (repeatable; combined with "
                         "--sizes)")
    ap.add_argument("--modes", type=str, default="fused",
                    help="comma list from {fused,row}")
    ap.add_argument("--batches", type=str, default="1",
                    help="comma list of serving batch sizes B (one "
                         "ops.mwd_batched launch advances B grids)")
    ap.add_argument("--steps", type=int, default=2,
                    help="time steps each measured launch advances")
    ap.add_argument("--reps", type=int, default=2,
                    help="timed launches per point (median)")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--dtype", type=str, default="f32",
                    help="stream dtype of every point (f32/bf16/fp16); "
                         "problems are generated at this dtype and the "
                         "word size follows it — the reduced-precision "
                         "sweep leg (--smoke always includes a built-in "
                         "bf16 leg)")
    ap.add_argument("--word-bytes", type=int, default=None,
                    help="override the stream word size recorded on each "
                         "point (default: derived from --dtype)")
    ap.add_argument("--results", type=str, default=None,
                    help=f"results file (default {DEFAULT_RESULTS}, smoke "
                         f"{SMOKE_RESULTS}); resume scans its directory")
    ap.add_argument("--no-resume", dest="resume", action="store_false",
                    help="re-measure every point even if already recorded")
    ap.add_argument("--tune", choices=("none", "model", "measured"),
                    default="none",
                    help="auto-tune each point's plan first and persist it "
                         "(bulk registry warming); 'none' resolves "
                         "registry-first with the analytic fallback")
    ap.add_argument("--distributed", action="store_true",
                    help="add a deep-halo super-stepper point per "
                         "(stencil, grid) on the local mesh")
    ap.add_argument("--registry", type=str, default=None,
                    help=f"plan registry path (default ${reg.ENV_VAR} or "
                         f"{reg.DEFAULT_PATH})")
    ap.add_argument("--expect-cached", action="store_true",
                    help="exit 1 if any point had to be measured (CI gate "
                         "that a finished sweep resumes to zero work)")
    ap.add_argument("--spec", type=str, default=None,
                    help="device spec name or spec-file path the model "
                         "columns price against (default: "
                         f"$REPRO_DEVICE_SPEC or {devspecs.DEFAULT_SPEC_NAME})")
    args = ap.parse_args(argv)

    if args.spec:
        devspecs.set_default_spec(args.spec)
    if args.op_module:
        import importlib
        importlib.import_module(args.op_module)
    registry = (reg.PlanRegistry(args.registry) if args.registry
                else reg.default_registry())
    results_path = args.results or (
        SMOKE_RESULTS if args.smoke
        else SCALING_RESULTS if args.scaling else DEFAULT_RESULTS)
    dtype_name = precision.dtype_name(args.dtype)
    word_bytes = (args.word_bytes if args.word_bytes is not None
                  else precision.word_bytes(dtype_name))

    if args.smoke or args.scaling:
        fixed = "--smoke" if args.smoke else "--scaling"
        clash = [f for f, v, d in (
            ("--smoke --scaling", args.smoke and args.scaling, False),
            ("--stencil", args.stencil, None), ("--sizes", args.sizes, None),
            ("--grid", args.grid, None), ("--modes", args.modes, "fused"),
            ("--batches", args.batches, "1"), ("--steps", args.steps, 2),
            ("--dtype", dtype_name, "f32"),
            ("--distributed", args.distributed, False)) if v != d]
        if clash:
            ap.error(f"{fixed} runs a fixed lattice; drop {' '.join(clash)}")
        points = (_smoke_points(word_bytes) if args.smoke
                  else scaling_points(word_bytes))
        summary = run_sweep_points(points, registry=registry,
                                   results_path=results_path,
                                   resume=args.resume, reps=args.reps,
                                   warmup=args.warmup, tune=args.tune)
    else:
        specs = [ir.resolve_op(n) for n in (args.stencil or st.SPECS)]
        grids = ladder(args.sizes.split(",")) if args.sizes else []
        for g in args.grid or []:
            grids.append(tuple(int(x) for x in g.split(",")))
        if not grids:
            grids = ladder((8, 12, 16))
        summary = run_sweep(
            specs, grids, modes=tuple(args.modes.split(",")),
            batches=tuple(int(b) for b in args.batches.split(",")),
            n_steps=args.steps, reps=args.reps, warmup=args.warmup,
            results_path=results_path, resume=args.resume, tune=args.tune,
            distributed=args.distributed, word_bytes=word_bytes,
            registry=registry, dtype_name=dtype_name)
    if args.expect_cached and summary["n_measured"]:
        raise SystemExit(
            f"--expect-cached: {summary['n_measured']} point(s) were "
            f"measured instead of resumed from {results_path}")
    return summary


def run_sweep_points(points, *, registry: reg.PlanRegistry,
                     results_path: str, resume: bool = True, reps: int = 2,
                     warmup: int = 1, tune: str = "none",
                     verbose: bool = True) -> dict:
    """`run_sweep` over an explicit, pre-built point list (smoke profile).

    Besides the per-point records, a finished run re-fits the ECM
    calibration over every single-launch point in the file and persists it
    as the per-spec artifact ``<results dir>/ecm-<spec>.json``
    (`models.save_calibration`) whenever at least three such points exist.
    """
    results = load_results(results_path)
    results["hw_fingerprint"] = devspecs.fingerprint()
    done = done_keys(results_path) if resume else {}
    fp = devspecs.fingerprint()
    n_measured = n_skipped = 0
    t0 = time.perf_counter()
    for ps in points:
        if done.get(ps.key) == fp:
            n_skipped += 1
            if verbose:
                print(f"{ps.key},cached")
            continue
        point = run_point(ps, registry, reps=reps, warmup=warmup, tune=tune)
        results["points"][ps.key] = point
        save_results(results_path, results)
        n_measured += 1
        if verbose:
            print(f"{ps.key},measured,{point['measured']['t_s']:.4f},"
                  f"{point['measured']['glups']:.5f},"
                  f"{point['traffic']['b_per_lup']:.2f},"
                  f"{point['model']['glups']:.2f}")
    summary = {"n_measured": n_measured, "n_skipped": n_skipped,
               "seconds": time.perf_counter() - t0,
               "results_path": results_path, "points": results["points"]}
    calib_pts = [(p["flops"], p["traffic"]["hbm_bytes"],
                  p["measured"]["t_s"])
                 for p in results["points"].values()
                 if not p.get("distributed")]
    if len(calib_pts) >= 3:
        calib = models.fit_ecm(calib_pts)
        summary["calibration_path"] = models.save_calibration(
            calib, os.path.dirname(results_path) or ".")
    if verbose:
        calib_line = calibration_summary(results["points"].values())
        print(f"# {n_measured} measured, {n_skipped} cached -> "
              f"{results_path} ({summary['seconds']:.1f}s); "
              f"registry {registry.stats()}"
              + (f"; fit {calib_line}" if calib_line else ""))
    return summary


if __name__ == "__main__":
    main()
