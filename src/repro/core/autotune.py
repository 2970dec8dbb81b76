"""Auto-tuner (paper Sec. 4.2.2, Fig. 7), model-pruned hill climbing.

Flow, mirroring the paper:
  1. enumerate feasible thread-group factorizations (here: device-group sizes
     tg_x that divide the devices available along x);
  2. for each, local-search hill-climb over (D_w, N_F) seeded at the largest
     D_w whose VMEM footprint (`models.mwd_vmem_bytes`) fits;
  3. score with an injected measure() callback — wall-clock on hardware, the
     ECM/roofline model in dry-run mode (this container).

The machine model is a declarative `repro.core.specs.DeviceSpec`
(``chip=None`` resolves the process default), so the same search runs
against any spec file. Measured searches are spec-aware twice over: the
analytic model under the active spec positions each thread-group's seed
(a free cold-start hill-climb before the first wall-clock call) and prunes
candidates whose predicted score falls below `prune_ratio` of the best
analytic score seen, so the expensive measure() budget concentrates on
contenders.

The tuner dynamically grows the number of measured diamond rows until the
score stabilizes, like the paper's "acceptable performance" loop.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

from repro.core import models, specs as devspecs
from repro.core.mwd import MWDPlan
from repro.core.stencils import StencilSpec


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Winner of one auto-tuning search plus every plan it scored."""

    plan: MWDPlan
    score: float                      # higher is better (e.g. GLUP/s)
    evaluated: tuple[tuple[MWDPlan, float], ...]


def _plan_valid(spec: StencilSpec, plan: MWDPlan) -> bool:
    """Whether the MWD kernel accepts the plan (2R | D_w and N_F | D_w)."""
    return (plan.d_w % (2 * spec.radius) == 0 and plan.n_f >= 1
            and plan.d_w % plan.n_f == 0)


def model_score(spec: StencilSpec, grid_shape, word_bytes: int = 4,
                chip: devspecs.DeviceSpec | None = None,
                batch: int = 1) -> Callable[[MWDPlan], float]:
    """Default scorer: ECM-TPU predicted GLUP/s (per device).

    `batch` models the batched serving launch (`ops.mwd_batched`): one
    dispatch advances `batch` independent grids, so the steady-state terms
    scale by B while the dispatch cost is amortized to T_d/B per request
    (`models.batch_amortized_time`). B=1 keeps the single-request model.
    `chip=None` resolves the process default device spec once, at scorer
    construction — the returned callable is pinned to that spec.
    """
    chip = chip or devspecs.current_spec()
    nz, ny, nx = grid_shape

    def score(plan: MWDPlan) -> float:
        if not _plan_valid(spec, plan):
            return -math.inf
        if not models.vmem_fits(spec, plan.d_w, plan.n_f, nx // plan.tg_x,
                                chip, word_bytes):
            return -math.inf
        bc = models.code_balance(spec, plan.d_w, word_bytes)
        lups = nz * ny * (nx // plan.tg_x)
        pred = models.ecm_predict(spec, bc, lups, chip, word_bytes)
        # fine-grained sync penalty: one ICI neighbor exchange of the tile's
        # x-halo per in-tile time step when tg_x > 1 (the paper's
        # bandwidth-vs-sync tradeoff, priced in)
        t_sync = 0.0
        if plan.tg_x > 1:
            halo_bytes = 2 * spec.radius * nz * plan.d_w * word_bytes
            t_sync = halo_bytes / chip.ici_bw_per_link + 2e-6  # +latency
        if not plan.fused:
            # per-row launch mode: each diamond row re-streams the inactive
            # edge tiles and pays one dispatch; amortized over the H = D_w/2R
            # steps a row pass advances (fused pays neither inter-row cost)
            h = plan.d_w // (2 * spec.radius)
            extra_b = models.mwd_row_overhead_bytes(
                spec, plan.d_w, plan.n_f, (nz, ny, nx // plan.tg_x),
                word_bytes)
            t_sync += (extra_b / chip.hbm_bw + models.T_DISPATCH_S) / h
        # one fused launch advances all B grids: per-item steady-state work
        # x B, ONE dispatch for the whole batch (B=1 degenerates to the
        # single-request launch paying its own dispatch)
        t = models.batch_amortized_time(pred.t_total + t_sync, batch)
        return batch * pred.lups / t / 1e9

    return score


def time_callable(launch: Callable[[], object], *, reps: int = 3,
                  warmup: int = 1, stat: str = "median") -> float:
    """Wall-clock seconds of `launch` over `reps` timed calls.

    THE timing policy of the repo — `warmup` untimed calls (compilation),
    then the `stat` ("median", the default, or "min") of `reps`
    `perf_counter` intervals. `launch` must block until its device work
    completes (`jax.block_until_ready` inside). Everything that reports a
    measured time (`measure_score`, the sweep harness's single-launch and
    distributed legs) goes through here, so a change of policy lands
    everywhere at once. "min" is for RATIO consumers (the scaling gate
    pairs adjacent measurements): scheduler noise on a contended host is
    one-sided positive, so min-of-reps tracks the true cost of each leg
    far more reproducibly than the median.
    """
    import time as _time

    import numpy as np

    if stat not in ("median", "min"):
        raise ValueError(f"stat must be 'median' or 'min', got {stat!r}")
    for _ in range(warmup):
        launch()
    times = []
    for _ in range(reps):
        t0 = _time.perf_counter()
        launch()
        times.append(_time.perf_counter() - t0)
    return float(np.min(times) if stat == "min" else np.median(times))


def time_callable_paired(launch_a: Callable[[], object],
                         launch_b: Callable[[], object], *, reps: int = 7,
                         warmup: int = 2) -> tuple[float, float]:
    """Min-of-reps times of two launches sampled in ABAB interleave.

    For ratio consumers (the scaling gate compares overlapped vs
    synchronous super-steps): timing the two programs in separate
    sessions lets slow host drift between the sessions swamp a
    near-zero true difference, so both are warmed first and then the
    timed reps alternate a/b within the SAME session — drift hits both
    sides equally and the per-side min cancels one-sided scheduler
    noise. Returns ``(t_a, t_b)`` seconds.
    """
    import time as _time

    for _ in range(warmup):
        launch_a()
        launch_b()
    t_a, t_b = [], []
    for _ in range(reps):
        t0 = _time.perf_counter()
        launch_a()
        t_a.append(_time.perf_counter() - t0)
        t0 = _time.perf_counter()
        launch_b()
        t_b.append(_time.perf_counter() - t0)
    return float(min(t_a)), float(min(t_b))


def time_mwd_launch(spec: StencilSpec, states, coeffs, n_steps: int,
                    plan: MWDPlan, *, reps: int = 3, warmup: int = 1) -> float:
    """Median wall-clock seconds of ONE real MWD launch under `plan`.

    The launch primitive shared by the measured auto-tuner
    (`measure_score`) and the grid-size sweep harness
    (`repro.launch.sweep`), so both report the same clock: the launch is
    `ops.mwd` for one problem or `ops.mwd_batched` when `states`/`coeffs`
    hold several, timed under the `time_callable` policy.

    `states` and `coeffs` are parallel lists of per-problem (cur, prev)
    pairs and packed coefficients (length 1 for a single-problem launch).
    """
    import jax

    from repro.kernels import ops          # deferred: keeps core jax-light

    batch = len(states)

    def launch():
        if batch > 1:
            out = ops.mwd_batched(spec, states, coeffs, n_steps,
                                  d_w=plan.d_w, n_f=plan.n_f,
                                  fused=plan.fused)
        else:
            out = ops.mwd(spec, states[0], coeffs[0], n_steps,
                          d_w=plan.d_w, n_f=plan.n_f, fused=plan.fused)
        jax.block_until_ready(out)
        return out

    return time_callable(launch, reps=reps, warmup=warmup)


def measure_score(spec: StencilSpec, grid_shape, word_bytes: int = 4,
                  chip: devspecs.DeviceSpec | None = None, *, n_steps: int = 4,
                  reps: int = 3, warmup: int = 1, seed: int = 0,
                  batch: int = 1, dtype=None) -> Callable[[MWDPlan], float]:
    """Measured scorer: wall-clock GLUP/s of the real `ops.mwd` launch.

    This is the paper's Fig. 7 measurement step: the candidate plan is
    compiled and run as the actual Pallas MWD launch (fused single-launch or
    per-row, whichever `plan.fused` says), timed as the median of `reps`
    calls after `warmup` untimed ones. Infeasible plans (kernel-invalid
    geometry, VMEM overflow per Eq. 3) are pruned by the model *without*
    measuring — the model-pruned search that makes measurement affordable.

    `dtype` sets the stream dtype of the measured problems (default f32,
    the container's measurement dtype) — pass it together with the matching
    `word_bytes` so the analytic VMEM prune sees the same word the launch
    streams. `tg_x > 1` plans are timed on this device's share of the grid,
    `nx // tg_x`.

    `batch` > 1 times the batched serving launch instead: ONE
    `ops.mwd_batched` call advancing `batch` independent problems, so the
    winner persisted under the ``b<B>`` registry key is tuned on the launch
    shape the server actually dispatches.

    The returned callable counts launches in its `measurements` attribute,
    which is how `repro.launch.tune` proves a registry hit measured nothing.
    """
    from repro.core import stencils as st

    chip = chip or devspecs.current_spec()
    nz, ny, nx = grid_shape
    problems: dict[int, tuple] = {}

    def score(plan: MWDPlan) -> float:
        if not _plan_valid(spec, plan):
            return -math.inf
        nx_l = nx // plan.tg_x
        if nx_l <= 2 * spec.radius:
            return -math.inf               # no interior left on this device
        if not models.vmem_fits(spec, plan.d_w, plan.n_f, nx_l, chip,
                                word_bytes):
            return -math.inf
        if nx_l not in problems:
            probs = [st.make_problem(spec, (nz, ny, nx_l), dtype=dtype,
                                     seed=seed + i)
                     for i in range(batch)]
            problems[nx_l] = ([p[0] for p in probs], [p[1] for p in probs])
        states, coeffs = problems[nx_l]
        t = time_mwd_launch(spec, states, coeffs, n_steps, plan,
                            reps=reps, warmup=warmup)
        score.measurements += 1
        lups = nz * ny * nx_l * n_steps * batch
        return lups / t / 1e9

    score.measurements = 0
    return score


def _neighbors(plan: MWDPlan, radius: int,
               d_w_cap: int | None = None) -> list[MWDPlan]:
    step = 2 * radius
    cands = []
    for d_w in (plan.d_w - step, plan.d_w + step):
        if d_w >= step and (d_w_cap is None or d_w <= d_w_cap):
            cands.append(dataclasses.replace(plan, d_w=d_w))
    for n_f in (plan.n_f - 1, plan.n_f + 1, plan.n_f * 2):
        if n_f >= 1 and n_f != plan.n_f:
            cands.append(dataclasses.replace(plan, n_f=n_f))
    # execution mode is part of the search space: fused single-launch
    # schedule vs one launch per diamond row
    cands.append(dataclasses.replace(plan, fused=not plan.fused))
    return cands


def _seed_d_w(spec: StencilSpec, nx: int, chip: devspecs.DeviceSpec,
              d_w_cap: int | None = None, word_bytes: int = 4) -> int:
    """Largest D_w fitting VMEM — the model-pruned starting point."""
    step = 2 * spec.radius
    cap = 4096 if d_w_cap is None else max(step, (d_w_cap // step) * step)
    d_w = step
    while d_w + step <= cap and models.vmem_fits(spec, d_w + step, 1, nx,
                                                 chip, word_bytes):
        d_w += step
    return d_w


def _analytic_climb(analytic: Callable[[MWDPlan], float], seed: MWDPlan,
                    radius: int, d_w_cap: int | None = None,
                    budget: int = 128) -> tuple[MWDPlan, float]:
    """Free hill-climb under the analytic model only; returns (plan, score).

    The measured search's cold start: positions each thread-group's seed at
    the model optimum before the first wall-clock call is spent.
    """
    scored: dict[MWDPlan, float] = {}

    def ev(plan: MWDPlan) -> float:
        if plan not in scored and len(scored) < budget:
            scored[plan] = analytic(plan)
        return scored.get(plan, -math.inf)

    cur, cur_score = seed, ev(seed)
    while True:
        improved = False
        for cand in _neighbors(cur, radius, d_w_cap):
            s = ev(cand)
            if s > cur_score:
                cur, cur_score, improved = cand, s, True
        if not improved:
            break
    return cur, cur_score


def autotune(spec: StencilSpec, grid_shape, devices_x: int = 1,
             measure: Callable[[MWDPlan], float] | None = None,
             chip: devspecs.DeviceSpec | None = None, word_bytes: int = 4,
             max_evals: int = 64, d_w_cap: int | None = None,
             batch: int = 1, prune_ratio: float = 0.25) -> TuneResult:
    """Model-pruned local search for the best MWD plan (paper Fig. 7).

    `measure` scores candidates: `model_score` (analytic, the default) or
    `measure_score` (wall-clock on the real launch — the measured tuning
    path `repro.launch.tune` drives). The default `MWDPlan()` is always
    evaluated first, so the winner never scores below the untuned baseline.

    `chip=None` resolves the process default device spec. When `measure`
    is injected (a measured search), the analytic model under that spec
    does double duty: a free cold-start hill-climb positions each
    thread-group's seed at the model optimum, and candidates whose
    analytic score falls below ``prune_ratio`` times the best analytic
    score seen so far are scored ``-inf`` without measuring (set
    ``prune_ratio=0`` to measure everything). The first candidate (the
    untuned baseline) is always measured.

    `d_w_cap` bounds the diamond width the search may try; measured runs cap
    it at the grid's y extent so the seed (sized for VMEM, Eq. 3) cannot
    dwarf a sanity-scale problem.

    `batch` > 1 tunes for the batched serving launch (`ops.mwd_batched`):
    the default scorer amortizes the dispatch over B grids. It only
    parameterizes the default `model_score`; an injected `measure` callback
    is used as-is.
    """
    chip = chip or devspecs.current_spec()
    nz, ny, nx = grid_shape
    analytic = model_score(spec, grid_shape, word_bytes, chip, batch)
    is_measured = measure is not None
    measure = measure or analytic
    evaluated: dict[MWDPlan, float] = {}
    analytic_ref = -math.inf          # best analytic score seen (prune ref)

    def eval_plan(plan: MWDPlan) -> float:
        nonlocal analytic_ref
        if plan in evaluated:
            return evaluated[plan]
        if len(evaluated) >= max_evals:
            return -math.inf
        if is_measured and prune_ratio > 0.0:
            a = analytic(plan)
            analytic_ref = max(analytic_ref, a)
            # the first candidate sets the reference and is never pruned;
            # later ones must predict at least prune_ratio of the best
            if a < prune_ratio * analytic_ref and a < analytic_ref:
                evaluated[plan] = -math.inf
                return -math.inf
        evaluated[plan] = measure(plan)
        return evaluated[plan]

    # the untuned default is the floor every tuned result must clear
    baseline = MWDPlan()
    best: tuple[float, MWDPlan] = (eval_plan(baseline), baseline)

    # thread-group factorization (Fig. 7 step 2): tg_x over divisors
    tg_sizes = [d for d in range(1, devices_x + 1) if devices_x % d == 0]
    for tg in tg_sizes:
        seed = MWDPlan(d_w=_seed_d_w(spec, nx // tg, chip, d_w_cap,
                                     word_bytes), n_f=1, tg_x=tg)
        if is_measured:
            # cold start: let the free analytic model walk the seed to its
            # optimum before spending wall-clock measurements
            seed, _ = _analytic_climb(analytic, seed, spec.radius, d_w_cap)
        cur, cur_score = seed, eval_plan(seed)
        while True:  # local hill-climb (paper's recursive local search)
            improved = False
            for cand in _neighbors(cur, spec.radius, d_w_cap):
                s = eval_plan(cand)
                if s > cur_score:
                    cur, cur_score, improved = cand, s, True
            if not improved:
                break
        if cur_score > best[0]:
            best = (cur_score, cur)

    return TuneResult(plan=best[1], score=best[0],
                      evaluated=tuple(evaluated.items()))
