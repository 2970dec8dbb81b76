"""Public jit'd kernel API.

Every entry point takes (spec, state, coeffs, n_steps [, plan params]) and is
validated against repro.kernels.ref (pure-jnp oracle) by tests/test_kernels.py
over shape/dtype sweeps.  `spec` is any `StencilOp` — the paper's four or a
user-defined operator — and `coeffs` uses the op's packed convention
(`repro.core.ir.split_coeffs`).

Compile-time scalar coefficients are baked into the kernels as constants
(the paper's codes inline them too), so the wrappers split the packed
coefficients into the canonical (arrays, scalars) form and hoist the scalars
out of the traced arguments (static) before jitting; the stacked per-cell
coefficient stream stays a traced array.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import ir, precision
from repro.core.mwd import MWDPlan
from repro.core.stencils import StencilSpec
from repro.kernels import ref as _ref
from repro.kernels import stencil_fused, stencil_mwd, stencil_sweep
from repro.kernels.adjoint import mwd_diff, mwd_diff_batched  # noqa: F401
# mwd_diff / mwd_diff_batched: forward-identical to mwd / mwd_batched with a
# structural custom_vjp (repro.kernels.adjoint) — the differentiable entry
# points the training stack and `launch.fit` drive.

ref = _ref


def resolve_plan(spec: StencilSpec, state, plan, batch: int = 1) -> MWDPlan:
    """Turn `ops.mwd`'s `plan=` argument into a concrete `MWDPlan`.

    `plan` may be an `MWDPlan` (used as-is) or the string "auto", which
    resolves registry-first against the persistent tuned-plan cache
    (`repro.core.registry`) keyed by the operator's structural fingerprint,
    grid shape, word size, batch size, and the hardware fingerprint —
    falling back to the analytic model-scored auto-tuner on a miss.
    Single-device launches resolve with devices_x=1; `batch` > 1 selects the
    ``b<B>`` key segment so tuned batched plans never collide with B=1
    entries.
    """
    if isinstance(plan, MWDPlan):
        return plan
    if plan != "auto":
        raise ValueError(f"plan must be an MWDPlan or 'auto', got {plan!r}")
    from repro.core import registry
    cur = state[0]
    word = cur.dtype.itemsize
    resolved, _source = registry.resolve_plan(spec, cur.shape[-3:],
                                              word_bytes=word, devices_x=1,
                                              batch=batch)
    return resolved


def _split_coeffs(spec: StencilSpec, coeffs):
    """-> (traced_stacked_arrays_or_None, static_scalar_floats)."""
    arrays, scalars = ir.split_coeffs(spec, coeffs)
    return arrays, tuple(float(x) for x in scalars)


@partial(jax.jit, static_argnames=("spec", "scalars", "n_steps", "bz"))
def _spatial(spec, state, arrays, scalars, n_steps, bz):
    return stencil_sweep.run_sweep(spec, state, arrays, scalars, n_steps,
                                   bz=bz)


def spatial(spec: StencilSpec, state, coeffs, n_steps: int, bz: int = 8):
    """Optimal spatial blocking baseline: n_steps single-sweep kernel passes."""
    arrays, scalars = _split_coeffs(spec, coeffs)
    return _spatial(spec, state, arrays, scalars, n_steps, bz)


@partial(jax.jit,
         static_argnames=("spec", "scalars", "n_steps", "t_block", "bz", "by"))
def _ghostzone(spec, state, arrays, scalars, n_steps, t_block, bz, by):
    return stencil_fused.run_fused(spec, state, arrays, scalars, n_steps,
                                   t_block=t_block, bz=bz, by=by)


def ghostzone(spec: StencilSpec, state, coeffs, n_steps: int,
              t_block: int = 4, bz: int = 16, by: int = 16):
    """Ghost-zone fused temporal blocking (beyond-paper candidate)."""
    arrays, scalars = _split_coeffs(spec, coeffs)
    return _ghostzone(spec, state, arrays, scalars, n_steps, t_block, bz, by)


@partial(jax.jit, static_argnames=("spec", "scalars", "n_steps", "d_w", "n_f",
                                   "fused", "acc"))
def _mwd(spec, state, arrays, scalars, n_steps, d_w, n_f, fused, acc=None):
    return stencil_mwd.mwd_run(spec, state, arrays, scalars, n_steps,
                               d_w=d_w, n_f=n_f, fused=fused, acc_dtype=acc)


@partial(jax.profiler.annotate_function, name="repro.mwd")
def mwd(spec: StencilSpec, state, coeffs, n_steps: int,
        d_w: int = 8, n_f: int = 2, fused: bool = True,
        plan: MWDPlan | str | None = None, dtype=None, acc="auto"):
    """Paper-faithful multi-threaded wavefront diamond blocking.

    fused=True runs the whole compiled schedule in a single pallas_call with
    the parity grids resident in HBM; fused=False launches one pass per
    diamond row (the legacy mode the auto-tuner compares against).

    plan: overrides (d_w, n_f, fused) with an `MWDPlan`, or "auto" to use
    the tuned plan for this (stencil, grid, hardware) from the persistent
    registry — write it with `python -m repro.launch.tune`; misses fall
    back to the model-scored auto-tuner (no measurement).

    dtype: optional stream dtype (anything `core.precision.parse_dtype`
    accepts, e.g. "bf16"). State and coefficient arrays are cast BEFORE
    plan resolution, so the registry key's ``w<word>`` segment and the
    analytic code balance both see the reduced word. The accuracy contract
    is `spec.tolerance(dtype)`; None keeps the inputs' dtype untouched.

    acc: accumulator policy for the in-tile updates — "auto" (f32
    accumulation for sub-32-bit streams), "native", or an explicit dtype
    (`core.precision.resolve_acc`).

    Profiler host spans: ``repro.mwd`` (the whole call), inside it
    ``repro.mwd.plan`` (plan resolution, when `plan` is given) and
    ``repro.mwd.launch`` (dispatch of the jitted program).
    """
    if dtype is not None:
        dt = precision.parse_dtype(dtype)
        state = tuple(jnp.asarray(s, dt) for s in state)
    if plan is not None:
        with TraceAnnotation("repro.mwd.plan"):
            p = resolve_plan(spec, state, plan)
        d_w, n_f, fused = p.d_w, p.n_f, p.fused
    arrays, scalars = _split_coeffs(spec, coeffs)
    if dtype is not None and arrays is not None:
        arrays = jnp.asarray(arrays, dt)
    acc_dt = precision.resolve_acc(state[0].dtype, acc)
    with TraceAnnotation("repro.mwd.launch"):
        return _mwd(spec, state, arrays, scalars, n_steps, d_w, n_f, fused,
                    acc_dt)


@partial(jax.jit, static_argnames=("spec", "scalars", "n_steps", "d_w", "n_f",
                                   "fused", "acc"))
def _mwd_batched(spec, state, arrays, scalars, n_steps, d_w, n_f, fused,
                 acc=None):
    # per-item inputs arrive as tuples (pytrees) and stack INSIDE the jit:
    # XLA fuses the stack with the launch padding, so the host pays one
    # dispatch for the whole batch instead of B small stacking ops
    cur, prev = state
    if isinstance(cur, tuple):
        cur, prev = jnp.stack(cur), jnp.stack(prev)
    if isinstance(arrays, tuple):
        arrays = jnp.stack(arrays)
    return stencil_mwd.mwd_run_batched(spec, (cur, prev), arrays, scalars,
                                       n_steps, d_w=d_w, n_f=n_f, fused=fused,
                                       acc_dtype=acc)


def mwd_batched(spec: StencilSpec, states, coeffs, n_steps: int,
                d_w: int = 8, n_f: int = 2, fused: bool = True,
                plan: MWDPlan | str | None = None, dtype=None, acc="auto"):
    """Advance B independent same-shaped grids in ONE fused MWD launch.

    `states` is either a sequence of B per-request ``(cur, prev)`` pairs or
    an already-stacked pair of ``(B, nz, ny, nx)`` arrays; `coeffs` is a
    **list** of B per-request packed coefficients (validated by
    `ir.split_coeffs_batch` and stacked inside the jit — array streams
    batch, scalars must be shared since the kernel inlines them as
    compile-time constants) or one packed set applied to every request
    (anything that is not a list, e.g. the scalar tuple of a
    const-coefficient op).  Returns batched ``(cur, prev)`` arrays.

    The result is bitwise-equal to a per-item `ops.mwd` loop: the batched
    grid runs entry b's exact B=1 instruction sequence before entry b+1,
    but pays one dispatch + one trace for the whole batch — the serving
    lever (`launch.serve --stencil`) that turns B kernel round-trips into
    one.

    plan: an `MWDPlan` or "auto"; "auto" resolves registry-first under the
    batched ``b<B>`` plan key (see `repro.core.registry.plan_key`).

    dtype / acc: stream dtype and accumulator policy, as in `ops.mwd`.
    A batch whose members disagree on dtype is refused unless `dtype=` is
    given explicitly — `jnp.stack` would otherwise silently promote every
    member to the widest dtype, changing both the traffic (word size) and
    the accuracy contract behind the caller's back.
    """
    dt = precision.parse_dtype(dtype) if dtype is not None else None
    if (isinstance(states, (tuple, list)) and len(states) == 2
            and getattr(states[0], "ndim", 0) == 4):
        cur, prev = states
        if dt is not None:
            cur, prev = jnp.asarray(cur, dt), jnp.asarray(prev, dt)
        b, grid_shape, sdt = cur.shape[0], cur.shape[1:], cur.dtype
    else:
        cur = tuple(s[0] for s in states)   # stacked inside the jit
        prev = tuple(s[1] for s in states)
        member_dts = {x.dtype for x in cur} | {x.dtype for x in prev}
        if dt is None and len(member_dts) > 1:
            raise ValueError(
                f"{spec.name}: mixed-dtype batch "
                f"{sorted(str(d) for d in member_dts)} — stacking would "
                f"silently promote; pass dtype= to cast explicitly or "
                f"batch per dtype")
        if dt is not None:
            cur = tuple(jnp.asarray(x, dt) for x in cur)
            prev = tuple(jnp.asarray(x, dt) for x in prev)
        b, grid_shape, sdt = len(cur), cur[0].shape, cur[0].dtype
    if plan is not None:
        p = resolve_plan(spec, (jax.ShapeDtypeStruct(grid_shape, sdt),),
                         plan, batch=b)
        d_w, n_f, fused = p.d_w, p.n_f, p.fused
    if isinstance(coeffs, list):        # per-request packed coefficients
        if len(coeffs) != b:
            raise ValueError(f"{spec.name}: got {len(coeffs)} coefficient "
                             f"sets for a batch of {b}")
        arrays, scalars = ir.split_coeffs_batch(spec, coeffs)
    else:                       # one packed set shared by the whole batch
        arrays, scalars = ir.split_coeffs(spec, coeffs)
        if arrays is not None:
            arrays = tuple(arrays for _ in range(b))
        scalars = tuple(float(x) for x in scalars)
    if dt is not None and arrays is not None:
        arrays = tuple(jnp.asarray(a, dt) for a in arrays)
    acc_dt = precision.resolve_acc(sdt, acc)
    return _mwd_batched(spec, (cur, prev), arrays, scalars, n_steps,
                        d_w, n_f, fused, acc_dt)


@partial(jax.jit, static_argnames=("spec", "n_steps"))
def naive(spec: StencilSpec, state, coeffs, n_steps: int):
    """Un-blocked reference (paper Fig. 1a)."""
    return _ref.naive_steps(spec, state, coeffs, n_steps)


METHODS = {"naive": naive, "spatial": spatial, "ghostzone": ghostzone,
           "mwd": mwd}
