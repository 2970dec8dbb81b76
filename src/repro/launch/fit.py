"""Coefficient-field fitting through the differentiable MWD launch.

The inverse-problem workload the adjoint machinery exists for: recover a
stencil's per-cell coefficient streams from observed forward trajectories
by gradient descent THROUGH the fused kernel — every optimization step runs
one fused MWD advance per observation window forward and one adjoint MWD
advance backward (`repro.kernels.adjoint`), with plans resolved
registry-first exactly like any served workload (the gradient launches
under the ``vjp`` variant key).

Problem setup (seeded, reproducible): truth coefficients from
`ir.make_problem`, observations generated by the REAL forward kernel on
`--windows` independent initial states, initial guess = truth + a
`--perturb`-scaled noise field.  The loss is the interior MSE between the
predicted and observed final levels, averaged over windows; the driver is
the generic `training.steps.make_fit_step` (AdamW + warmup-cosine), jitted
end to end.

  PYTHONPATH=src python -m repro.launch.fit                      # 7pt-var
  PYTHONPATH=src python -m repro.launch.fit --stencil 25pt-const \
      --grid 14,20,16 --steps 2 --max-steps 100
  PYTHONPATH=src python -m repro.launch.fit --gate 10 --out trace.json

`--gate R` turns the run into a pass/fail check: the final loss must sit at
least R times below the initial loss within `--max-steps` optimization
steps (exit code 4 otherwise) — CI's seeded smoke gate.  `--out` writes the
full fit trace as JSON for artifact upload.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import ir
from repro.core import specs as devspecs
from repro.kernels import ops
from repro.launch.telemetry import make_telemetry
from repro.optim import optimizers
from repro.training.steps import make_fit_step


def build_fit(op: ir.StencilOp, grid, *, n_steps: int = 2, windows: int = 2,
              seed: int = 0, perturb: float = 1.0, lr: float = 3e-2,
              warmup: int = 10, max_steps: int = 200, clip: float = 1.0,
              dtype=None, plan=None):
    """Assemble one seeded coefficient-fit problem.

    Returns ``(state, fit_step, loss_fn, truth)``: the initial train state
    (perturbed coefficients + optimizer state), the jitted step, the bare
    loss closure (for the step-0 readout), and the truth streams (for the
    recovery-error metric).  Only per-cell coefficient streams are
    recoverable — compile-time scalars are baked into the kernels as
    constants and carry no gradient — so `op` must have array slots.
    """
    if not op.n_coeff_arrays:
        raise ValueError(
            f"{op.name}: nothing to fit — the op has no per-cell coefficient "
            "streams (compile-time scalars are static, not differentiable)")
    _, coeffs_true = ir.make_problem(op, grid, seed=seed)
    truth, scalars = ir.split_coeffs(op, coeffs_true)
    scalars = tuple(float(x) for x in scalars)
    states = [ir.make_problem(op, grid, seed=seed + 101 + j)[0]
              for j in range(windows)]
    obs = [ops.mwd(op, s, ir.join_coeffs(op, truth, scalars), n_steps,
                   plan=plan, dtype=dtype)
           for s in states]
    r = op.radius

    def loss_fn(params):
        total = 0.0
        for s, o in zip(states, obs):
            pred = ops.mwd_diff(op, s, ir.join_coeffs(op, params, scalars),
                                n_steps, plan=plan, dtype=dtype)
            d = (pred[0] - o[0])[..., r:-r, r:-r, r:-r]
            total = total + jnp.mean(jnp.square(d.astype(jnp.float32)))
        rmse = jnp.sqrt(jnp.mean(jnp.square(params - truth)))
        return total / len(states), {"coeff_rmse": rmse}

    rng = np.random.default_rng(seed + 7)
    noise = jnp.asarray(rng.standard_normal(truth.shape), truth.dtype)
    params0 = truth + perturb * op.coeff_scale * noise

    opt = optimizers.adamw(
        lr=optimizers.warmup_cosine(lr, warmup=warmup, total=max_steps))
    state = {"params": params0, "opt": opt.init(params0),
             "step": jnp.asarray(0, jnp.int32)}
    fit_step = jax.jit(make_fit_step(opt, loss_fn, clip=clip))
    return state, fit_step, loss_fn, truth


def run_fit(op: ir.StencilOp, grid, *, max_steps: int = 200,
            log_every: int = 10, telemetry=None, **kw) -> dict:
    """Drive the fit to `max_steps` and return the trace report."""
    tele = make_telemetry(telemetry)
    state, fit_step, loss_fn, _ = build_fit(op, grid, max_steps=max_steps,
                                            **kw)
    loss0 = float(loss_fn(state["params"])[0])
    trace, t0 = [], time.perf_counter()
    loss = loss0
    for i in range(max_steps):
        state, metrics = fit_step(state)
        loss = float(metrics["loss"])
        rec = {"step": i + 1, "loss": loss,
               "grad_norm": float(metrics["grad_norm"]),
               "coeff_rmse": float(metrics["coeff_rmse"])}
        trace.append(rec)
        if (i + 1) % log_every == 0 or i + 1 == max_steps:
            tele.emit("fit", stencil=op.name, **rec)
    tele.close()
    return {"stencil": op.name, "grid": list(grid), "loss0": loss0,
            "loss": loss, "reduction": loss0 / max(loss, 1e-30),
            "steps": len(trace), "seconds": time.perf_counter() - t0,
            "trace": trace}


def main(argv=None) -> dict:
    """CLI entry point; returns the fit report (tested directly)."""
    compile_cache.enable()
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.fit",
        description="Fit per-cell stencil coefficients through the "
                    "differentiable fused MWD launch")
    ap.add_argument("--stencil", default="7pt-var",
                    help="op to fit (needs per-cell coefficient streams): "
                         "paper op, registered name, or module.path:ATTR")
    ap.add_argument("--grid", type=str, default=None,
                    help="Z,Y,X grid (default: per-stencil sanity scale)")
    ap.add_argument("--steps", type=int, default=2,
                    help="stencil time steps per observation window")
    ap.add_argument("--windows", type=int, default=2,
                    help="independent observation trajectories")
    ap.add_argument("--max-steps", type=int, default=200,
                    help="optimization step budget")
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--perturb", type=float, default=1.0,
                    help="initial-guess noise, in units of the op's "
                         "coefficient scale")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", type=str, default=None,
                    help="stream dtype for the forward/adjoint launches")
    ap.add_argument("--plan", type=str, default=None,
                    help="'auto' resolves forward plans registry-first and "
                         "gradient plans under the vjp variant key")
    ap.add_argument("--gate", type=float, default=0.0,
                    help="require loss0/loss >= GATE (exit 4 otherwise)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--telemetry", type=str, default="stdout",
                    help="'stdout', 'jsonl:<path>', or '' for none")
    ap.add_argument("--out", type=str, default=None,
                    help="write the full fit trace as JSON here")
    ap.add_argument("--spec", type=str, default=None,
                    help="device spec name/path for plan resolution")
    args = ap.parse_args(argv)

    if args.spec:
        devspecs.set_default_spec(args.spec)
    op = ir.resolve_op(args.stencil)
    from repro.core import registry as reg
    grid = (tuple(int(x) for x in args.grid.split(",")) if args.grid
            else reg.default_grid(op))

    report = run_fit(op, grid, n_steps=args.steps, windows=args.windows,
                     seed=args.seed, perturb=args.perturb, lr=args.lr,
                     warmup=args.warmup, clip=args.clip, dtype=args.dtype,
                     plan=args.plan, max_steps=args.max_steps,
                     log_every=args.log_every, telemetry=args.telemetry)
    print(f"fit[{op.name}] grid={grid} loss {report['loss0']:.4e} -> "
          f"{report['loss']:.4e} ({report['reduction']:.1f}x) in "
          f"{report['steps']} steps, {report['seconds']:.1f}s")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    if args.gate and report["reduction"] < args.gate:
        print(f"gate FAILED: {report['reduction']:.2f}x < {args.gate}x")
        raise SystemExit(4)
    return report


if __name__ == "__main__":
    main()
