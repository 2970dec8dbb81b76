#!/usr/bin/env python3
"""Bring-up check: the fused MWD stencil engine on one TPU chip.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the distributed phase only

One process runs every phase, through the entry points a user calls:

  device     platform, device kind, spec; the MWD launch is a Mosaic kernel
  forward    ops.mwd (explicit plan) on 7pt-const 512^3 and 25pt-var 384^3,
             and one plan="auto" resolution against a fresh registry file
  serving    launch.serve.serve_stencil: batched responses == sequential runs
  adjoint    launch.fit.run_fit: 3 optimisation steps through the VJP
  4 chips    distributed.stepper.run_distributed, overlap off and on

Every result is compared on the chip with ops.naive, the un-blocked
reference, within BUDGET. Problems are drawn on the device from a seed.
Wall times are bring-up times (compilation included), not benchmarks.
The last line of stdout is one JSON object; any failure exits nonzero and
prints no such line. Without a TPU the script exits 1 before any phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "smoke")

# |got - naive| <= ATOL + RTOL * |naive|, elementwise (f32 fields of O(1)
# values; the kernel and XLA's reference round the same expression
# differently by a few ulps per step)
BUDGET = {"atol": 1e-5, "rtol": 1e-5}

FULL = {
    "forward": (("7pt-const", 512, 16), ("25pt-var", 384, 8)),
    "auto": ("7pt-var", 128, 8),
    "serve": ("7pt-var", 128, 8, 8, 4),      # op, n, steps, requests, batch
    "fit": ("7pt-var", 128, 3),              # op, n, optimisation steps
    "dist": ("7pt-const", 512, 8, 4),        # op, n, steps, t_block
}


def log(msg: str) -> None:
    print(msg, flush=True)


def device_problem(spec, shape, seed):
    """Seeded random (state, packed coeffs), drawn on the default device.

    Same layout as `ir.make_problem` (N(0,1) levels, coeff_scale * N(0,1)
    streams, the op's default scalars), without a host round trip.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import ir

    k = jax.random.split(jax.random.key(seed), 3)
    cur = jax.random.normal(k[0], shape, jnp.float32)
    prev = (jax.random.normal(k[1], shape, jnp.float32)
            if spec.time_order == 2 else cur)
    arrays = None
    if spec.n_coeff_arrays:
        arrays = spec.coeff_scale * jax.random.normal(
            k[2], (spec.n_coeff_arrays,) + shape, jnp.float32)
    scalars = spec.default_scalars or tuple(
        0.1 / (j + 1) for j in range(spec.n_scalars))
    return (cur, prev), ir.join_coeffs(spec, arrays, scalars)


def within_budget(label, got, want):
    """Assert both levels of `got` are within BUDGET of `want`; log it."""
    import jax.numpy as jnp

    worst_abs = worst_rel = 0.0
    for g, w in zip(got, want):
        d = jnp.abs(g - w)
        ok = bool(jnp.all(d <= BUDGET["atol"] + BUDGET["rtol"] * jnp.abs(w)))
        assert bool(jnp.all(jnp.isfinite(g))), f"{label}: non-finite output"
        worst_abs = max(worst_abs, float(jnp.max(d)))
        worst_rel = max(worst_rel, float(jnp.max(d) / jnp.max(jnp.abs(w))))
        assert ok, (f"{label}: max abs {worst_abs:.3e} outside "
                    f"atol={BUDGET['atol']} rtol={BUDGET['rtol']}")
    log(f"  {label}: vs naive max_abs={worst_abs:.3e} "
        f"max_rel={worst_rel:.3e} (budget atol={BUDGET['atol']} "
        f"rtol={BUDGET['rtol']})")


def phase_device():
    """Platform, device kind, resolved spec; exits when there is no TPU."""
    import jax
    from repro.core import specs

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); this check "
              "runs on the chip only", file=sys.stderr)
        raise SystemExit(1)
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(jax.devices())} spec={specs.current_spec().name}")
    return dev


def mosaic_launch_text(spec, state, coeffs, n_steps, plan):
    """HLO of the compiled MWD launch (asserted to hold a Mosaic kernel)."""
    import jax
    from repro.kernels import ops

    fn = jax.jit(lambda s: ops.mwd(spec, s, coeffs, n_steps, plan=plan))
    return fn.lower(state).compile().as_text()


def phase_forward(cases, auto):
    import jax
    from repro.core import ir, registry
    from repro.core.mwd import MWDPlan
    from repro.kernels import ops

    plan = MWDPlan(d_w=8, n_f=2)
    for i, (name, n, steps) in enumerate(cases):
        spec = ir.OPS[name]
        state, coeffs = device_problem(spec, (n, n, n), seed=i)
        if i == 0:
            text = mosaic_launch_text(spec, state, coeffs, steps, plan)
            assert "tpu_custom_call" in text, "MWD launch is not a kernel"
            log("  MWD launch compiles to a tpu_custom_call")
        got = jax.block_until_ready(ops.mwd(spec, state, coeffs, steps,
                                            plan=plan))
        want = ops.naive(spec, state, coeffs, steps)
        within_budget(f"{name} {n}^3 x{steps} dw8.nf2", got, want)
        del got, want, state, coeffs

    name, n, steps = auto
    spec = ir.OPS[name]
    path = os.path.join(OUT, "plans.json")
    if os.path.exists(path):
        os.remove(path)
    os.environ[registry.ENV_VAR] = path      # no untracked plan file steers
    state, coeffs = device_problem(spec, (n, n, n), seed=7)
    chosen = ops.resolve_plan(spec, state, "auto")
    got = ops.mwd(spec, state, coeffs, steps, plan="auto")
    within_budget(f"{name} {n}^3 x{steps} plan=auto "
                  f"(dw{chosen.d_w}.nf{chosen.n_f})", got,
                  ops.naive(spec, state, coeffs, steps))


def phase_serving(case):
    import numpy as np
    from repro.core import ir
    from repro.core import stencils as stc
    from repro.core.mwd import MWDPlan
    from repro.kernels import ops
    from repro.launch import serve

    name, n, steps, n_req, max_batch = case
    plan = MWDPlan(d_w=8, n_f=2)
    grid = (n, n, n)
    report = serve.serve_stencil(name, grid, n_steps=steps,
                                 n_requests=n_req, max_batch=max_batch,
                                 plan=plan)
    spec = ir.OPS[name]
    assert report["served"] == n_req, report["served"]
    for rid in range(n_req):            # serve_stencil draws seed + rid
        state, coeffs = stc.make_problem(spec, grid, seed=rid)
        got = report["results"][rid]
        seq = ops.mwd(spec, state, coeffs, steps, plan=plan)
        for g, s in zip(got, seq):
            assert np.array_equal(np.asarray(g), np.asarray(s)), \
                f"request {rid}: batched response != its sequential run"
        within_budget(f"request {rid}", got,
                      ops.naive(spec, state, coeffs, steps))
    log(f"  {n_req} responses == their sequential ops.mwd runs (bitwise); "
        f"batch sizes {report['batch_sizes']}")


def phase_adjoint(case):
    import math

    from repro.core import ir
    from repro.core.mwd import MWDPlan
    from repro.launch import fit

    name, n, steps = case
    rep = fit.run_fit(ir.OPS[name], (n, n, n), max_steps=steps, warmup=1,
                      plan=MWDPlan(d_w=8, n_f=2))
    losses = [rep["loss0"]] + [t["loss"] for t in rep["trace"]]
    assert all(math.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    log(f"  fit {name} {n}^3: loss {' -> '.join(f'{v:.6e}' for v in losses)}")


def phase_distributed(case):
    import jax
    import numpy as np
    from repro.core import ir
    from repro.core.mwd import MWDPlan
    from repro.distributed import stepper
    from repro.kernels import ops
    from repro.launch.mesh import make_process_mesh

    name, n, steps, t_block = case
    spec = ir.OPS[name]
    mesh = make_process_mesh()
    log(f"  mesh {dict(mesh.shape)} over {mesh.devices.size} devices")
    state, coeffs = device_problem(spec, (n, n, n), seed=3)
    want = ops.naive(spec, state, coeffs, steps)
    outs = {}
    for overlap in (False, True):
        out = stepper.run_distributed(spec, mesh, state, coeffs, steps,
                                      t_block=t_block,
                                      plan=MWDPlan(d_w=8, n_f=2),
                                      overlap=overlap)
        jax.block_until_ready(out)
        devs = {s.device for s in out[0].addressable_shards}
        assert len(devs) == mesh.devices.size, f"shards on {devs}"
        within_budget(f"{name} {n}^3 x{steps} tb{t_block} overlap={overlap}"
                      f" on {len(devs)} devices",
                      tuple(jax.device_put(o, jax.devices()[0]) for o in out),
                      want)
        outs[overlap] = [np.asarray(o) for o in out]
    for a, b in zip(outs[False], outs[True]):
        assert np.array_equal(a, b), "overlap != sync"
    log("  overlap == sync (bitwise)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip distributed phase")
    args = ap.parse_args(argv)
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: the repro package (src/repro) is not beside this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    from repro import compile_cache

    compile_cache.enable()
    dev = phase_device()
    os.makedirs(OUT, exist_ok=True)
    if args.chips == 4:
        if len(jax.devices()) != 4:
            print(f"chip_smoke: --chips 4 needs 4 devices, found "
                  f"{len(jax.devices())}", file=sys.stderr)
            return 1
        phases = [("distributed", phase_distributed, (FULL["dist"],))]
    else:
        phases = [("forward", phase_forward, (FULL["forward"], FULL["auto"])),
                  ("serving", phase_serving, (FULL["serve"],)),
                  ("adjoint", phase_adjoint, (FULL["fit"],))]
    for label, fn, fargs in phases:
        t0 = time.perf_counter()
        log(f"phase {label}:")
        fn(*fargs)
        log(f"phase {label}: ok, bring-up wall time "
            f"{time.perf_counter() - t0:.1f} s (compilation included; "
            "not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
