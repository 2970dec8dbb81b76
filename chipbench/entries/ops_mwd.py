"""Entry adapter: `repro.kernels.ops.mwd` on one chip.

Each call advances the previous call's output by ``steps_per_call`` steps
through the program's public entry, with the plan policy of the traffic
file (``"auto"``, or a pinned ``{"d_w": .., "n_f": ..}``). The dispatch is
wrapped in the host span ``bench.call`` and the wait for the result in
``bench.wait``.
"""

from __future__ import annotations

import math

import jax


def _plan_arg(traffic: dict):
    from repro.core.mwd import MWDPlan

    plan = traffic["plan"]
    if plan == "auto":
        return "auto"
    return MWDPlan(d_w=plan["d_w"], n_f=plan["n_f"])


def _program(config: dict, traffic: dict):
    """The timed call as a function of (state, coefficient arrays)."""
    from repro.core import ir
    from repro.kernels import ops

    spec = ir.OPS[config["op"]]
    scalars = tuple(config["coefficients"]["scalars"])
    plan = _plan_arg(traffic)

    def call(state, arrays):
        return ops.mwd(spec, state, ir.join_coeffs(spec, arrays, scalars),
                       traffic["steps_per_call"], plan=plan)
    return call


def shardings(config: dict, traffic: dict, devices):
    """Where the draw places state and coefficients: the first device."""
    del config, traffic
    dev = jax.sharding.SingleDeviceSharding(devices[0])
    return dev, dev


class Entry:
    """The timed call of one cell, with the plan it resolved."""

    def __init__(self, config: dict, traffic: dict, devices, arrays):
        from repro.core import ir, registry

        self.config, self.traffic, self.arrays = config, traffic, arrays
        self.spec = ir.OPS[config["op"]]
        self.n_steps = traffic["steps_per_call"]
        self.grid = tuple(traffic["grid"])
        self.lups_per_call = math.prod(self.grid) * self.n_steps
        self.coeffs = ir.join_coeffs(self.spec, arrays,
                                     tuple(config["coefficients"]["scalars"]))
        self.plan = _plan_arg(traffic)
        if self.plan == "auto":
            plan, source = registry.resolve_plan(
                self.spec, self.grid, word_bytes=config["word_bytes"],
                devices_x=1)
        else:
            plan, source = self.plan, "pinned by the traffic file"
        self.resolved = plan
        self.plan_text = f"dw{plan.d_w}.nf{plan.n_f} (plan_source {source})"

    def compiled_call(self, compiler_options: dict):
        """The timed call as one program compiled with `compiler_options`.

        The traced run's phase session runs it (`chipbench.run`); the
        window never does.
        """
        fn = jax.jit(_program(self.config, self.traffic),
                     compiler_options=compiler_options)
        return lambda state: fn(state, self.arrays)

    def call(self, state):
        """One timed call: dispatch, then wait for both levels."""
        from repro.kernels import ops

        with jax.profiler.TraceAnnotation("bench.call"):
            out = ops.mwd(self.spec, state, self.coeffs, self.n_steps,
                          plan=self.plan)
        with jax.profiler.TraceAnnotation("bench.wait"):
            return jax.block_until_ready(out)

    def program_counts(self) -> str:
        """The program's own DMA count for one call (not a metric)."""
        from repro.core import traffic

        t = traffic.mwd_run_traffic(self.spec, self.grid, self.n_steps,
                                    self.resolved.d_w, self.resolved.n_f)
        return (f"program's DMA count (core/traffic.py, not a metric): "
                f"{t['bytes']:.6g} B per call, {t['code_balance']:.4f} B/LUP, "
                f"{t['tiles']} active tiles")


def prepare(config: dict, traffic: dict, devices, arrays) -> Entry:
    """The cell's timed call over coefficients `arrays` (already placed)."""
    return Entry(config, traffic, devices, arrays)


def lowerables(config: dict, traffic: dict, devices):
    """(label, jitted fn, abstract args) of the timed call, for AOT compiles.

    `devices` may be described (not attached) chips.
    """
    import jax.numpy as jnp

    from repro.core import ir, registry

    spec = ir.OPS[config["op"]]
    grid = tuple(traffic["grid"])
    sh = jax.sharding.SingleDeviceSharding(devices[0])
    dt = jnp.dtype(config["dtype"])
    state = (jax.ShapeDtypeStruct(grid, dt, sharding=sh),) * 2
    n_arr = config["coefficients"]["arrays"]
    arrays = (jax.ShapeDtypeStruct((n_arr,) + grid, dt, sharding=sh)
              if n_arr else None)
    plan = _plan_arg(traffic)
    shown, source = ((plan, "pinned") if plan != "auto" else
                     registry.resolve_plan(spec, grid,
                                           word_bytes=config["word_bytes"]))
    label = f"ops.mwd dw{shown.d_w}.nf{shown.n_f} ({source})"
    return [(label, jax.jit(_program(config, traffic)), (state, arrays))]
