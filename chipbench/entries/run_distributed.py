"""Entry adapter: `repro.distributed.stepper.run_distributed` on a mesh.

The traffic file names the mesh (``{"data": 1, "model": 4}``: grid z over
``data``, grid y over ``model``), ``t_block``, ``overlap`` and the plan
policy. Each call advances the previous call's sharded output by
``steps_per_call`` steps through the program's public entry. The dispatch
is wrapped in the host span ``bench.call`` and the wait for the result in
``bench.wait``.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(traffic: dict, devices) -> Mesh:
    """The traffic file's ("data", "model") mesh over the first devices."""
    shape = (traffic["mesh"]["data"], traffic["mesh"]["model"])
    n = math.prod(shape)
    if len(devices) < n:
        raise ValueError(f"mesh {traffic['mesh']} needs {n} devices, "
                         f"{len(devices)} present")
    grid = np.empty(shape, dtype=object)
    for i, d in enumerate(devices[:n]):
        grid[i // shape[1], i % shape[1]] = d
    return Mesh(grid, ("data", "model"))


def shardings(config: dict, traffic: dict, devices):
    """Grid z over "data", y over "model": how the stepper shards its grids."""
    del config
    mesh = make_mesh(traffic, devices)
    return (NamedSharding(mesh, P("data", "model", None)),
            NamedSharding(mesh, P(None, "data", "model", None)))


def _plan_arg(traffic: dict):
    from repro.core.mwd import MWDPlan

    plan = traffic["plan"]
    if plan == "auto":
        return "auto"
    return MWDPlan(d_w=plan["d_w"], n_f=plan["n_f"])


class Entry:
    """The timed call of one cell, with the plan it resolved."""

    def __init__(self, config: dict, traffic: dict, devices, arrays):
        from repro.core import ir, registry
        from repro.distributed import stepper

        self.spec = ir.OPS[config["op"]]
        self.mesh = make_mesh(traffic, devices)
        self.n_steps = traffic["steps_per_call"]
        self.t_block = traffic["t_block"]
        self.overlap = traffic["overlap"]
        self.grid = tuple(traffic["grid"])
        self.lups_per_call = math.prod(self.grid) * self.n_steps
        self.coeffs = ir.join_coeffs(self.spec, arrays,
                                     tuple(config["coefficients"]["scalars"]))
        self.plan = _plan_arg(traffic)
        shape_e = stepper.local_extended_shape(self.spec, self.mesh,
                                               self.grid, self.t_block)
        if self.plan == "auto":
            plan, source = registry.resolve_plan(
                self.spec, shape_e, word_bytes=config["word_bytes"],
                devices_x=1)
            plan = stepper.cap_plan_d_w(self.spec, plan, shape_e[1])
        else:
            plan, source = self.plan, "pinned by the traffic file"
        ovl = (stepper.overlap_feasible(self.spec, self.mesh, self.grid,
                                        self.t_block)
               if self.overlap == "auto" else self.overlap)
        self.resolved = plan
        self.shape_e = shape_e
        self.plan_text = (f"dw{plan.d_w}.nf{plan.n_f} (plan_source {source}) "
                          f"on the {shape_e} shard block, overlap={ovl}")

    def call(self, state):
        """One timed call: dispatch, then wait for both sharded levels."""
        from repro.distributed import stepper

        with jax.profiler.TraceAnnotation("bench.call"):
            out = stepper.run_distributed(self.spec, self.mesh, state,
                                          self.coeffs, self.n_steps,
                                          t_block=self.t_block,
                                          plan=self.plan,
                                          overlap=self.overlap)
        with jax.profiler.TraceAnnotation("bench.wait"):
            return jax.block_until_ready(out)

    def program_counts(self) -> str:
        """The program's own DMA count for one shard's kernel launch."""
        from repro.core import traffic

        t = traffic.mwd_run_traffic(self.spec, self.shape_e, self.t_block,
                                    self.resolved.d_w, self.resolved.n_f)
        return (f"program's DMA count (core/traffic.py, not a metric): "
                f"{t['bytes']:.6g} B per full-block launch of one shard "
                f"({self.t_block} steps), {t['code_balance']:.4f} B/LUP")


def prepare(config: dict, traffic: dict, devices, arrays) -> Entry:
    """The cell's timed call over coefficients `arrays` (already sharded)."""
    return Entry(config, traffic, devices, arrays)


def lowerables(config: dict, traffic: dict, devices):
    """(label, jitted fn, abstract args) of the programs one call runs.

    Mirrors what `run_distributed` builds for a full call: the one-time
    coefficient extension and the (overlapped or synchronous) super-step.
    `devices` may be described (not attached) chips.
    """
    import jax.numpy as jnp

    from repro.core import ir, registry
    from repro.distributed import stepper

    spec = ir.OPS[config["op"]]
    mesh = make_mesh(traffic, devices)
    grid = tuple(traffic["grid"])
    tb = traffic["t_block"]
    dt = jnp.dtype(config["dtype"])
    st_sh, arr_sh = shardings(config, traffic, devices)
    shape_e = stepper.local_extended_shape(spec, mesh, grid, tb)
    plan = _plan_arg(traffic)
    if plan == "auto":
        plan, _ = registry.resolve_plan(spec, shape_e,
                                        word_bytes=config["word_bytes"],
                                        devices_x=1)
        plan = stepper.cap_plan_d_w(spec, plan, shape_e[1])
    scalars = tuple(float(x) for x in config["coefficients"]["scalars"])
    ovl = traffic["overlap"]
    if ovl == "auto":
        ovl = stepper.overlap_feasible(spec, mesh, grid, tb)
    state = jax.ShapeDtypeStruct(grid, dt, sharding=st_sh)
    n_arr = config["coefficients"]["arrays"]
    arrays = jax.ShapeDtypeStruct((n_arr,) + grid, dt, sharding=arr_sh)
    svec = jax.ShapeDtypeStruct((len(scalars),), dt,
                                sharding=NamedSharding(mesh, P()))
    ext = stepper.extended_coeff_sds(spec, mesh, grid, tb, dt)
    ext = (jax.ShapeDtypeStruct(ext[0].shape, dt, sharding=arr_sh),
           jax.ShapeDtypeStruct(ext[1].shape, dt,
                                sharding=NamedSharding(mesh, P())))
    extender = stepper.make_coeff_extender(spec, mesh, tb)
    step = stepper.make_super_step(spec, mesh, grid, tb, hoisted=True,
                                   pad_g=spec.radius * tb, plan=plan,
                                   scalars=scalars, overlap=ovl)
    return [("coefficient extension", extender, ((arrays, svec),)),
            (f"super-step (overlap={ovl}, dw{plan.d_w}.nf{plan.n_f})", step,
             (state, state, ext))]
