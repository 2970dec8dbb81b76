"""Elastic rescale + slice health tracking.

The checkpoint format is mesh-agnostic (full logical arrays), so elasticity
reduces to: detect a changed device set -> rebuild the mesh -> restore the
latest checkpoint with shardings for the new mesh -> rebuild the jitted step.
`ElasticStencilRun` packages that loop for the distributed super-stepper:
on every grow or shrink it re-resolves the per-shard MWD plan from the tuned
registry (the kernel launches on the NEW local extended block, a different
tuning key) and rebuilds the overlapped stepper before resuming from the
latest checkpoint.

`plan_mesh` degrades gracefully: it returns the largest production-shaped
mesh the healthy device set supports (2 pods -> 1 pod -> debug shapes), which
is what the launcher uses after a pod drops. `HealthMonitor` is the host-side
heartbeat registry the launcher polls; on real clusters the heartbeats come
from per-slice agents, here tests drive it directly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax


def plan_mesh(n_devices: int) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """Largest supported mesh for the healthy device count."""
    if n_devices >= 512:
        return (2, 16, 16), ("pod", "data", "model")
    if n_devices >= 256:
        return (16, 16), ("data", "model")
    # degraded/debug shapes: keep 'model' as the minor axis
    for model in (16, 8, 4, 2, 1):
        if n_devices % model == 0 and n_devices >= model:
            return (n_devices // model, model), ("data", "model")
    return (n_devices, 1), ("data", "model")


def build_mesh(n_devices: int | None = None,
               devices=None) -> jax.sharding.Mesh:
    """Build the `plan_mesh` shape over the first n healthy devices.

    `devices` overrides the pool (defaults to ``jax.devices()``); the mesh
    takes its first `n_devices` entries, so a shrink to a subset of the
    machine's devices builds a genuinely smaller mesh instead of failing
    against the full device count.
    """
    pool = list(jax.devices()) if devices is None else list(devices)
    n = len(pool) if n_devices is None else n_devices
    if n > len(pool):
        raise ValueError(
            f"requested a {n}-device mesh but only {len(pool)} devices "
            "are healthy")
    shape, axes = plan_mesh(n)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=pool[:n])


@dataclasses.dataclass
class HealthMonitor:
    """Heartbeat registry with a deadline; launcher polls healthy_slices()."""

    slices: tuple[str, ...]
    timeout_s: float = 60.0
    clock: Callable[[], float] = time.monotonic

    def __post_init__(self):
        now = self.clock()
        self._last_beat = {s: now for s in self.slices}

    def heartbeat(self, slice_id: str) -> None:
        """Record a liveness beat from `slice_id` (resets its deadline)."""
        self._last_beat[slice_id] = self.clock()

    def healthy_slices(self) -> list[str]:
        """Slices whose last beat is within the timeout."""
        now = self.clock()
        return [s for s, t in self._last_beat.items()
                if now - t <= self.timeout_s]

    @property
    def degraded(self) -> bool:
        """True when at least one slice has missed its deadline."""
        return len(self.healthy_slices()) < len(self.slices)


def rescale_restore(ckpt_dir: str, tree_like, make_sharding,
                    n_devices: int | None = None):
    """Restore the latest checkpoint onto a mesh for the current device set.

    Rebuilds the (possibly reduced) mesh first; `make_sharding(mesh, name,
    leaf)` supplies each leaf's sharding. Returns ``(step, state, mesh)``.
    """
    from repro.distributed import checkpoint

    new_mesh = build_mesh(n_devices)
    step, state = checkpoint.restore(
        ckpt_dir, tree_like,
        sharding_fn=lambda name, leaf: make_sharding(new_mesh, name, leaf))
    return step, state, new_mesh


class ElasticStencilRun:
    """A distributed stencil run that survives mesh grows and shrinks.

    The launcher loop:

        run = ElasticStencilRun(spec, state, coeffs, ckpt_dir, t_block=2,
                                plan="auto", overlap="auto")
        run.advance(k)            # k time steps on the current mesh
        run.save()                # mesh-agnostic checkpoint
        run.rescale(n_healthy)    # a slice died (or capacity came back):
                                  # rebuild the mesh over the healthy set,
                                  # re-resolve the per-shard plan from the
                                  # tuned registry, rebuild the overlapped
                                  # stepper, resume from the checkpoint

    Everything mesh-dependent is derived: only the mesh-agnostic pieces
    (spec, global state, coefficients, step count) carry across a rescale.
    Plan resolution happens at (re)build time, not per advance — the tuning
    key is the per-shard extended block (`stepper.local_extended_shape`),
    which changes with the shard geometry, so a registry tuned for both the
    degraded and the full mesh replays without any re-search.
    """

    def __init__(self, spec, state, coeffs, ckpt_dir: str, *,
                 t_block: int = 2, plan=None, overlap="auto",
                 compress: bool = False, n_devices: int | None = None,
                 devices=None):
        self.spec = spec
        self.ckpt_dir = ckpt_dir
        self.t_block = t_block
        self.overlap = overlap
        self.compress = compress
        self._plan_req = plan
        self._pool = list(devices) if devices is not None else None
        self.grid_shape = tuple(state[0].shape)
        self.state = state
        self.coeffs = coeffs
        self.steps_done = 0
        self._rebuild(n_devices)

    def _rebuild(self, n_devices: int | None) -> None:
        from repro.distributed import stepper

        self.mesh = build_mesh(n_devices, devices=self._pool)
        self.plan_source = None
        if self._plan_req == "auto":
            from repro.core import registry

            shape_e = stepper.local_extended_shape(
                self.spec, self.mesh, self.grid_shape, self.t_block)
            plan, self.plan_source = registry.resolve_plan(
                self.spec, shape_e,
                word_bytes=self.state[0].dtype.itemsize,
                devices_x=self.mesh.shape.get("x", 1))
            self.plan = stepper.cap_plan_d_w(self.spec, plan, shape_e[1])
        else:
            self.plan = self._plan_req

    def advance(self, n_steps: int):
        """Run `n_steps` more time steps on the current mesh."""
        from repro.distributed import stepper

        self.state = stepper.run_distributed(
            self.spec, self.mesh, self.state, self.coeffs, n_steps,
            t_block=self.t_block, plan=self.plan, compress=self.compress,
            overlap=self.overlap)
        self.steps_done += n_steps
        return self.state

    def save(self) -> str:
        """Mesh-agnostic checkpoint of the current state at steps_done."""
        from repro.distributed import checkpoint

        return checkpoint.save(
            self.ckpt_dir, self.steps_done,
            {"cur": self.state[0], "prev": self.state[1]})

    def rescale(self, n_devices: int | None = None, devices=None):
        """Grow or shrink onto `n_devices`; resume from the latest ckpt."""
        from repro.distributed import checkpoint, stepper

        if devices is not None:
            self._pool = list(devices)
        self._rebuild(n_devices)
        gs = stepper.GridSharding(self.mesh)
        like = {"cur": self.state[0], "prev": self.state[1]}
        self.steps_done, restored = checkpoint.restore(
            self.ckpt_dir, like,
            sharding_fn=lambda _name, _leaf: gs.sharding())
        self.state = (restored["cur"], restored["prev"])
        return self.mesh
