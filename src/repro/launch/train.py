"""Training launcher.

Production entry point: builds the mesh (elastic: whatever device set is
healthy), places the train state, restores the newest checkpoint if present,
and runs the step loop with async checkpointing, deadline-based straggler
accounting, and optional cross-pod gradient compression.

CPU-friendly: with --reduced it trains the smoke-scale config of any
architecture on the local devices.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.data.pipeline import PipelineConfig, SyntheticPipeline
from repro.distributed import checkpoint, elastic
from repro.models import lm
from repro.models.params import tree_init
from repro.training import sharding as shd
from repro.training import steps as tsteps


class StepGuard:
    """Deadline-based straggler accounting over the train-step clock.

    Flags steps slower than `factor` x the rolling median; on clusters this
    triggers scheduler rebalancing / health checks, here it is logged and
    counted.
    """

    def __init__(self, factor: float = 3.0):
        self.times: list[float] = []
        self.factor = factor
        self.stragglers = 0

    def observe(self, dt: float) -> bool:
        """Record one step time; True if it crossed the straggler deadline."""
        slow = (len(self.times) >= 5
                and dt > self.factor * float(np.median(self.times)))
        self.times.append(dt)
        if slow:
            self.stragglers += 1
        return slow


def main(argv=None):
    """CLI entry point: build mesh, restore/init state, run the step loop."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    mesh = elastic.build_mesh()
    print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} devices")

    spec_tree = lm.param_specs(cfg)
    opt, train_step = tsteps.make_train_step(cfg, lr=args.lr,
                                             chunk=min(args.seq, 2048),
                                             accum=args.accum)
    params_sh = shd.param_shardings(mesh, spec_tree)

    start_step = 0
    if args.ckpt and checkpoint.latest_step(args.ckpt) is not None:
        state_sds, sh_fn = tsteps.train_state_specs(cfg)
        flat_sh = jax.tree_util.tree_leaves_with_path(sh_fn(mesh))
        shmap = {jax.tree_util.keystr(p): s for p, s in flat_sh}
        start_step, state = checkpoint.restore(
            args.ckpt, state_sds,
            sharding_fn=lambda name, leaf: shmap.get(
                name, jax.NamedSharding(mesh, jax.sharding.PartitionSpec())))
        print(f"resumed from step {start_step}")
    else:
        params = jax.device_put(tree_init(spec_tree, seed=args.seed),
                                params_sh)
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}

    pipe = SyntheticPipeline(PipelineConfig(args.batch, args.seq,
                                            cfg.vocab_size))
    ckpt = checkpoint.AsyncCheckpointer(args.ckpt) if args.ckpt else None
    guard = StepGuard()
    jstep = jax.jit(train_step, donate_argnums=(0,))

    with jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            batch = pipe.get_batch(step, cfg)
            t0 = time.perf_counter()
            state, metrics = jstep(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            slow = guard.observe(dt)
            tag = " [straggler]" if slow else ""
            if step % 5 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms{tag}")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state)
    if ckpt:
        ckpt.wait_pending()
        print(f"checkpoints: {checkpoint.all_steps(args.ckpt)}")
    print(f"done; stragglers observed: {guard.stragglers}")
    return state


if __name__ == "__main__":
    main()
