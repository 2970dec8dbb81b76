"""Multi-device stencil run with deep halos + elastic restart demo.

Forces 8 host devices, runs the 7pt-var stencil on a (2,2,2) pod/data/model
mesh with deep-halo super-steps, checkpoints, then RESHARDS the checkpoint
onto a degraded 4-device mesh (one "pod" lost) and finishes the run there —
the elastic-rescale path. Verifies against the single-host naive reference.

  PYTHONPATH=src python examples/distributed_stencil.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

from repro.core import stencils as st                        # noqa: E402
from repro.distributed import checkpoint, stepper            # noqa: E402


def main():
    spec = st.SPECS["7pt-var"]
    shape = (16, 16, 32)
    T1, T2 = 4, 4
    state, coeffs = st.make_problem(spec, shape, seed=11)

    # phase 1: healthy 2x2x2 mesh (2 pods); overlap="auto" runs the
    # interior/boundary-split schedule (bitwise-equal to synchronous) where
    # the shards have room, and falls back to synchronous where not
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3)
    out = stepper.run_distributed(spec, mesh, state, coeffs, T1, t_block=2,
                                  overlap="auto")
    ckpt_dir = "/tmp/dist_stencil_ckpt"
    checkpoint.save(ckpt_dir, T1, {"cur": out[0], "prev": out[1]})
    print(f"phase 1: {T1} steps on {mesh.devices.size} devices, checkpointed")

    # phase 2: a pod dies -> rebuild on 4 devices, reshard, continue
    small = jax.make_mesh((2, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
                             devices=jax.devices()[:4])
    gs = stepper.GridSharding(small)
    _, restored = checkpoint.restore(
        ckpt_dir, {"cur": out[0], "prev": out[1]},
        sharding_fn=lambda name, leaf: gs.sharding())
    out2 = stepper.run_distributed(spec, small, (restored["cur"],
                                                 restored["prev"]),
                                   coeffs, T2, t_block=2)
    print(f"phase 2: {T2} more steps on degraded {small.devices.size}-device mesh")

    ref = st.run_naive(spec, state, coeffs, T1 + T2)
    err = float(jnp.max(jnp.abs(ref[0] - jax.device_get(out2[0]))))
    print(f"elastic-restart result vs naive: max|err| = {err:.2e}")
    assert err < 1e-4
    print("verified: pod loss -> reshard -> continue is exact.")


if __name__ == "__main__":
    main()
