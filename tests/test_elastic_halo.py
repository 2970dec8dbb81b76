"""Elastic planning + single-device halo paths + health monitor."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stencils as st
from repro.distributed import elastic, halo


def test_plan_mesh_degradation_ladder():
    assert elastic.plan_mesh(512) == ((2, 16, 16), ("pod", "data", "model"))
    assert elastic.plan_mesh(256) == ((16, 16), ("data", "model"))
    assert elastic.plan_mesh(64) == ((4, 16), ("data", "model"))
    assert elastic.plan_mesh(8) == ((1, 8), ("data", "model"))
    assert elastic.plan_mesh(1) == ((1, 1), ("data", "model"))


def test_health_monitor():
    t = [0.0]
    mon = elastic.HealthMonitor(("pod0", "pod1"), timeout_s=10,
                                clock=lambda: t[0])
    assert not mon.degraded
    t[0] = 5.0
    mon.heartbeat("pod0")
    t[0] = 12.0
    assert mon.healthy_slices() == ["pod0"]
    assert mon.degraded


def test_halo_single_device_edge_clamp():
    """n==1 path: halos are edge clamps; stepper must equal naive."""
    import jax
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    from repro.distributed import stepper
    spec = st.SPECS["7pt-const"]
    state, coeffs = st.make_problem(spec, (8, 8, 16), seed=0)
    want = st.run_naive(spec, state, coeffs, 4)
    got = stepper.run_distributed(spec, mesh, state, coeffs, 4, t_block=2)
    assert float(jnp.max(jnp.abs(want[0] - got[0]))) < 1e-5


def test_halo_depth_guard():
    x = jnp.zeros((4, 4, 8))
    with pytest.raises(ValueError, match="halo depth"):
        halo.exchange_axis(x, "data", 0, depth=5)


def test_halo_bytes_model():
    b = halo.halo_bytes((32, 32, 64), depth=4, word_bytes=4, n_streams=2)
    z_face = 4 * 32 * 64
    y_face = 4 * (32 + 8) * 64
    assert b == 2 * (z_face + y_face) * 4 * 2
