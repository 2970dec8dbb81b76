"""Multi-device tests in a subprocess (8 forced host devices).

The subprocess is needed because the main test process must keep the real
single-device view (see conftest). One subprocess runs all checks to amortize
startup.
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core import stencils as st
from repro.core.mwd import MWDPlan

from repro.distributed import stepper, compression, checkpoint
from repro.distributed.stepper import GridSharding

def auto(n):
    return (jax.sharding.AxisType.Auto,) * n


mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=auto(3))

# 1. distributed deep-halo stepper == naive, all four stencils
for name in st.SPECS:
    spec = st.SPECS[name]
    shape = (8, 8, 16) if spec.radius == 1 else (32, 16, 18)
    state, coeffs = st.make_problem(spec, shape, seed=7)
    T = 5
    want = st.run_naive(spec, state, coeffs, T)
    got = stepper.run_distributed(spec, mesh, state, coeffs, T, t_block=2)
    err = float(jnp.max(jnp.abs(want[0] - jax.device_get(got[0]))))
    assert err < 1e-4, (name, err)
print("stepper OK")

# 1-custom. a user-defined StencilOp (not among the paper's four) runs the
# same distributed path with zero edits: jnp super-steps AND the fused
# MWD-kernel super-step both == single-device naive
from repro.core import ir
_taps = [ir.Tap(0, 0, 0, ir.array(0))]
_taps += [ir.Tap(*o, ir.array(k + 1)) for k, o in enumerate(
    [(-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1), (0, 0, 1),
     (0, -1, -1), (0, 1, 1)])]
custom = ir.StencilOp("dist-custom9", tuple(_taps), coeff_scale=0.08)
state, coeffs = st.make_problem(custom, (8, 8, 16), seed=5)
want = st.run_naive(custom, state, coeffs, 4)
for plan in (None, MWDPlan(d_w=2, n_f=1)):
    got = stepper.run_distributed(custom, mesh, state, coeffs, 4, t_block=2,
                                  plan=plan)
    err = float(jnp.max(jnp.abs(want[0] - jax.device_get(got[0]))))
    assert err < 1e-4, ("custom", plan, err)
print("custom-op stepper OK")

# 1a. MWD-kernel super-steps: ONE fused launch per halo exchange per device,
#     both time orders, == naive
for name in ("7pt-const", "25pt-const"):
    spec = st.SPECS[name]
    shape = (8, 8, 16) if spec.radius == 1 else (32, 16, 18)
    state, coeffs = st.make_problem(spec, shape, seed=7)
    T = 5
    want = st.run_naive(spec, state, coeffs, T)
    got = stepper.run_distributed(spec, mesh, state, coeffs, T, t_block=2,
                                  plan=MWDPlan(d_w=2 * spec.radius, n_f=1))
    err = float(jnp.max(jnp.abs(want[0] - jax.device_get(got[0]))))
    err1 = float(jnp.max(jnp.abs(want[1] - jax.device_get(got[1]))))
    assert err < 1e-4 and err1 < 1e-4, (name, err, err1)
print("mwd-kernel stepper OK")

# 1c. plan="auto" regression: resolution must key on the PER-SHARD extended
#     block shape (it used to key on the global grid, whose tuned d_w can
#     exceed a shard's whole y extent) and cap an oversized tuned d_w.
#     The registry holds ONLY an entry for the local extended shape, with a
#     deliberately oversized d_w; autotune is stubbed to fail, so resolving
#     against any other shape (a miss -> search) or failing to cap dies.
import os as _os
from repro.core import autotune as _at, registry as _reg
_os.environ[_reg.ENV_VAR] = sys.argv[2] + "/plans.json"
spec = st.SPECS["7pt-const"]
shape = (8, 8, 16)                      # ny=8 over 2 y-shards: local ny 4
shape_e = stepper.local_extended_shape(spec, mesh, shape, t_block=2)
assert shape_e == (6, 8, 20), shape_e   # nz/4+2g, ny/2+2g, nx+2g (g=2)
_reg.default_registry().put(spec, shape_e, MWDPlan(d_w=32, n_f=2), 9.0)
def _no_search(*a, **k):
    raise AssertionError("plan='auto' resolved off the per-shard key")
_at.autotune = _no_search
state, coeffs = st.make_problem(spec, shape, seed=11)
want = st.run_naive(spec, state, coeffs, 4)
got = stepper.run_distributed(spec, mesh, state, coeffs, 4, t_block=2,
                              plan="auto")
err = float(jnp.max(jnp.abs(want[0] - jax.device_get(got[0]))))
assert err < 1e-4, err
print("auto-plan shard-key OK")

# 1b. hoisting probe: the steady-state super-step ppermutes ONLY the
#     solution state — 4 sends (2 axes x 2 directions) for a one-stream op.
#     The time-invariant coefficients cross the wire in the one-time
#     extender (its own 4 sends); a non-hoisted step pays both every
#     super-step. Counted on the traced jaxpr, so a regression that sneaks
#     the coefficient exchange back into the hot loop fails loudly.
spec = st.SPECS["7pt-var"]
grid = (8, 8, 16)
gs = GridSharding(mesh)
state, coeffs = st.make_problem(spec, grid, seed=3)
cur = jax.device_put(state[0], gs.sharding())
arrays, svec = stepper.canonical_coeffs(spec, coeffs, grid, cur.dtype)
arrays = jax.device_put(arrays, gs.sharding(leading=1))
extender = stepper.make_coeff_extender(spec, mesh, 2)
coeffs_h = extender((arrays, svec))

def n_ppermute(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("ppermute")

n_hoist = n_ppermute(stepper.make_super_step(spec, mesh, grid, 2,
                                             hoisted=True),
                     cur, cur, coeffs_h)
n_plain = n_ppermute(stepper.make_super_step(spec, mesh, grid, 2),
                     cur, cur, (arrays, svec))
n_ext = n_ppermute(extender, (arrays, svec))
assert n_hoist == 4, n_hoist
assert n_ext == 4, n_ext
assert n_plain == n_hoist + n_ext, (n_plain, n_hoist, n_ext)
print("hoisted OK")

# 2. int8 error-feedback compressed pmean: exact for equal grads,
#    residual-bounded otherwise, converges under accumulation
def pod_mean(g, err):
    f = jax.shard_map(lambda g, e: compression.compressed_pmean(g, e, "pod"),
                  mesh=mesh, in_specs=(P("pod"), P("pod")),
                  out_specs=(P("pod"), P("pod")))
    return f(g, err)

g = jnp.stack([jnp.full((4,), 2.0), jnp.full((4,), 2.0)])   # same on 2 pods
out, err = pod_mean(g, jnp.zeros_like(g))
assert np.allclose(np.asarray(out), 2.0, atol=1e-2), out

rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)
true_mean = np.asarray(g).mean(axis=0)
errbuf = jnp.zeros_like(g)
acc = np.zeros((2, 64), np.float32)
for i in range(20):
    out, errbuf = pod_mean(g, errbuf)
    acc += np.asarray(out)
# error feedback: the time-average converges to the true mean
est = acc / 20
assert np.abs(est - true_mean[None]).max() < 0.02, np.abs(est - true_mean).max()
print("compression OK")

# 2b. compressed halo exchange: int8 error-feedback super-steps stay within
#     a coarse budget vs naive for all four ops (25pt-const exercises the
#     time_order-2 "prev" halo stream); T=5 at t_block=2 forces the partial
#     final super-step, which must rebuild the step AND re-size the residual
#     faces for the smaller halo depth
for name in st.SPECS:
    spec = st.SPECS[name]
    shape = (8, 8, 16) if spec.radius == 1 else (32, 16, 18)
    state, coeffs = st.make_problem(spec, shape, seed=7)
    want = st.run_naive(spec, state, coeffs, 5)
    got = stepper.run_distributed(spec, mesh, state, coeffs, 5, t_block=2,
                                  compress=True)
    err = float(jnp.max(jnp.abs(want[0] - jax.device_get(got[0]))))
    assert err < 5e-2, (name, err)
    # compression must actually perturb the exact path (or the int8 wire
    # saving is fictional): identical output would mean the exchange never
    # quantized anything
    exact = stepper.run_distributed(spec, mesh, state, coeffs, 5, t_block=2)
    diff = float(jnp.max(jnp.abs(jax.device_get(exact[0])
                                 - jax.device_get(got[0]))))
    assert diff > 0.0, name
# compressed halos compose with the fused MWD-kernel super-step
spec = st.SPECS["7pt-const"]
state, coeffs = st.make_problem(spec, (8, 8, 16), seed=7)
want = st.run_naive(spec, state, coeffs, 4)
got = stepper.run_distributed(spec, mesh, state, coeffs, 4, t_block=2,
                              plan=MWDPlan(d_w=4, n_f=2), compress=True)
err = float(jnp.max(jnp.abs(want[0] - jax.device_get(got[0]))))
assert err < 5e-2, err
print("compressed-halo OK")

# 3. sharded save -> restore onto a DIFFERENT (smaller) mesh
spec = st.SPECS["7pt-const"]
state, coeffs = st.make_problem(spec, (8, 8, 16), seed=1)
out = stepper.run_distributed(spec, mesh, state, coeffs, 2, t_block=2)
d = sys.argv[2]
checkpoint.save(d, 2, {"cur": out[0], "prev": out[1]})
small = jax.make_mesh((2, 2), ("data", "model"), axis_types=auto(2),
                      devices=jax.devices()[:4])
gs = GridSharding(small)
_, restored = checkpoint.restore(d, {"cur": out[0], "prev": out[1]},
                                 sharding_fn=lambda n, l: gs.sharding())
out2 = stepper.run_distributed(spec, small,
                               (restored["cur"], restored["prev"]),
                               coeffs, 3, t_block=1)
want = st.run_naive(spec, state, coeffs, 5)
err = float(jnp.max(jnp.abs(want[0] - jax.device_get(out2[0]))))
assert err < 1e-4, err
print("elastic OK")
print("ALL_SUBPROCESS_OK")
"""


SCRIPT_OVERLAP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np


def auto(n):
    return (jax.sharding.AxisType.Auto,) * n

from repro.core import stencils as st
from repro.core.mwd import MWDPlan
from repro.distributed import elastic, stepper

MESHES = {
    1: jax.make_mesh((1, 1), ("data", "model"), axis_types=auto(2), devices=jax.devices()[:1]),
    2: jax.make_mesh((2, 1), ("data", "model"), axis_types=auto(2), devices=jax.devices()[:2]),
    8: jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=auto(3)),
}

def check(spec, grid, T, tb, mesh, tol=None, **kw):
    # overlap=True vs the synchronous schedule: BITWISE equal (tol=None),
    # or within tol of naive when the run is lossy (compressed halos)
    state, coeffs = st.make_problem(spec, grid, seed=3)
    ref = stepper.run_distributed(spec, mesh, state, coeffs, T,
                                  t_block=tb, **kw)
    got = stepper.run_distributed(spec, mesh, state, coeffs, T,
                                  t_block=tb, overlap=True, **kw)
    bit = all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(ref, got))
    naive = st.run_naive(spec, state, coeffs, T)
    err = float(np.abs(np.asarray(got[0]) - np.asarray(naive[0])).max())
    budget = 1e-4 if tol is None else tol
    assert err < budget, (spec.name, grid, err)
    if tol is None:
        assert bit, (spec.name, grid, mesh.devices.size)
    return bit

# 1. overlapped == synchronous bitwise: all four paper ops on 1/2/8-device
#    meshes; T=5 at t_block=2 exercises the trailing partial super-step
for nd in (1, 2, 8):
    check(st.SPECS["7pt-const"], (24, 16, 8), 5, 2, MESHES[nd])
    check(st.SPECS["7pt-var"], (24, 16, 8), 4, 2, MESHES[nd])
    check(st.SPECS["25pt-const"], (72, 36, 16), 4, 2, MESHES[nd])
    check(st.SPECS["25pt-var"], (72, 36, 16), 2, 2, MESHES[nd])
print("overlap bitwise OK")

# 1y. the scaling ladder's y-only meshes shard the other axis — the zone
#     geometry and the mirrored interior-input chain differ per sharding
#     case, so bitwise equality is checked there too
for nd in (2, 8):
    ymesh = jax.make_mesh((1, nd), ("data", "model"), axis_types=auto(2),
                          devices=jax.devices()[:nd])
    check(st.SPECS["7pt-const"], (24, 64, 8), 4, 2, ymesh)
    check(st.SPECS["25pt-const"], (72, 144, 16), 4, 2, ymesh)
print("overlap y-mesh OK")

# 2. a custom IR op (not among the paper's four) gets the same guarantee
from repro.core import ir
_taps = [ir.Tap(0, 0, 0, ir.array(0))]
_taps += [ir.Tap(*o, ir.array(k + 1)) for k, o in enumerate(
    [(-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1), (0, 0, 1),
     (0, -1, -1), (0, 1, 1)])]
custom = ir.StencilOp("ovl-custom9", tuple(_taps), coeff_scale=0.08)
check(custom, (24, 16, 8), 4, 2, MESHES[8])
print("overlap custom-op OK")

# 3. fused MWD-kernel super-steps: the overlapped kernel schedule is
#    bitwise-equal to the synchronous kernel schedule
check(st.SPECS["7pt-const"], (24, 16, 8), 4, 2, MESHES[2],
      plan=MWDPlan(d_w=2, n_f=1))
check(st.SPECS["25pt-const"], (72, 36, 16), 4, 2, MESHES[8],
      plan=MWDPlan(d_w=8, n_f=1))
print("overlap kernel OK")

# 4. compressed halos compose with overlap: lossy (so no bitwise claim),
#    but inside the same error budget as the synchronous compressed run
check(st.SPECS["7pt-const"], (24, 16, 8), 4, 2, MESHES[8],
      compress=True, tol=5e-2)
check(st.SPECS["25pt-const"], (72, 36, 16), 4, 2, MESHES[8],
      compress=True, tol=5e-2)
print("overlap compressed OK")

# 5. elastic shrink-then-grow: ElasticStencilRun replays tuned plans from
#    the registry at each mesh size (autotune stubbed to fail, so any
#    resolution miss dies), overlap="auto" falls back where shards are too
#    small, and the composed run still matches single-device naive
from repro.core import autotune as _at, registry as _reg
os.environ[_reg.ENV_VAR] = sys.argv[2] + "/elastic-plans.json"
def _no_search(*a, **k):
    raise AssertionError("elastic rescale fell through to a plan search")
_at.autotune = _no_search
spec = st.SPECS["7pt-const"]
grid = (8, 16, 16)
for nd in (8, 2):
    shape_e = stepper.local_extended_shape(spec, elastic.build_mesh(nd),
                                           grid, 2)
    _reg.default_registry().put(spec, shape_e, MWDPlan(d_w=2, n_f=1), 9.0)
state, coeffs = st.make_problem(spec, grid, seed=9)
run = elastic.ElasticStencilRun(spec, state, coeffs, sys.argv[2],
                                t_block=2, plan="auto", overlap="auto",
                                n_devices=8)
assert run.plan_source.startswith("registry"), run.plan_source
run.advance(4)
run.save()
run.rescale(2)                      # shrink: 8 -> 2 devices
assert run.plan_source.startswith("registry"), run.plan_source
run.advance(2)
run.save()
run.rescale(8)                      # grow back
run.advance(2)
want = st.run_naive(spec, state, coeffs, 8)
err = float(np.abs(np.asarray(jax.device_get(run.state[0]))
                   - np.asarray(want[0])).max())
assert err < 1e-4, err
assert run.steps_done == 8, run.steps_done
print("elastic shrink-grow OK")
print("ALL_OVERLAP_OK")
"""


@pytest.mark.slow
def test_distributed_subprocess(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, src, str(tmp_path)],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ALL_SUBPROCESS_OK" in proc.stdout, proc.stdout
    assert "auto-plan shard-key OK" in proc.stdout, proc.stdout
    assert "compressed-halo OK" in proc.stdout, proc.stdout


@pytest.mark.slow
def test_overlap_subprocess(tmp_path):
    """Overlapped super-steps: bitwise vs sync + elastic rescale replay."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT_OVERLAP, src, str(tmp_path)],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ALL_OVERLAP_OK" in proc.stdout, proc.stdout
    assert "overlap bitwise OK" in proc.stdout, proc.stdout
    assert "elastic shrink-grow OK" in proc.stdout, proc.stdout
