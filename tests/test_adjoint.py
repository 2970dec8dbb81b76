"""Gradcheck harness for the structural custom_vjp MWD adjoint.

Three independent oracles pin `repro.kernels.adjoint`:

1. `jax.grad` of the pure-jnp reference (`stencils.run_naive`) — autodiff
   through the un-blocked sweep, no kernels involved;
2. central finite differences in f64 — no autodiff involved at all;
3. the O(volume) `_tap_apply_full` reference for the O(surface·R)
   `_frame_shell` frame accumulation.

Property tests (hypothesis, via tests/_hyp) drive random grids, step
counts and plans over the paper operators plus a custom mixed
const/array-coefficient IR op; example-based tests cover the batched
(`mwd_diff_batched`), distributed (`distributed_vjp`) and registry
(``vjp`` plan-key variant) paths.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ir
from repro.core import registry as reg
from repro.core import stencils as st
from repro.core.mwd import MWDPlan
from repro.kernels import adjoint as adj_mod
from repro.kernels import ops
from tests._hyp import HAVE_HYPOTHESIS, given, settings, strategies

# a 2nd-order op the paper set does NOT cover: const + array tap
# coefficients mixed in one operator, with a const time-recurrence scale
# (the adjoint must carry const coefficients over unchanged while
# transporting the array streams as rolled fields)
_MIXED = ir.StencilOp(
    "adj-mixed",
    (ir.Tap(0, 0, 0, ir.const(1)),
     ir.Tap(-1, 0, 0, ir.array(0)), ir.Tap(1, 0, 0, ir.array(0)),
     ir.Tap(0, -1, 0, ir.array(1)), ir.Tap(0, 1, 0, ir.array(1)),
     ir.Tap(0, 0, -1, ir.const(2)), ir.Tap(0, 0, 1, ir.const(2))),
    time_order=2, scale=ir.const(0),
    default_scalars=(0.21, -0.53, 0.11), coeff_scale=0.08)

_ALL = dict(st.SPECS, **{_MIXED.name: _MIXED})

_GRIDS_R1 = ((6, 8, 8), (8, 12, 10), (10, 8, 12))
_GRIDS_R4 = ((16, 20, 16), (12, 24, 18))


def _grid_for(op, i=0):
    return (_GRIDS_R1 if op.radius == 1 else _GRIDS_R4)[i]


def _tol(op, ref_mag, dtype=jnp.float32):
    atol, rtol = op.tolerance(dtype)
    return 8.0 * (atol + rtol * max(ref_mag, 1.0))


def _problem(op, grid, seed, dtype=None):
    state, coeffs = st.make_problem(op, grid, dtype=dtype, seed=seed)
    arrays, scalars = ir.split_coeffs(op, coeffs)
    return state, arrays, tuple(float(x) for x in scalars)


def _loss_fn(op, scalars, n_steps, w, w2, runner, **kw):
    """Scalar loss through `runner`, differentiable in (cur, prev, arrays)."""
    def f(cur, prev, arrays):
        coeffs = ir.join_coeffs(op, arrays, scalars)
        out = runner(op, (cur, prev), coeffs, n_steps, **kw)
        return (jnp.sum(w * out[0].astype(w.dtype))
                + jnp.sum(w2 * out[1].astype(w.dtype)))
    return f


def _check_grads(op, grid, n_steps, seed=0, **kw):
    """custom_vjp cotangents == jax.grad of the naive oracle, all inputs."""
    state, arrays, scalars = _problem(op, grid, seed)
    rng = np.random.default_rng(seed + 13)
    w = jnp.asarray(rng.standard_normal(state[0].shape), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal(state[0].shape), jnp.float32)
    argnums = (0, 1, 2) if arrays is not None else (0, 1)
    args = (state[0], state[1], arrays)

    got_f = _loss_fn(op, scalars, n_steps, w, w2,
                     lambda o, s, c, n: ops.mwd_diff(o, s, c, n, **kw))
    ref_f = _loss_fn(op, scalars, n_steps, w, w2,
                     lambda o, s, c, n: st.run_naive(o, s, c, n))
    # the primal must be the REAL fused kernel result, bitwise
    fused = ops.mwd(op, state, ir.join_coeffs(op, arrays, scalars),
                    n_steps, **kw)
    diff = ops.mwd_diff(op, state, ir.join_coeffs(op, arrays, scalars),
                        n_steps, **kw)
    for a, b in zip(fused, diff):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    g_got = jax.grad(got_f, argnums=argnums)(*args)
    g_ref = jax.grad(ref_f, argnums=argnums)(*args)
    for name, a, b in zip(("cur", "prev", "arrays"), g_got, g_ref):
        err = float(jnp.max(jnp.abs(a - b)))
        mag = float(jnp.max(jnp.abs(b)))
        assert err <= _tol(op, mag), (
            f"{op.name}/{name}: grad err {err:.3e} vs ref magnitude "
            f"{mag:.3e} (n_steps={n_steps}, grid={grid}, kw={kw})")


# ---------------------------------------------------------------------------
# gradcheck vs the autodiffed oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(_ALL))
def test_gradcheck_vs_oracle(name):
    op = _ALL[name]
    _check_grads(op, _grid_for(op), n_steps=2, seed=0)


@pytest.mark.parametrize("name", ["7pt-var", "adj-mixed"])
def test_gradcheck_explicit_and_auto_plan(name):
    op = _ALL[name]
    _check_grads(op, _grid_for(op, 1), n_steps=2, seed=1,
                 plan=MWDPlan(d_w=4, n_f=1))
    _check_grads(op, _grid_for(op, 1), n_steps=2, seed=1, plan="auto")


def test_zero_steps_is_identity():
    op = st.SPECS["7pt-var"]
    state, coeffs = st.make_problem(op, (6, 8, 8), seed=3)
    out = ops.mwd_diff(op, state, coeffs, 0)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(state[1]))


@pytest.mark.parametrize("name", ["7pt-const", "7pt-var", "adj-mixed"])
@settings(max_examples=4, deadline=None)
@given(data=strategies.data())
def test_gradcheck_property(name, data):
    """Random grid x step count x plan: cotangents match the oracle."""
    op = _ALL[name]
    grid = data.draw(strategies.sampled_from(
        _GRIDS_R1 if op.radius == 1 else _GRIDS_R4))
    n_steps = data.draw(strategies.integers(min_value=1, max_value=3))
    d_w = data.draw(strategies.sampled_from((4, 8))) if op.radius == 1 else 8
    n_f = data.draw(strategies.sampled_from((1, 2)))
    seed = data.draw(strategies.integers(min_value=0, max_value=3))
    _check_grads(op, grid, n_steps, seed=seed, d_w=d_w, n_f=n_f)


# ---------------------------------------------------------------------------
# gradcheck vs central finite differences (f64, autodiff-free oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["7pt-var", "adj-mixed"])
def test_gradcheck_finite_differences(name):
    op = _ALL[name]
    grid, n_steps, eps = _grid_for(op), 2, 1e-5
    with jax.enable_x64(True):
        state, arrays, scalars = _problem(op, grid, seed=5,
                                          dtype=jnp.float64)
        rng = np.random.default_rng(11)
        w = jnp.asarray(rng.standard_normal(state[0].shape), jnp.float64)
        w2 = jnp.asarray(rng.standard_normal(state[0].shape), jnp.float64)
        f = _loss_fn(op, scalars, n_steps, w, w2,
                     lambda o, s, c, n: ops.mwd_diff(o, s, c, n))
        args = (state[0], state[1], arrays)
        grads = jax.grad(f, argnums=(0, 1, 2))(*args)
        dirs = tuple(jnp.asarray(rng.standard_normal(a.shape), jnp.float64)
                     for a in args)
        directional = sum(float(jnp.sum(g * d))
                          for g, d in zip(grads, dirs))
        up = f(*(a + eps * d for a, d in zip(args, dirs)))
        dn = f(*(a - eps * d for a, d in zip(args, dirs)))
        fd = (float(up) - float(dn)) / (2 * eps)
    denom = max(abs(fd), abs(directional), 1e-12)
    assert abs(directional - fd) / denom < 1e-6, (
        f"{op.name}: <grad, d> = {directional!r} vs central FD {fd!r}")


# ---------------------------------------------------------------------------
# batched path
# ---------------------------------------------------------------------------

def test_gradcheck_batched_matches_per_item():
    op, grid, n_steps, b = st.SPECS["7pt-var"], (6, 8, 8), 2, 3
    probs = [st.make_problem(op, grid, seed=20 + i) for i in range(b)]
    cur = jnp.stack([p[0][0] for p in probs])
    prev = jnp.stack([p[0][1] for p in probs])
    arrays = jnp.stack([ir.split_coeffs(op, p[1])[0] for p in probs])
    scalars = tuple(float(x)
                    for x in ir.split_coeffs(op, probs[0][1])[1])
    rng = np.random.default_rng(31)
    w = jnp.asarray(rng.standard_normal(cur.shape), jnp.float32)

    def loss_b(c, p, a):
        coeffs = [ir.join_coeffs(op, a[i], scalars) for i in range(b)]
        out = ops.mwd_diff_batched(op, (c, p), coeffs, n_steps)
        return jnp.sum(w * out[0])

    def loss_ref(c, p, a):
        total = 0.0
        for i in range(b):
            out = st.run_naive(op, (c[i], p[i]),
                               ir.join_coeffs(op, a[i], scalars), n_steps)
            total = total + jnp.sum(w[i] * out[0])
        return total

    g_got = jax.grad(loss_b, argnums=(0, 1, 2))(cur, prev, arrays)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(cur, prev, arrays)
    for name, a, c in zip(("cur", "prev", "arrays"), g_got, g_ref):
        err = float(jnp.max(jnp.abs(a - c)))
        mag = float(jnp.max(jnp.abs(c)))
        assert err <= _tol(op, mag), f"batched/{name}: {err:.3e}"


def test_batched_shared_coeffs_forward_matches_mwd_batched():
    op, grid, n_steps, b = st.SPECS["7pt-var"], (6, 8, 8), 2, 2
    probs = [st.make_problem(op, grid, seed=40 + i) for i in range(b)]
    states = [p[0] for p in probs]
    coeffs = probs[0][1]                     # one set shared by the batch
    want = ops.mwd_batched(op, states, coeffs, n_steps)
    got = ops.mwd_diff_batched(op, states, coeffs, n_steps)
    for a, c in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ---------------------------------------------------------------------------
# distributed path (1-device in-process mesh; 8-device runs live in the
# test_distributed subprocess harness)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["7pt-var", "25pt-const"])
def test_distributed_vjp_matches_oracle(name):
    op = st.SPECS[name]
    grid, n_steps = _grid_for(op), 2
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
    state, arrays, scalars = _problem(op, grid, seed=7)
    coeffs = ir.join_coeffs(op, arrays, scalars)
    outs, vjp_fn = adj_mod.distributed_vjp(op, mesh, state, coeffs,
                                           n_steps, t_block=2)
    want = st.run_naive(op, state, coeffs, n_steps)
    for a, c in zip(want, outs):
        assert float(jnp.max(jnp.abs(a - jax.device_get(c)))) < 1e-4

    rng = np.random.default_rng(51)
    w = jnp.asarray(rng.standard_normal(state[0].shape), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal(state[0].shape), jnp.float32)
    g_cur, g_prev, g_arr = vjp_fn((w, w2))
    ref_f = _loss_fn(op, scalars, n_steps, w, w2,
                     lambda o, s, c, n: st.run_naive(o, s, c, n))
    g_ref = jax.grad(ref_f, argnums=(0, 1, 2))(state[0], state[1], arrays)
    for nm, a, c in zip(("cur", "prev", "arrays"),
                        (g_cur, g_prev, g_arr), g_ref):
        err = float(jnp.max(jnp.abs(a - c)))
        mag = float(jnp.max(jnp.abs(c)))
        assert err <= _tol(op, mag), f"distributed/{nm}: {err:.3e}"


# ---------------------------------------------------------------------------
# frame accumulation: O(surface) shell == O(volume) reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(_ALL))
def test_frame_shell_matches_full_reference(name):
    op = _ALL[name]
    grid = _grid_for(op, 1)
    _, arrays, scalars = _problem(op, grid, seed=9)
    adj = ir.adjoint(op)
    adj_arrays, adj_scalars = adj.map_coeffs(arrays, scalars)
    rng = np.random.default_rng(17)
    g = jnp.asarray(rng.standard_normal(grid), jnp.float32)
    full = adj_mod._tap_apply_full(adj, adj_arrays, adj_scalars, g)
    shell = adj_mod._frame_shell(adj, adj_arrays, adj_scalars, g)
    np.testing.assert_allclose(np.asarray(shell),
                               np.asarray(adj_mod._frame_only(full,
                                                              op.radius)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# registry: the ``vjp`` plan-key variant
# ---------------------------------------------------------------------------

def test_vjp_plan_key_is_distinct_suffix():
    op = st.SPECS["7pt-const"]
    k0 = reg.plan_key(op, (10, 18, 14))
    kv = reg.plan_key(op, (10, 18, 14), variant="vjp")
    assert kv == k0 + "|vjp"
    with pytest.raises(ValueError):
        reg.plan_key(op, (10, 18, 14), variant="bogus")


def test_vjp_registry_roundtrip(tmp_path):
    path = str(tmp_path / "plans.json")
    r = reg.PlanRegistry(path)
    op = st.SPECS["7pt-var"]
    r.put(op, (10, 18, 14), MWDPlan(d_w=4, n_f=2), 1.0)
    r.put(op, (10, 18, 14), MWDPlan(d_w=2, n_f=1), 1.0, variant="vjp")
    assert r.get(op, (10, 18, 14)).plan.d_w == 4
    assert r.get(op, (10, 18, 14), variant="vjp").plan.d_w == 2
    r2 = reg.PlanRegistry(path)              # fresh load from disk
    assert r2.get(op, (10, 18, 14), variant="vjp").plan.d_w == 2
    assert r2.get(op, (10, 18, 14)).plan.d_w == 4


def test_load_upgrades_legacy_key_preserving_variant(tmp_path):
    """A pre-batch-schema key keeps its ``|vjp`` suffix through the b1
    upgrade instead of being mangled into a bogus batch segment."""
    path = tmp_path / "plans.json"
    r = reg.PlanRegistry(str(path))
    op = st.SPECS["7pt-var"]
    r.put(op, (10, 18, 14), MWDPlan(d_w=2, n_f=1), 1.0, variant="vjp")
    raw = json.loads(path.read_text())
    (key, entry), = raw["plans"].items()
    assert key.endswith("|b1|vjp")
    raw["plans"] = {key.replace("|b1|vjp", "|vjp"): entry}
    path.write_text(json.dumps(raw))
    r2 = reg.PlanRegistry(str(path))
    assert r2.get(op, (10, 18, 14), variant="vjp").plan.d_w == 2


def test_resolve_adjoint_plan_keys_on_adjoint_op(tmp_path, monkeypatch):
    # default_registry re-resolves $REPRO_PLAN_REGISTRY per call, so the
    # monkeypatched path isolates this test from the real plan cache
    monkeypatch.setenv(reg.ENV_VAR, str(tmp_path / "plans.json"))
    op = st.SPECS["7pt-var"]
    plan, source = adj_mod.resolve_adjoint_plan(op, (10, 18, 14))
    assert isinstance(plan, MWDPlan)
    assert plan.d_w % (2 * op.radius) == 0
    assert source and "registry" not in source       # empty registry: model
    # a plan tuned for the ADJOINT op under the vjp variant is found
    adj = ir.adjoint(op)
    reg.default_registry().put(adj.op, (10, 18, 14), MWDPlan(d_w=2, n_f=1),
                               9.9, variant="vjp")
    plan2, source2 = adj_mod.resolve_adjoint_plan(op, (10, 18, 14))
    assert plan2.d_w == 2 and source2.startswith("registry")


def test_hypothesis_available_in_ci():
    import os
    if os.environ.get("CI"):
        assert HAVE_HYPOTHESIS, "CI must run the property tests for real"
