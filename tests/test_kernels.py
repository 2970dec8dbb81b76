"""Per-kernel validation vs the pure-jnp oracle: shape & dtype sweeps."""

import jax.numpy as jnp
import pytest

from repro.core import stencils as st
from repro.kernels import ops, ref

SHAPES_R1 = [(6, 10, 12), (10, 20, 24), (9, 17, 31)]
SHAPES_R4 = [(10, 18, 14), (13, 21, 18)]


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _tol(dtype):
    return 5e-4 if dtype == jnp.float32 else 2e-1


@pytest.mark.parametrize("name", list(st.SPECS))
@pytest.mark.parametrize("si", [0, 1])
@pytest.mark.parametrize("t_steps", [1, 3])
def test_sweep_kernel(name, si, t_steps):
    spec = st.SPECS[name]
    shape = (SHAPES_R1 if spec.radius == 1 else SHAPES_R4)[si]
    state, coeffs = st.make_problem(spec, shape, seed=si)
    want = ref.naive_steps(spec, state, coeffs, t_steps)
    got = ops.spatial(spec, state, coeffs, t_steps, bz=4)
    assert _err(want[0], got[0]) < 5e-4


@pytest.mark.parametrize("name", list(st.SPECS))
@pytest.mark.parametrize("t_steps,t_block", [(2, 2), (5, 3)])
def test_ghostzone_kernel(name, t_steps, t_block):
    spec = st.SPECS[name]
    shape = SHAPES_R1[1] if spec.radius == 1 else SHAPES_R4[0]
    state, coeffs = st.make_problem(spec, shape, seed=3)
    want = ref.naive_steps(spec, state, coeffs, t_steps)
    got = ops.ghostzone(spec, state, coeffs, t_steps, t_block=t_block,
                        bz=4, by=8)
    assert _err(want[0], got[0]) < 5e-4
    assert _err(want[1], got[1]) < 5e-4


@pytest.mark.parametrize("name", list(st.SPECS))
@pytest.mark.parametrize("t_steps,k,n_f", [(4, 1, 2), (3, 2, 4)])
def test_mwd_kernel(name, t_steps, k, n_f):
    spec = st.SPECS[name]
    d_w = 2 * spec.radius * k
    if d_w % n_f:
        n_f = d_w
    shape = SHAPES_R1[1] if spec.radius == 1 else SHAPES_R4[1]
    state, coeffs = st.make_problem(spec, shape, seed=4)
    want = ref.naive_steps(spec, state, coeffs, t_steps)
    got = ops.mwd(spec, state, coeffs, t_steps, d_w=d_w, n_f=n_f)
    assert _err(want[0], got[0]) < 5e-4
    assert _err(want[1], got[1]) < 5e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernels_dtype_sweep(dtype):
    spec = st.SPEC_7C
    state, coeffs = st.make_problem(spec, (8, 16, 16), dtype=dtype, seed=5)
    want = ref.naive_steps(spec, state, coeffs, 2)
    for fn, kw in [(ops.spatial, dict(bz=4)),
                   (ops.ghostzone, dict(t_block=2, bz=4, by=8)),
                   (ops.mwd, dict(d_w=4, n_f=2))]:
        got = fn(spec, state, coeffs, 2, **kw)
        assert got[0].dtype == dtype
        assert _err(want[0], got[0]) < _tol(dtype), fn


def test_mwd_kernel_nonmultiple_grid():
    """Grid sizes not divisible by d_w / n_f / slabs still come out exact."""
    spec = st.SPEC_7C
    state, coeffs = st.make_problem(spec, (11, 19, 13), seed=9)
    want = ref.naive_steps(spec, state, coeffs, 5)
    got = ops.mwd(spec, state, coeffs, 5, d_w=8, n_f=4)
    assert _err(want[0], got[0]) < 5e-4


# Kernel vs oracle: the kernel evaluates the oracle's expression cell for
# cell, in the same order, but reads its operands from aligned VMEM windows.
# XLA:CPU contracts a different subset of the variable-coefficient
# multiply-adds into FMAs at those shapes, so those results may move by an
# ulp. The budget: within ULP_BUDGET ulps of the field's largest magnitude.
# Constant-coefficient ops stay bitwise.
ULP_BUDGET = 2
BITWISE_OPS = ("7pt-const", "25pt-const")


@pytest.mark.parametrize("name", list(st.SPECS))
def test_fused_mwd_matches_oracle_bitwise(name):
    """The single-launch fused schedule == run_mwd oracle, both parities,
    all four corner-case stencils (interpret mode): bitwise for the
    constant-coefficient ops, within ULP_BUDGET for the others."""
    import numpy as np

    from repro.core import mwd

    spec = st.SPECS[name]
    shape = (10, 20, 24) if spec.radius == 1 else (13, 21, 18)
    d_w, n_f = 4 * spec.radius, 2
    state, coeffs = st.make_problem(spec, shape, seed=11)
    t_steps = 5
    want = mwd.run_mwd(spec, state, coeffs, t_steps, mwd.MWDPlan(d_w=d_w))
    got = ops.mwd(spec, state, coeffs, t_steps, d_w=d_w, n_f=n_f, fused=True)
    for w, g in zip(want, got):
        w, g = np.asarray(w), np.asarray(g)
        if name in BITWISE_OPS:
            np.testing.assert_array_equal(w, g)
        else:
            budget = ULP_BUDGET * np.spacing(np.abs(w).max())
            assert np.abs(w - g).max() <= budget


@pytest.mark.parametrize("name", list(st.SPECS))
def test_fused_equals_per_row_launches(name):
    """One launch for the whole schedule == one launch per diamond row."""
    import numpy as np

    spec = st.SPECS[name]
    shape = (10, 20, 24) if spec.radius == 1 else (13, 21, 18)
    d_w, n_f = 2 * spec.radius, 2 * spec.radius
    state, coeffs = st.make_problem(spec, shape, seed=12)
    fused = ops.mwd(spec, state, coeffs, 4, d_w=d_w, n_f=n_f, fused=True)
    rows = ops.mwd(spec, state, coeffs, 4, d_w=d_w, n_f=n_f, fused=False)
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(rows[0]))
    np.testing.assert_array_equal(np.asarray(fused[1]), np.asarray(rows[1]))


def test_mwd_zero_steps_is_identity():
    """T=0 compiles to an empty schedule; both modes return state unchanged."""
    import numpy as np

    spec = st.SPEC_7C
    state, coeffs = st.make_problem(spec, (8, 12, 10), seed=0)
    for fused in (True, False):
        out = ops.mwd(spec, state, coeffs, 0, d_w=4, n_f=2, fused=fused)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(state[0]))


def test_fused_mwd_nonmultiple_grid_and_dtype():
    spec = st.SPEC_7C
    state, coeffs = st.make_problem(spec, (11, 19, 13), seed=9)
    want = ref.naive_steps(spec, state, coeffs, 5)
    got = ops.mwd(spec, state, coeffs, 5, d_w=8, n_f=4, fused=True)
    assert _err(want[0], got[0]) < 5e-4
    state, coeffs = st.make_problem(spec, (8, 16, 16), dtype=jnp.bfloat16,
                                    seed=5)
    want = ref.naive_steps(spec, state, coeffs, 2)
    got = ops.mwd(spec, state, coeffs, 2, d_w=4, n_f=2, fused=True)
    assert got[0].dtype == jnp.bfloat16
    assert _err(want[0], got[0]) < _tol(jnp.bfloat16)
