"""Per-kernel validation vs the pure-jnp oracle: shape & dtype sweeps."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import ir
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


def _mwd_cases(base, *more):
    """Kernel cases per op: `base` keeps the op's name as its id."""
    return [pytest.param(name, *case, id="-".join(
                [name] + [f"{v}" for v in case]) if case != base else name)
            for name in st.SPECS for case in (base,) + more]


def _domain_oracle(spec, state, coeffs, t_steps, interior):
    """Plain masked time stepping: cells outside `interior` are held."""
    from repro.kernels import stencil_mwd

    arrays, scalars = ir.split_coeffs(spec, coeffs)
    scalars = tuple(float(x) for x in scalars)   # inlined, as in the kernel
    cur, prev = state
    bufs = [cur, stencil_mwd.sync_dirichlet_frame(cur, prev, spec.radius)]
    lo_z, hi_z, lo_y, hi_y, lo_x, hi_x = interior
    held = jnp.ones(cur.shape, bool).at[lo_z:hi_z, lo_y:hi_y,
                                        lo_x:hi_x].set(False)
    for t in range(t_steps):
        src, dst = bufs[t % 2], bufs[1 - t % 2]
        new = ir.make_sweep(spec)(src, dst, arrays, scalars)
        bufs[1 - t % 2] = jnp.where(held, dst, new)
    p = t_steps % 2
    return bufs[p], bufs[1 - p]


# (d_w / R, N_F, steps, call). Steps well below T = d_w/R and not a
# multiple of H leave most in-tile levels empty; d_w = 14R makes the owned
# rows start at varying offsets within the sublane tile; N_F = 1 slabs are
# held by the level's guard alone. "batched" runs the case as entry 1 of a
# two-grid launch; "domain" is the distributed stepper's call: the
# tessellation spans the whole y extent and a tighter interior holds the
# cells around it. "tall" runs on a grid TALL_NZ planes deep, where each
# tile's window ring wraps many times; with N_F = 3, which divides no
# z_ws = N_F + d_w + R here, slabs straddle the wrap.
TALL_NZ = {1: 40, 4: 48}


@pytest.mark.parametrize("name,k,n_f,t_steps,call", _mwd_cases(
    (4, 2, 5, "single"), (16, 1, 1, "single"), (16, 2, 3, "single"),
    (16, 1, 21, "single"), (14, 2, 5, "single"), (16, 1, 3, "batched"),
    (14, 1, 5, "domain"), (2, 1, 5, "tall"), (6, 3, 5, "tall"),
    (2, 1, 5, "tall-batched")))
def test_fused_mwd_matches_oracle_bitwise(name, k, n_f, t_steps, call):
    """The single-launch fused schedule == run_mwd oracle, both parities,
    all four corner-case stencils (interpret mode): bitwise for the
    constant-coefficient ops, within ULP_BUDGET for the others (and for
    every op in the domain case, whose oracle is `_domain_oracle`)."""
    import numpy as np

    from repro.core import mwd
    from repro.kernels import stencil_mwd

    spec = st.SPECS[name]
    r = spec.radius
    shape = (10, 20, 24) if r == 1 else (13, 21, 18)
    d_w = k * r
    if call.startswith("tall"):
        shape = (TALL_NZ[r],) + shape[1:]
        geo = stencil_mwd.launch_geometry(r, d_w, n_f, shape, 4, t_steps)
        assert geo.n_j > 2 * geo.win.z_ws / n_f      # wraps more than twice
        assert n_f == 1 or geo.win.z_ws % n_f        # slabs straddle the wrap
    state, coeffs = st.make_problem(spec, shape, seed=11)
    if call == "domain":
        nz, ny, nx = shape
        interior = (r + 1, nz - r - 2, r + 2, ny - r - 1, r + 1, nx - r - 3)
        want = _domain_oracle(spec, state, coeffs, t_steps, interior)
        arrays, scalars = ir.split_coeffs(spec, coeffs)
        got = jax.jit(lambda b: stencil_mwd.mwd_run(
            spec, state, arrays, scalars, t_steps, d_w=d_w, n_f=n_f,
            interior=b, y_domain=(0, ny)))(jnp.asarray(interior, jnp.int32))
    else:
        want = mwd.run_mwd(spec, state, coeffs, t_steps, mwd.MWDPlan(d_w=d_w))
        if call.endswith("batched"):
            other, other_c = st.make_problem(spec, shape, seed=13)
            got = ops.mwd_batched(spec, [other, state], [other_c, coeffs],
                                  t_steps, d_w=d_w, n_f=n_f)
            got = [g[1] for g in got]
        else:
            got = ops.mwd(spec, state, coeffs, t_steps, d_w=d_w, n_f=n_f,
                          fused=True)
    for w, g in zip(want, got):
        w, g = np.asarray(w), np.asarray(g)
        # the domain oracle sweeps the whole grid at once, so XLA:CPU
        # contracts its multiply-adds differently for every op
        if name in BITWISE_OPS and call != "domain":
            np.testing.assert_array_equal(w, g)
        else:
            if call == "domain":            # held cells are copies
                lo_z, hi_z, lo_y, hi_y, lo_x, hi_x = interior
                held = np.ones(w.shape, bool)
                held[lo_z:hi_z, lo_y:hi_y, lo_x:hi_x] = False
                np.testing.assert_array_equal(w[held], g[held])
            budget = ULP_BUDGET * np.spacing(np.abs(w).max())
            assert np.abs(w - g).max() <= budget


@pytest.mark.parametrize("name,k,t_steps", _mwd_cases(
    (2, 4), (16, 3), (14, 9), (2, 3)))
def test_fused_equals_per_row_launches(name, k, t_steps):
    """One launch for the whole schedule == one launch per diamond row."""
    import numpy as np

    spec = st.SPECS[name]
    shape = (10, 20, 24) if spec.radius == 1 else (13, 21, 18)
    d_w, n_f = k * spec.radius, 2 * spec.radius
    state, coeffs = st.make_problem(spec, shape, seed=12)
    fused = ops.mwd(spec, state, coeffs, t_steps, d_w=d_w, n_f=n_f,
                    fused=True)
    rows = ops.mwd(spec, state, coeffs, t_steps, d_w=d_w, n_f=n_f,
                   fused=False)
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(rows[0]))
    np.testing.assert_array_equal(np.asarray(fused[1]), np.asarray(rows[1]))


# The benchmark cells' launches (512^3, plan auto on a v5e): non-empty
# levels and the rows each computes, as shares of the rows a masked update
# of the whole span at every level computed.
@pytest.mark.parametrize("name,d_w,t_steps,non_empty,computed,useful", [
    ("7pt-var", 70, 64, 0.586, 0.343, 0.243),
    ("25pt-const", 128, 64, 0.747, 0.414, 0.342),
    ("7pt-var", 70, 4, 0.050, 0.033, 0.024)])
def test_update_work_at_the_benchmark_cells(name, d_w, t_steps, non_empty,
                                            computed, useful):
    import numpy as np

    from repro.kernels import stencil_mwd

    spec = st.SPECS[name]
    work = stencil_mwd.update_work(spec, (512, 512, 512), t_steps, d_w, 1)
    shares = work.shares()
    assert shares == pytest.approx(
        {"non_empty": non_empty, "computed": computed, "useful": useful},
        abs=5e-4)
    assert (work.computed >= work.useful).all()
    assert (work.computed <= work.full_span).all()
    assert ((work.computed > 0) == (work.useful > 0)).all()
    assert stencil_mwd.update_work(spec, (512, 512, 512), t_steps, d_w,
                                   1) is work       # memoized
    assert np.all(work.computed % 8 == 0)           # whole sublane tiles


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
