"""Reduced-precision accuracy harness: per-op error budgets vs the f64 oracle.

The contract under test (`StencilOp.tolerance`): an MWD advance whose data
STREAMS are bf16/fp16 (float32 in-tile accumulation, the `acc="auto"`
default) must stay element-wise within the op's declared ``(atol, rtol)``
budget of the float64 naive reference. Three directions keep the budgets
honest:

* every paper op AND a custom IR op satisfy their budget across random
  grids / step counts / seeds (hypothesis, degrading to examples without it),
* the budgets are TIGHT: a 10x-tightened budget must fail for at least one
  op per reduced dtype (the calibrated budgets sit ~4x above the observed
  worst case, so padding them 10x looser would be caught here),
* f32 problems are bitwise-unchanged by the accumulator plumbing (native
  accumulation inserts no casts).

The oracle pattern: problems are GENERATED at f32 (the values the reduced
run actually sees) and cast UP to f64 for the reference, so the comparison
isolates the stream/accumulate rounding, not input quantization. Also pins
the word-size defaults (`precision.DEFAULT_WORD_BYTES`) that models/traffic
historically disagreed on (models defaulted to the paper's w8, traffic to
w4 — an Eq. 5 curve and an exact DMA counter called with defaults silently
mixed word sizes).
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ir, models, precision, traffic
from repro.core import stencils as st
from repro.kernels import ops
from tests._hyp import HAVE_HYPOTHESIS, given, settings, strategies

# A user-defined operator (deliberately NOT one of the paper's four and NOT
# registered): no explicit error_budget, so it exercises the eps-scaled
# default tolerance fallback end-to-end.
_CUSTOM = ir.StencilOp(
    "precision-custom7",
    tuple([ir.Tap(0, 0, 0, ir.array(0))]
          + [ir.Tap(dz, dy, dx, ir.array(1))
             for dz, dy, dx in [(-1, 0, 0), (1, 0, 0), (0, -1, 0),
                                (0, 1, 0), (0, 0, -1), (0, 0, 1)]]),
    coeff_scale=0.08)

REDUCED = ("bf16", "fp16")
PROP_OPS = ("7pt-const", "7pt-var", "25pt-const", "25pt-var", "custom")

# naive-reference-friendly grids per radius (the radius-4 operators need
# nz > 2R interior and y room for a D_w = 2R = 8 diamond)
_GRIDS_R1 = ((6, 8, 8), (8, 12, 10), (10, 8, 12))
_GRIDS_R4 = ((16, 20, 16), (12, 24, 18))


def _op(name: str) -> ir.StencilOp:
    return _CUSTOM if name == "custom" else ir.OPS[name]


def _budget_excess(op, grid, n_steps, dtype, seed=0, tighten=1.0):
    """max over cells of |got - ref64| - (atol + rtol*|ref64|), and out dtype.

    <= 0 means the advance is inside the (optionally tightened) budget.
    """
    state, coeffs = ir.make_problem(op, grid, seed=seed)        # f32 inputs
    with jax.enable_x64(True):
        st64, co64 = jax.tree_util.tree_map(
            lambda x: jnp.asarray(np.asarray(x, np.float64)), (state, coeffs))
        ref = np.asarray(st.run_naive(op, st64, co64, n_steps)[0], np.float64)
    d_w = 8 if op.radius > 1 else 4
    got = ops.mwd(op, state, coeffs, n_steps, d_w=d_w, n_f=2, dtype=dtype)
    out = np.asarray(got[0], np.float64)
    atol, rtol = op.tolerance(dtype)
    excess = np.abs(out - ref) - tighten * (atol + rtol * np.abs(ref))
    return float(excess.max()), got[0].dtype


# ---------------------------------------------------------------------------
# the budget contract: every op, both reduced dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", REDUCED)
@pytest.mark.parametrize("name", PROP_OPS)
def test_reduced_stream_within_budget(name, dtype):
    op = _op(name)
    grid = _GRIDS_R4[0] if op.radius > 1 else _GRIDS_R1[1]
    excess, out_dt = _budget_excess(op, grid, 2, dtype)
    assert excess <= 0.0, (name, dtype, excess)
    assert out_dt == precision.parse_dtype(dtype)   # streams stayed reduced


@pytest.mark.parametrize("name", PROP_OPS)
@settings(max_examples=4, deadline=None)
@given(data=strategies.data())
def test_budget_property(name, data):
    """Random grid / steps / seed / dtype stay inside the declared budget."""
    op = _op(name)
    grids = _GRIDS_R4 if op.radius > 1 else _GRIDS_R1
    grid = data.draw(strategies.sampled_from(grids))
    n_steps = data.draw(strategies.integers(min_value=1, max_value=3))
    seed = data.draw(strategies.integers(min_value=0, max_value=3))
    dtype = data.draw(strategies.sampled_from(REDUCED))
    excess, _ = _budget_excess(op, grid, n_steps, dtype, seed=seed)
    assert excess <= 0.0, (name, grid, n_steps, seed, dtype, excess)


@pytest.mark.parametrize("dtype", REDUCED)
def test_budgets_are_tight(dtype):
    """A 10x-tightened budget must FAIL for at least one op per dtype.

    Guards against budget padding: the declared budgets sit ~4x above the
    calibrated worst case, so /10 lands below the error actually observed.
    """
    failed = []
    for name in ("7pt-const", "7pt-var"):
        excess, _ = _budget_excess(ir.OPS[name], (8, 12, 10), 5, dtype,
                                   tighten=0.1)
        if excess > 0.0:
            failed.append(name)
    assert failed, f"10x-tightened {dtype} budget did not fail any op"


def test_f32_native_accumulation_bitwise():
    """f32 problems: the acc plumbing inserts no casts (bitwise identity)."""
    op = ir.OPS["7pt-var"]
    state, coeffs = ir.make_problem(op, (8, 12, 10), seed=0)
    a = ops.mwd(op, state, coeffs, 3, d_w=4, n_f=2)              # acc="auto"
    b = ops.mwd(op, state, coeffs, 3, d_w=4, n_f=2, acc="native")
    c = ops.mwd(op, state, coeffs, 3, d_w=4, n_f=2, dtype="f32", acc="f32")
    assert a[0].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_bf16_explicit_f32_acc_matches_auto():
    """acc="auto" on a sub-32-bit stream IS f32 accumulation (bitwise)."""
    op = ir.OPS["7pt-const"]
    state, coeffs = ir.make_problem(op, (6, 8, 8), seed=1)
    a = ops.mwd(op, state, coeffs, 2, d_w=4, n_f=2, dtype="bf16")
    b = ops.mwd(op, state, coeffs, 2, d_w=4, n_f=2, dtype="bf16", acc="f32")
    assert a[0].dtype == precision.parse_dtype("bf16")
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


# ---------------------------------------------------------------------------
# precision module itself
# ---------------------------------------------------------------------------

def test_parse_dtype_and_names():
    assert precision.parse_dtype(None) == np.dtype(np.float32)
    for alias, name in (("float32", "f32"), ("fp32", "f32"), ("half", "fp16"),
                        ("f16", "fp16"), ("bfloat16", "bf16"),
                        ("double", "f64")):
        assert precision.dtype_name(precision.parse_dtype(alias)) == name
    assert precision.parse_dtype(jnp.bfloat16) == precision.parse_dtype("bf16")
    with pytest.raises(ValueError, match="unknown dtype"):
        precision.parse_dtype("int7")


def test_word_bytes_by_dtype():
    assert precision.word_bytes() == precision.DEFAULT_WORD_BYTES == 4
    assert precision.word_bytes("bf16") == 2
    assert precision.word_bytes("fp16") == 2
    assert precision.word_bytes("f64") == 8


def test_finfo_understands_bfloat16():
    assert float(precision.finfo("bf16").eps) == 2.0 ** -8 * 2  # 0.0078125
    assert float(precision.finfo("fp16").eps) == 2.0 ** -10


def test_resolve_acc_policy():
    f32 = np.dtype(np.float32)
    assert precision.resolve_acc("bf16") == f32
    assert precision.resolve_acc("fp16", "auto") == f32
    assert precision.resolve_acc("f32", "auto") is None
    assert precision.resolve_acc("bf16", "native") is None
    assert precision.resolve_acc("bf16", None) is None
    assert precision.resolve_acc("bf16", "f32") == f32
    assert precision.resolve_acc("f32", "f32") is None   # same-dtype: native


def test_default_tolerance_scales_with_eps():
    """Ops without a declared budget fall back to k*eps per dtype."""
    k = 4.0 * len(_CUSTOM.taps)
    for dt in REDUCED + ("f32",):
        eps = float(precision.finfo(dt).eps)
        assert _CUSTOM.tolerance(dt) == (k * eps, k * eps)
    # declared budgets win over the fallback
    assert ir.OPS["7pt-const"].tolerance("bf16") == (0.03, 0.003)
    assert ir.OPS["25pt-const"].tolerance("bf16") == (1.2, 0.12)


# ---------------------------------------------------------------------------
# word-size default regression (the models-w8 vs traffic-w4 split)
# ---------------------------------------------------------------------------

def test_word_size_defaults_agree_everywhere():
    """No Eq. 5 / traffic callable may default to a different word size."""
    seen = 0
    for mod in (models, traffic):
        for _, fn in inspect.getmembers(mod, inspect.isfunction):
            if fn.__module__ != mod.__name__:
                continue
            for p in inspect.signature(fn).parameters.values():
                if p.name in ("word_bytes", "word") and isinstance(
                        p.default, int):
                    assert p.default == precision.DEFAULT_WORD_BYTES, fn
                    seen += 1
    sig = inspect.signature(ir.StencilOp.spatial_code_balance)
    assert (sig.parameters["word_bytes"].default
            == precision.DEFAULT_WORD_BYTES)
    assert seen >= 4    # the scan actually found the model/traffic family


def test_eq5_and_traffic_agree_and_scale_with_word():
    spec = st.SPECS["7pt-const"]
    bc = models.code_balance(spec, 8)
    assert bc == models.code_balance(
        spec, 8, word_bytes=precision.DEFAULT_WORD_BYTES)
    assert models.code_balance(spec, 8, word_bytes=2) == pytest.approx(bc / 2)

    tr = traffic.mwd_run_traffic(spec, (8, 16, 8), 2, 8, 2)
    tr4 = traffic.mwd_run_traffic(spec, (8, 16, 8), 2, 8, 2,
                                  word=precision.DEFAULT_WORD_BYTES)
    assert tr["bytes"] == tr4["bytes"]
    tr2 = traffic.mwd_run_traffic(spec, (8, 16, 8), 2, 8, 2,
                                  word=precision.word_bytes("bf16"))
    # bf16 halves every word but tiles 16 sublanes instead of 8, so its
    # aligned windows are taller: at d_w=8 the two cancel, and at a wide
    # diamond bf16 moves a little more than half the f32 bytes
    w4 = models.mwd_window(spec.radius, 8, 2, 8, 4)
    w2 = models.mwd_window(spec.radius, 8, 2, 8, 2)
    assert (w2.s, w2.wy) == (16, 2 * w4.wy)
    assert tr2["bytes"] == tr4["bytes"]
    wide4, wide2 = (traffic.mwd_run_traffic(spec, (8, 256, 8), 2, 64, 2,
                                            word=w)["bytes"] for w in (4, 2))
    assert 0.5 < wide2 / wide4 < 0.6


def test_hypothesis_available_in_ci():
    """CI installs the test extra; the property tests must run for real."""
    import os
    if os.environ.get("CI"):
        assert HAVE_HYPOTHESIS
