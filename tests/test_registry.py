"""Tuned-plan registry: round-trip, registry-first resolution, invalidation.

Pure Python + interpret-mode kernels — no TPU. Each test points
$REPRO_PLAN_REGISTRY at its own tmp file, so the process-wide default
registry cache never leaks state across tests.
"""

import jax
import dataclasses
import json
import math

import pytest

from repro import hw
from repro.core import autotune, registry as reg, stencils as st
from repro.core.mwd import MWDPlan

SPEC = st.SPECS["7pt-const"]
GRID = (8, 14, 10)


def test_roundtrip_save_load(tmp_path):
    path = str(tmp_path / "plans.json")
    r = reg.PlanRegistry(path)
    plan = MWDPlan(d_w=4, n_f=2, fused=False)
    r.put(SPEC, GRID, plan, 3.14, source="measured", evals=7)

    r2 = reg.PlanRegistry(path)          # fresh load from disk
    got = r2.get(SPEC, GRID)
    assert got is not None
    assert got.plan == plan
    assert got.score == 3.14
    assert got.source == "measured"
    assert got.evals == 7
    assert got.fingerprint == hw.fingerprint()


def test_key_includes_grid_word_and_devices(tmp_path):
    r = reg.PlanRegistry(str(tmp_path / "plans.json"))
    r.put(SPEC, GRID, MWDPlan(d_w=4), 1.0)
    assert r.get(SPEC, (8, 14, 12)) is None
    assert r.get(SPEC, GRID, word_bytes=8) is None
    assert r.get(SPEC, GRID, devices_x=2) is None
    assert r.get(st.SPECS["7pt-var"], GRID) is None
    assert r.get(SPEC, GRID) is not None


def test_stale_fingerprint_invalidated(tmp_path):
    path = str(tmp_path / "plans.json")
    r = reg.PlanRegistry(path)
    r.put(SPEC, GRID, MWDPlan(d_w=4), 1.0, fingerprint="old-hardware")
    # lookup under the real fingerprint: stale -> miss
    assert r.get(SPEC, GRID) is None
    # and the stale entry is pruned from the next save
    r.put(SPEC, (9, 9, 9), MWDPlan(d_w=2), 2.0)
    with open(path) as f:
        on_disk = json.load(f)["plans"]
    assert list(on_disk) == [reg.plan_key(SPEC, (9, 9, 9))]


def test_corrupt_or_missing_file_is_empty(tmp_path):
    missing = reg.PlanRegistry(str(tmp_path / "nope.json"))
    assert len(missing) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert len(reg.PlanRegistry(str(bad))) == 0
    wrong_ver = tmp_path / "ver.json"
    wrong_ver.write_text(json.dumps({"version": 99, "plans": {
        "x": {"plan": {}, "score": 1, "source": "m", "fingerprint": "f"}}}))
    assert len(reg.PlanRegistry(str(wrong_ver))) == 0


def test_put_sanitizes_kernel_invalid_nf(tmp_path):
    r = reg.PlanRegistry(str(tmp_path / "plans.json"))
    entry = r.put(SPEC, GRID, MWDPlan(d_w=8, n_f=3), 1.0)
    assert entry.plan.d_w % entry.plan.n_f == 0


def test_load_sanitizes_hand_edited_file(tmp_path):
    """A hand-edited registry cannot crash a launch or poison other entries."""
    fp = hw.fingerprint()
    path = tmp_path / "plans.json"
    entry = {"plan": {"d_w": 8, "n_f": 3}, "score": 1.0,
             "source": "measured", "fingerprint": fp}
    bad_nf0 = {"plan": {"d_w": 8, "n_f": 0}, "score": 1.0,
               "source": "measured", "fingerprint": fp}
    garbage = {"plan": {"d_w": 0, "n_f": 1}, "score": 1.0,
               "source": "measured", "fingerprint": fp}
    wrong_geometry = {"plan": {"d_w": 6, "n_f": 1}, "score": 1.0,
                      "source": "measured", "fingerprint": fp}
    path.write_text(json.dumps({"version": reg.SCHEMA_VERSION, "plans": {
        reg.plan_key(SPEC, GRID): entry,
        reg.plan_key(SPEC, (1, 1, 1)): bad_nf0,
        reg.plan_key(SPEC, (2, 2, 2)): garbage,
        reg.plan_key(st.SPECS["25pt-const"], GRID): wrong_geometry}}))
    r = reg.PlanRegistry(str(path))
    got = r.get(SPEC, GRID)
    assert got is not None and got.plan.d_w % got.plan.n_f == 0
    nf0 = r.get(SPEC, (1, 1, 1))
    assert nf0 is not None and nf0.plan.n_f >= 1    # clamped, not crashing
    assert r.get(SPEC, (2, 2, 2)) is None           # unusable: dropped
    # d_w=6 is not a multiple of 2R=8 for the 25pt stencil: treated as miss
    assert r.get(st.SPECS["25pt-const"], GRID) is None


def test_resolve_registry_first_then_model(tmp_path, monkeypatch):
    r = reg.PlanRegistry(str(tmp_path / "plans.json"))
    cached = MWDPlan(d_w=4, n_f=1)
    r.put(SPEC, GRID, cached, 9.0)
    # a registry hit must never enter the search
    monkeypatch.setattr(autotune, "autotune",
                        lambda *a, **k: pytest.fail("searched on a hit"))
    plan, source = r.resolve(SPEC, GRID)
    assert (plan, source) == (cached, "registry:measured")

    monkeypatch.undo()
    plan, source = r.resolve(SPEC, (8, 14, 12))     # miss -> model fallback
    assert source == "model"
    assert plan.d_w % plan.n_f == 0
    score = autotune.model_score(SPEC, (8, 14, 12))
    assert score(plan) >= score(MWDPlan())
    assert not math.isinf(score(plan))

    # the fallback is memoized: a second miss resolves without re-searching
    monkeypatch.setattr(autotune, "autotune",
                        lambda *a, **k: pytest.fail("re-searched a memo hit"))
    assert r.resolve(SPEC, (8, 14, 12)) == (plan, "model")


def test_ops_mwd_auto_uses_registry(tmp_path, monkeypatch):
    """ops.mwd(plan="auto") resolves registry-first and runs that plan."""
    from repro.kernels import ops

    path = str(tmp_path / "plans.json")
    monkeypatch.setenv(reg.ENV_VAR, path)
    reg.PlanRegistry(path).put(SPEC, GRID, MWDPlan(d_w=4, n_f=2), 5.0)
    monkeypatch.setattr(autotune, "autotune",
                        lambda *a, **k: pytest.fail("searched on a hit"))

    state, coeffs = st.make_problem(SPEC, GRID, seed=0)
    import numpy as np
    got = ops.mwd(SPEC, state, coeffs, 3, plan="auto")
    want = ops.mwd(SPEC, state, coeffs, 3, d_w=4, n_f=2, fused=True)
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    assert (np.asarray(got[1]) == np.asarray(want[1])).all()


def test_ops_mwd_rejects_unknown_plan_string():
    from repro.kernels import ops

    state, coeffs = st.make_problem(SPEC, GRID, seed=0)
    with pytest.raises(ValueError, match="auto"):
        ops.mwd(SPEC, state, coeffs, 1, plan="fastest")


def test_tune_cli_second_run_measures_nothing(tmp_path, monkeypatch):
    """Acceptance: re-tuning the same (stencil, grid, fingerprint) is free."""
    from repro.launch import tune

    calls = {"n": 0}
    real_measure_score = autotune.measure_score

    def counting_measure_score(spec, grid_shape, *a, **k):
        # model-speed stand-in that still counts "measurements" the way the
        # real scorer does, so the zero-measurement claim is load-bearing
        inner = autotune.model_score(spec, grid_shape)

        def score(plan):
            s = inner(plan)
            if not math.isinf(s):
                calls["n"] += 1
                score.measurements += 1
            return s

        score.measurements = 0
        return score

    assert callable(real_measure_score)
    monkeypatch.setattr(autotune, "measure_score", counting_measure_score)
    path = str(tmp_path / "plans.json")

    first = tune.main(["--stencil", "7pt-const", "--registry", path])
    assert first[0]["source"] == "measured"
    assert first[0]["measurements"] > 0
    assert calls["n"] == first[0]["measurements"]

    calls["n"] = 0
    second = tune.main(["--stencil", "7pt-const", "--registry", path])
    assert second[0]["source"] == "cached"
    assert second[0]["measurements"] == 0
    assert calls["n"] == 0                       # zero measurements ran
    assert second[0]["plan"] == first[0]["plan"]


def test_tune_measured_upgrades_model_entry(tmp_path, monkeypatch):
    """A measured run re-tunes a key that only has a model-scored entry."""
    from repro.launch import tune

    def fake_measure_score(spec, grid_shape, *a, **k):
        inner = autotune.model_score(spec, grid_shape)

        def score(plan):
            s = inner(plan)
            if not math.isinf(s):
                score.measurements += 1
            return s

        score.measurements = 0
        return score

    monkeypatch.setattr(autotune, "measure_score", fake_measure_score)
    path = str(tmp_path / "plans.json")
    model = tune.main(["--stencil", "7pt-const", "--registry", path,
                       "--model-only"])
    assert model[0]["source"] == "model"
    measured = tune.main(["--stencil", "7pt-const", "--registry", path])
    assert measured[0]["source"] == "measured"   # upgraded, not "cached"
    assert measured[0]["measurements"] > 0
    # and now the measured entry is sticky
    again = tune.main(["--stencil", "7pt-const", "--registry", path])
    assert again[0]["source"] == "cached"


def test_measure_score_times_real_launch():
    """One real measured eval: positive GLUP/s, prune skips measurement."""
    scorer = autotune.measure_score(SPEC, (6, 10, 8), n_steps=2, reps=2,
                                    warmup=1)
    s = scorer(MWDPlan(d_w=2, n_f=1))
    assert s > 0 and scorer.measurements == 1
    assert scorer(MWDPlan(d_w=2, n_f=3)) == -math.inf   # kernel-invalid
    assert scorer(MWDPlan(d_w=3, n_f=1)) == -math.inf   # 2R does not divide
    assert scorer.measurements == 1                      # pruned, not timed


def test_run_distributed_accepts_auto_plan(tmp_path, monkeypatch):
    """The stepper resolves plan="auto" registry-first (single process),
    keyed on the PER-SHARD extended block shape the kernel launches on."""
    import numpy as np

    from repro.core import stencils
    from repro.distributed import stepper

    path = str(tmp_path / "plans.json")
    monkeypatch.setenv(reg.ENV_VAR, path)
    spec = stencils.SPECS["7pt-const"]
    shape = (8, 12, 10)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape_e = stepper.local_extended_shape(spec, mesh, shape, t_block=2)
    assert shape_e == (12, 16, 14)      # +2g on every axis, g = R*t_block
    reg.PlanRegistry(path).put(spec, shape_e, MWDPlan(d_w=4, n_f=2), 5.0)
    monkeypatch.setattr(autotune, "autotune",
                        lambda *a, **k: pytest.fail("searched on a hit"))

    state, coeffs = stencils.make_problem(spec, shape, seed=3)
    out = stepper.run_distributed(spec, mesh, state, coeffs, 4, t_block=2,
                                  plan="auto")
    want = stencils.run_naive(spec, state, coeffs, 4)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want[0]),
                               rtol=0, atol=1e-5)


def test_fingerprint_stable_and_sensitive():
    assert hw.fingerprint() == hw.fingerprint()
    other = dataclasses.replace(hw.V5E, hbm_bw=hw.V5E.hbm_bw * 2)
    assert hw.fingerprint(other) != hw.fingerprint()


def test_same_name_ops_do_not_collide(tmp_path):
    """Two user-defined ops sharing a display name get distinct plan keys
    (the key embeds the structural IR fingerprint)."""
    from repro.core import ir

    base = [ir.Tap(0, 0, 0, ir.const(0)),
            ir.Tap(0, 0, -1, ir.const(1)), ir.Tap(0, 0, 1, ir.const(1))]
    op_a = ir.StencilOp("custom", tuple(base))
    op_b = ir.StencilOp("custom", tuple(base + [
        ir.Tap(0, -1, 0, ir.const(1)), ir.Tap(0, 1, 0, ir.const(1))]))
    assert op_a.fingerprint != op_b.fingerprint
    assert reg.plan_key(op_a, GRID) != reg.plan_key(op_b, GRID)

    r = reg.PlanRegistry(str(tmp_path / "plans.json"))
    r.put(op_a, GRID, MWDPlan(d_w=4, n_f=1), 1.0)
    r.put(op_b, GRID, MWDPlan(d_w=8, n_f=2), 2.0)
    assert r.get(op_a, GRID).plan == MWDPlan(d_w=4, n_f=1)
    assert r.get(op_b, GRID).plan == MWDPlan(d_w=8, n_f=2)


def test_plan_key_rejects_bare_names():
    """A bare name would persist under a key the next load() drops; refuse."""
    with pytest.raises(TypeError, match="StencilOp"):
        reg.plan_key("7pt-const", GRID)


def test_legacy_name_only_keys_invalidated(tmp_path):
    """Pre-IR registry files keyed by bare stencil name are dropped at load
    (graceful invalidation: the entry re-tunes instead of colliding)."""
    fp = hw.fingerprint()
    path = tmp_path / "plans.json"
    legacy_key = f"7pt-const|{GRID[0]}x{GRID[1]}x{GRID[2]}|w4|dx1"
    good_key = reg.plan_key(SPEC, GRID)
    entry = {"plan": {"d_w": 4, "n_f": 2}, "score": 1.0,
             "source": "measured", "fingerprint": fp}
    path.write_text(json.dumps({"version": reg.SCHEMA_VERSION, "plans": {
        legacy_key: entry, good_key: dict(entry, score=2.0)}}))
    r = reg.PlanRegistry(str(path))
    assert len(r) == 1                      # legacy entry never loaded
    got = r.get(SPEC, GRID)
    assert got is not None and got.score == 2.0
    r.save()                                # and the file is rewritten clean
    assert list(json.load(open(path))["plans"]) == [good_key]
