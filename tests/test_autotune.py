"""Auto-tuner: feasibility, model pruning, thread-group selection."""

import math

from repro import hw
from repro.core import autotune, models, stencils as st


def test_result_is_feasible():
    for name, spec in st.SPECS.items():
        res = autotune.autotune(spec, (256, 256, 256), devices_x=1)
        assert models.vmem_fits(spec, res.plan.d_w, res.plan.n_f,
                                256 // res.plan.tg_x)
        assert res.score > 0


def test_hillclimb_beats_minimal_plan():
    spec = st.SPECS["7pt-var"]
    score = autotune.model_score(spec, (512, 512, 512))
    res = autotune.autotune(spec, (512, 512, 512), devices_x=1)
    from repro.core.mwd import MWDPlan
    baseline = score(MWDPlan(d_w=2 * spec.radius, n_f=1))
    assert res.score >= baseline


def test_group_sharing_selected_for_fat_stencil():
    """The paper's core claim: the memory-starved 25pt-var stencil picks a
    device group > 1 (cache-block sharing) when devices are available."""
    res = autotune.autotune(st.SPECS["25pt-var"], (1024, 1024, 1024),
                            devices_x=8)
    assert res.plan.tg_x > 1


def test_light_stencil_prefers_private_tiles():
    res = autotune.autotune(st.SPECS["7pt-const"], (256, 256, 256),
                            devices_x=8)
    assert res.plan.tg_x in (1, 2)


def test_seed_dw_respects_vmem(monkeypatch):
    spec = st.SPECS["25pt-var"]
    d = autotune._seed_d_w(spec, 2048, hw.V5E)
    assert models.vmem_fits(spec, d, 1, 2048)
    assert not models.vmem_fits(spec, d + 2 * spec.radius, 1, 2048)


def test_fused_execution_preferred():
    """The single-launch schedule saves inter-row streams + dispatches, so
    the tuner keeps fused=True and scores it above the per-row mode."""
    import dataclasses
    for name in ("7pt-const", "25pt-var"):
        spec = st.SPECS[name]
        res = autotune.autotune(spec, (512, 512, 512), devices_x=2)
        assert res.plan.fused
        score = autotune.model_score(spec, (512, 512, 512))
        assert score(res.plan) > score(
            dataclasses.replace(res.plan, fused=False))


def test_evaluations_bounded():
    res = autotune.autotune(st.SPECS["7pt-const"], (512, 512, 512),
                            devices_x=16, max_evals=16)
    assert len(res.evaluated) <= 16
