"""The paper's analytic models (Eqs. 2-5) against its own worked numbers."""

import pytest

from repro import hw
from repro.core import models
from repro.core.stencils import SPEC_7C, SPEC_7V, SPEC_25C, SPEC_25V, SPECS


def test_eq2_paper_example():
    """Paper Sec. 3.3: D_w=8, N_F=1, R=1, 7pt const -> C_S = 94 * N_xb."""
    assert models.cache_block_bytes(SPEC_7C, d_w=8, n_f=1, n_xb=1) == 94.0


def test_eq5_reduces_to_eq4_at_r1():
    for d_w in (4, 8, 16):
        b5 = models.code_balance(SPEC_7C, d_w, 8)
        # Eq. 4 written directly
        b4 = 16.0 * ((2 * d_w - 2) + (2 * d_w + 2)) / d_w ** 2
        assert abs(b5 - b4) < 1e-9


@pytest.mark.parametrize("spec,expect", [
    (SPEC_7C, 24), (SPEC_7V, 80), (SPEC_25C, 32), (SPEC_25V, 128)])
def test_spatial_balance_paper_values(spec, expect):
    assert models.spatial_code_balance(spec, 8) == expect


def test_code_balance_monotone_and_below_spatial():
    for spec in SPECS.values():
        step = 2 * spec.radius
        prev = float("inf")
        for d_w in (step, 2 * step, 4 * step, 16 * step):
            bc = models.code_balance(spec, d_w, 8)
            assert bc < prev
            prev = bc
        assert models.code_balance(spec, 16 * step, 8) \
            < models.spatial_code_balance(spec, 8)


def test_vmem_fit_boundary():
    spec = SPEC_25V
    fits_small = models.vmem_fits(spec, 8, 1, 1024)
    assert fits_small
    assert not models.vmem_fits(spec, 512, 1, 1024)


def test_ghostzone_redundancy_bounds():
    red = models.ghostzone_redundancy(1, 4, 64, 64)
    assert 1.0 < red < 1.4
    red_deep = models.ghostzone_redundancy(4, 8, 64, 64)
    assert red_deep > red


def test_ecm_hbm_bound_matches_roofline():
    spec = SPEC_7C
    bc = models.spatial_code_balance(spec, 4)
    pred = models.ecm_predict(spec, bc, 1e9)
    roof = hw.V5E.hbm_bw / bc / 1e9
    assert pred.glups <= roof * 1.001
    assert pred.t_hbm >= pred.t_compute  # spatial 7pt is memory-bound on v5e


def test_roofline_terms():
    t = models.roofline(197e12, 819e9, 50e9)
    assert abs(t.t_compute - 1.0) < 1e-9
    assert abs(t.t_memory - 1.0) < 1e-9
    assert abs(t.t_collective - 1.0) < 1e-9
    assert t.dominant in ("compute", "memory", "collective")


def test_energy_split():
    e = models.energy(flops=1e12, hbm_bytes=1e10, runtime_s=0.1)
    assert e.core_j > 0 and e.hbm_j > 0 and e.static_j > 0
    # DRAM energy scales with traffic (the Fig. 19 point)
    e2 = models.energy(flops=1e12, hbm_bytes=1e11, runtime_s=0.1)
    assert e2.hbm_j > 5 * e.hbm_j
