"""Declarative device specs: schema, resolution, latency term, translation.

Pure Python + interpret-mode kernels — no TPU. Every test runs behind the
autouse fixture below, which clears $REPRO_DEVICE_SPEC and the --spec
process override so the process default is always "tpu-v5e" on entry.
"""

import dataclasses
import json
import math
import os
import shutil

import pytest

from repro import compat
from repro.core import autotune, models, registry as reg
from repro.core import specs as devspecs
from repro.core import stencils as st
from repro.core.mwd import MWDPlan

STENCIL = st.SPECS["7pt-const"]
GRID = (8, 14, 10)


@pytest.fixture(autouse=True)
def _clean_spec_state(monkeypatch):
    """Reset the spec resolution state around every test."""
    monkeypatch.delenv(devspecs.ENV_SPEC, raising=False)
    monkeypatch.delenv(devspecs.ENV_SPEC_DIR, raising=False)
    devspecs.set_default_spec(None)
    yield
    devspecs.set_default_spec(None)


# ---------------------------------------------------------------------------
# Resolution + memoization
# ---------------------------------------------------------------------------

def test_get_spec_by_name_and_path():
    by_name = devspecs.get_spec("tpu-v5e")
    assert by_name.name == "tpu-v5e"
    path = os.path.join(devspecs.spec_dirs()[0], "tpu-v5e.json")
    assert devspecs.get_spec(path) == by_name


def test_get_spec_memoized():
    a = devspecs.get_spec("cpu-host")
    b = devspecs.get_spec("cpu-host")
    assert a is b                       # same (path, mtime) -> same object


def test_default_resolution_order(monkeypatch):
    assert devspecs.current_spec().name == devspecs.DEFAULT_SPEC_NAME
    devspecs.set_default_spec("interpret")
    assert devspecs.current_spec().name == "interpret"
    # the env var outranks the CLI override
    monkeypatch.setenv(devspecs.ENV_SPEC, "cpu-host")
    assert devspecs.current_spec().name == "cpu-host"


def test_set_default_spec_validates_before_committing():
    with pytest.raises(devspecs.SpecError):
        devspecs.set_default_spec("no-such-machine")
    assert devspecs.current_spec().name == devspecs.DEFAULT_SPEC_NAME


def test_unknown_spec_name_raises():
    with pytest.raises(devspecs.SpecError, match="no-such-machine"):
        devspecs.get_spec("no-such-machine")


def test_tpu_device_kind_resolves_through_table(monkeypatch):
    """On a TPU the default spec follows device_kind; unknown kinds fail."""
    assert devspecs.spec_name_for_kind("TPU v5 lite") == "tpu-v5e"
    with pytest.raises(devspecs.SpecError, match="TPU v9 imaginary"):
        devspecs.spec_name_for_kind("TPU v9 imaginary")
    import jax

    class Dev:
        device_kind = "TPU v9 imaginary"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(devspecs.SpecError, match="no spec"):
        devspecs.current_spec()
    Dev.device_kind = "TPU v5 lite"
    assert devspecs.current_spec().name == "tpu-v5e"


def test_compile_cache_honours_env_else_checkout_dir(monkeypatch):
    import jax

    from repro import compile_cache

    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == old     # left to JAX
    monkeypatch.delenv(compile_cache.ENV_VAR)
    try:
        path = compile_cache.enable()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".repro_cache", "jax")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# ---------------------------------------------------------------------------
# Schema validation
# ---------------------------------------------------------------------------

def _valid_raw():
    return devspecs.get_spec("cpu-host").to_dict()


def test_roundtrip_to_dict():
    spec = devspecs.get_spec("tpu-v5e")
    rebuilt = devspecs.DeviceSpec(**devspecs.validate_spec_dict(spec.to_dict()))
    assert rebuilt == spec


@pytest.mark.parametrize("mutate, msg", [
    (lambda d: d.pop("hbm_bw"), "missing"),
    (lambda d: d.update(turbo=9), "unknown"),
    (lambda d: d.update(latency_bytes=1.0), "derived"),
    (lambda d: d.update(freq=-1.0), "> 0"),
    (lambda d: d.update(freq="fast"), "number"),
    (lambda d: d.update(ici_links=True), "number"),
    (lambda d: d.update(static_power_w=-5.0), ">= 0"),
    (lambda d: d.update(name=""), "name"),
])
def test_schema_rejects(mutate, msg):
    raw = _valid_raw()
    mutate(raw)
    with pytest.raises(devspecs.SpecError, match=msg):
        devspecs.validate_spec_dict(raw)


def test_schema_rejects_non_object():
    with pytest.raises(devspecs.SpecError, match="object"):
        devspecs.validate_spec_dict([1, 2, 3])


def test_latency_bytes_is_derived():
    v5e = devspecs.get_spec("tpu-v5e")
    assert v5e.latency_bytes == pytest.approx(
        v5e.hbm_bw * v5e.hbm_latency_cycles / v5e.freq)
    assert v5e.latency_bytes == pytest.approx(409500.0)
    assert "latency_bytes" not in v5e.to_dict()


def test_cli_validates_and_rejects(tmp_path, capsys):
    ok = os.path.join(devspecs.spec_dirs()[0], "tpu-v5e.json")
    assert devspecs.main([ok]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"name": "bad"}))
    assert devspecs.main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "ok " in out and "FAIL" in out
    # no arguments: every committed spec, not the device-kind table
    assert devspecs.main([]) == 0
    assert devspecs.DEVICE_KINDS_FILE not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Fingerprint: memoized per spec, invalidated by a spec-file edit
# ---------------------------------------------------------------------------

def test_fingerprint_memoized_and_spec_keyed():
    v5e = devspecs.get_spec("tpu-v5e")
    host = devspecs.get_spec("cpu-host")
    assert devspecs.fingerprint(v5e) == devspecs.fingerprint(v5e)
    assert devspecs.fingerprint(v5e) != devspecs.fingerprint(host)
    devspecs.set_default_spec("cpu-host")
    assert devspecs.fingerprint() == devspecs.fingerprint(host)


def test_fingerprint_changes_on_spec_edit(tmp_path):
    src = os.path.join(devspecs.spec_dirs()[0], "tpu-v5e.json")
    path = tmp_path / "edited.json"
    shutil.copy(src, path)
    before = devspecs.fingerprint(devspecs.get_spec(str(path)))
    raw = json.loads(path.read_text())
    raw["hbm_bw"] = raw["hbm_bw"] * 2
    path.write_text(json.dumps(raw))
    # force a distinct mtime even on coarse-resolution filesystems
    stamp = os.stat(path).st_mtime_ns + 1_000_000
    os.utime(path, ns=(stamp, stamp))
    edited = devspecs.get_spec(str(path))
    assert edited.hbm_bw == raw["hbm_bw"]           # the memo reloaded it
    assert devspecs.fingerprint(edited) != before   # old plans invalidated


# ---------------------------------------------------------------------------
# Latency-bound detection in the analytic models
# ---------------------------------------------------------------------------

def test_ecm_small_grid_is_latency_bound():
    lups = 8 * 8 * 8
    p = models.ecm_predict(STENCIL, 24.0, lups)     # ~12 KiB << 409.5 KB
    assert p.hbm_bytes < devspecs.get_spec("tpu-v5e").latency_bytes
    assert p.dominant == "latency"
    assert p.t_total == p.t_latency > p.t_hbm


def test_ecm_large_grid_is_not_latency_bound():
    lups = 512 * 512 * 512
    p = models.ecm_predict(STENCIL, 24.0, lups)
    assert p.dominant != "latency"
    assert p.t_hbm > p.t_latency


def test_roofline_small_transfer_is_latency_bound():
    t = models.roofline(1e6, 1e4, 0.0)
    assert t.dominant == "latency"
    assert t.t_bound == t.t_latency
    assert 0.0 < t.roofline_fraction <= 1.0
    big = models.roofline(1e12, 1e12, 0.0)
    assert big.dominant != "latency"


def test_latency_term_scales_with_spec():
    host = devspecs.get_spec("cpu-host")
    p = models.ecm_predict(STENCIL, 24.0, 8 * 8 * 8, chip=host)
    assert p.t_latency == pytest.approx(host.hbm_latency_s)
    assert p.t_latency != models.ecm_predict(STENCIL, 24.0, 8 * 8 * 8).t_latency


# ---------------------------------------------------------------------------
# Per-spec calibration artifacts
# ---------------------------------------------------------------------------

def test_calibration_records_and_persists_spec(tmp_path):
    pts = [(1e6, 1e5, 1e-3), (2e6, 2e5, 2e-3), (4e6, 1e5, 3e-3)]
    calib = models.fit_ecm(pts, spec="cpu-host")
    assert calib.spec == "cpu-host"
    path = models.save_calibration(calib, str(tmp_path))
    assert path == models.calibration_path(str(tmp_path), "cpu-host")
    loaded = models.load_calibration(str(tmp_path), "cpu-host")
    assert loaded == calib
    assert models.load_calibration(str(tmp_path), "tpu-v5e") is None


def test_calibration_defaults_to_current_spec():
    devspecs.set_default_spec("interpret")
    calib = models.fit_ecm([(1e6, 1e5, 1e-3)])
    assert calib.spec == "interpret"


def test_save_calibration_requires_spec(tmp_path):
    calib = dataclasses.replace(models.fit_ecm([(1e6, 1e5, 1e-3)]), spec="")
    with pytest.raises(ValueError, match="spec"):
        models.save_calibration(calib, str(tmp_path))


# ---------------------------------------------------------------------------
# Portable plan translation
# ---------------------------------------------------------------------------

def _foreign_registry(tmp_path):
    """A registry holding one measured cpu-host entry, reopened under v5e."""
    path = str(tmp_path / "plans.json")
    devspecs.set_default_spec("cpu-host")
    r = reg.PlanRegistry(path)
    r.put(STENCIL, GRID, MWDPlan(d_w=4, n_f=2, fused=True), 0.5,
          source="measured", evals=9)
    devspecs.set_default_spec(None)                 # back to tpu-v5e
    return reg.PlanRegistry(path)


def test_resolve_translates_foreign_plan_without_measuring(tmp_path,
                                                           monkeypatch):
    r = _foreign_registry(tmp_path)

    def _no_tuning(*a, **k):
        raise AssertionError("translation must not fall back to autotune")

    monkeypatch.setattr(autotune, "autotune", _no_tuning)
    plan, source = r.resolve(STENCIL, GRID)
    assert source == "translated:cpu-host"
    assert plan == MWDPlan(d_w=4, n_f=2, fused=True)
    # memoized: the second resolve is a dict hit, still zero measurements
    assert r.resolve(STENCIL, GRID) == (plan, source)


def test_translation_rescales_score_by_model_ratio(tmp_path):
    r = _foreign_registry(tmp_path)
    foreign = r.foreign_entry(STENCIL, GRID)
    assert foreign is not None and foreign.spec == "cpu-host"
    out = compat.translate_entry(foreign, STENCIL, GRID,
                                 to_spec=devspecs.get_spec("tpu-v5e"))
    assert out is not None
    assert out.source == "translated:cpu-host"
    assert out.spec == "tpu-v5e"
    ratio = (autotune.model_score(STENCIL, GRID, 4,
                                  devspecs.get_spec("tpu-v5e"), 1)(foreign.plan)
             / autotune.model_score(STENCIL, GRID, 4,
                                    devspecs.get_spec("cpu-host"), 1)(foreign.plan))
    assert out.score == pytest.approx(foreign.score * ratio)
    assert math.isfinite(out.score) and out.score > 0


def test_translation_refusals(tmp_path):
    r = _foreign_registry(tmp_path)
    foreign = r.foreign_entry(STENCIL, GRID)
    v5e = devspecs.get_spec("tpu-v5e")
    # same spec: nothing to translate
    assert compat.translate_entry(
        foreign, STENCIL, GRID,
        to_spec=devspecs.get_spec("cpu-host")) is None
    # legacy entry with no recorded spec
    legacy = dataclasses.replace(foreign, spec="")
    assert compat.translate_entry(legacy, STENCIL, GRID, to_spec=v5e) is None
    # unknown source spec
    ghost = dataclasses.replace(foreign, spec="decommissioned-machine")
    assert compat.translate_entry(ghost, STENCIL, GRID, to_spec=v5e) is None
    # VMEM misfit under the target spec
    tiny = dataclasses.replace(v5e, name="tiny-vmem", vmem_bytes=64)
    assert compat.translate_entry(foreign, STENCIL, GRID, to_spec=tiny) is None


def test_foreign_entry_survives_save(tmp_path):
    r = _foreign_registry(tmp_path)
    r.put(STENCIL, (9, 9, 9), MWDPlan(d_w=2), 1.0)  # triggers a v5e save
    r2 = reg.PlanRegistry(r.path)
    foreign = r2.foreign_entry(STENCIL, GRID)
    assert foreign is not None and foreign.spec == "cpu-host"
    stats = r2.stats()
    assert stats["foreign"] == 1 and stats["spec"] == "tpu-v5e"


def test_translated_resolution_is_never_persisted(tmp_path, monkeypatch):
    r = _foreign_registry(tmp_path)
    monkeypatch.setattr(autotune, "autotune",
                        lambda *a, **k: pytest.fail("must not autotune"))
    r.resolve(STENCIL, GRID)
    r.save()
    on_disk = json.load(open(r.path))["plans"]
    entry = on_disk[reg.plan_key(STENCIL, GRID)]
    assert entry["spec"] == "cpu-host"               # still the raw foreign
    assert entry["source"] == "measured"             # record, not translated
