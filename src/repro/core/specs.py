"""Declarative device specs: the machine model as a first-class input.

Every analytic model in the repo — the ECM-TPU prediction, the roofline
terms, the Fig. 19 energy split, the auto-tuner's VMEM prune, the plan
registry's hardware fingerprint — is parameterized by ONE `DeviceSpec`.
Specs are declared in JSON files committed under ``specs/`` (tpu-v5e, a
generic cpu-host, and an interpret-mode fallback) and validated against the
schema below, so bringing the modeling stack to a new machine is writing a
JSON file, not editing Python constants (the ECM methodology of Malas et
al. and the machine-model-driven analysis of Treibig et al. both treat the
machine model as a per-machine input for exactly this reason).

Resolution (`get_spec`) accepts a committed spec name ("cpu-host"), a path
to a user spec file, or None for the process default. The default is
``$REPRO_DEVICE_SPEC`` when set, else the ``--spec`` flag of the launch
CLIs (`set_default_spec`), else the spec of the attached device: on a TPU
backend the ``device_kind`` is looked up in ``specs/device_kinds.json``
(a kind missing there is an error), and on any other backend "tpu-v5e" —
the paper target every committed model column was produced under.

The derived ``latency_bytes = hbm_bw * hbm_latency_cycles / freq`` field is
the memory-latency crossover: a launch moving fewer HBM bytes than this
cannot be bandwidth-bound — its transfer time is dominated by the first
access latency, and `models.ecm_predict` / `models.roofline` report a
"latency" dominant term instead of mis-modeling it as bandwidth-bound.

`fingerprint` (the registry invalidation key) derives from the RESOLVED
spec plus the JAX runtime, memoized per (spec, process): editing a spec
file changes the fingerprint and invalidates every plan tuned under it,
while repeated registry lookups never re-enumerate `jax.devices()`.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os


class SpecError(ValueError):
    """A device spec file failed schema validation or could not be found."""


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Per-device hardware constants driving every analytic model."""

    name: str
    peak_flops_bf16: float      # matrix-unit peak, FLOP/s
    peak_flops_vpu_f32: float   # vector f32 peak (stencils are vector work)
    hbm_bw: float               # main-memory B/s, sustained
    vmem_bw: float              # fast-memory<->compute aggregate B/s
    ici_bw_per_link: float      # B/s per interconnect link
    ici_links: int              # usable links per device
    vmem_bytes: int             # software-managed fast memory per core
    hbm_bytes: int              # main-memory capacity
    freq: float                 # core clock, Hz (latency-term conversion)
    hbm_latency_cycles: int     # first-access main-memory latency, cycles
    # Energy model constants (Fig. 19 analog). The *relative* DRAM-vs-core
    # split is what the paper's argument needs.
    static_power_w: float       # package idle/static draw
    joules_per_flop: float      # incremental core energy
    joules_per_hbm_byte: float  # incremental main-memory energy

    @property
    def hbm_latency_s(self) -> float:
        """First-access memory latency in seconds (the latency-term floor)."""
        return self.hbm_latency_cycles / self.freq

    @property
    def latency_bytes(self) -> float:
        """Traffic below which a transfer is latency- not bandwidth-bound.

        Derived, never declared: ``hbm_bw * hbm_latency_cycles / freq`` —
        the bytes the memory system would stream during one access latency.
        """
        return self.hbm_bw * self.hbm_latency_cycles / self.freq

    def to_dict(self) -> dict:
        """Declared fields only (derived properties are never serialized)."""
        return dataclasses.asdict(self)


# Schema: field -> (type, must_be_positive). `name` is checked separately.
_SCHEMA: dict[str, tuple[type, bool]] = {
    "peak_flops_bf16": (float, True),
    "peak_flops_vpu_f32": (float, True),
    "hbm_bw": (float, True),
    "vmem_bw": (float, True),
    "ici_bw_per_link": (float, True),
    "ici_links": (int, True),
    "vmem_bytes": (int, True),
    "hbm_bytes": (int, True),
    "freq": (float, True),
    "hbm_latency_cycles": (int, True),
    "static_power_w": (float, False),
    "joules_per_flop": (float, False),
    "joules_per_hbm_byte": (float, False),
}

ENV_SPEC = "REPRO_DEVICE_SPEC"
ENV_SPEC_DIR = "REPRO_SPEC_DIR"
DEFAULT_SPEC_NAME = "tpu-v5e"


def validate_spec_dict(raw: dict, *, origin: str = "<dict>") -> dict:
    """Schema-check one spec dict; returns the coerced field map.

    Rejects (with a `SpecError` naming the offending field and file):
    missing fields, unknown fields, non-numeric values, non-positive values
    for rate/size fields, and a missing/empty `name`. ``latency_bytes`` is
    DERIVED and therefore rejected if declared — a spec file cannot pin a
    crossover inconsistent with its own bandwidth/latency/frequency.
    """
    if not isinstance(raw, dict):
        raise SpecError(f"{origin}: spec must be a JSON object, "
                        f"got {type(raw).__name__}")
    name = raw.get("name")
    if not isinstance(name, str) or not name:
        raise SpecError(f"{origin}: missing or empty 'name'")
    unknown = set(raw) - set(_SCHEMA) - {"name"}
    if unknown:
        hint = (" ('latency_bytes' is derived from hbm_bw, "
                "hbm_latency_cycles and freq — do not declare it)"
                if "latency_bytes" in unknown else "")
        raise SpecError(f"{origin}: unknown field(s) "
                        f"{sorted(unknown)}{hint}")
    missing = set(_SCHEMA) - set(raw)
    if missing:
        raise SpecError(f"{origin}: missing field(s) {sorted(missing)}")
    out: dict = {"name": name}
    for field, (typ, positive) in _SCHEMA.items():
        v = raw[field]
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            raise SpecError(f"{origin}: field '{field}' must be a number, "
                            f"got {v!r}")
        if positive and not v > 0:
            raise SpecError(f"{origin}: field '{field}' must be > 0, "
                            f"got {v!r}")
        if not positive and v < 0:
            raise SpecError(f"{origin}: field '{field}' must be >= 0, "
                            f"got {v!r}")
        out[field] = typ(v)
    return out


def spec_dirs() -> list[str]:
    """Candidate directories holding committed ``<name>.json`` spec files.

    ``$REPRO_SPEC_DIR`` first, then ``specs/`` under the repo root (resolved
    relative to this file: src/repro/core/specs.py -> three levels up), then
    ``specs/`` under the current directory.
    """
    dirs = []
    env = os.environ.get(ENV_SPEC_DIR)
    if env:
        dirs.append(env)
    here = os.path.dirname(os.path.abspath(__file__))
    dirs.append(os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(here))), "specs"))
    dirs.append(os.path.join(os.getcwd(), "specs"))
    return dirs


def _resolve_path(name_or_path: str) -> str:
    if os.sep in name_or_path or name_or_path.endswith(".json"):
        if os.path.exists(name_or_path):
            return name_or_path
        raise SpecError(f"device spec file not found: {name_or_path}")
    for d in spec_dirs():
        cand = os.path.join(d, f"{name_or_path}.json")
        if os.path.exists(cand):
            return cand
    raise SpecError(
        f"unknown device spec '{name_or_path}': no {name_or_path}.json in "
        f"{spec_dirs()} (set ${ENV_SPEC_DIR} or pass a file path)")


def load_spec_file(path: str) -> DeviceSpec:
    """Parse + schema-validate one spec file into a `DeviceSpec`."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read device spec {path}: {e}") from e
    except ValueError as e:
        raise SpecError(f"device spec {path} is not valid JSON: {e}") from e
    return DeviceSpec(**validate_spec_dict(raw, origin=path))


# get_spec memo: (resolved path, mtime_ns) -> DeviceSpec. The mtime key
# makes an edited spec file reload (and, via the fingerprint below,
# invalidate every plan tuned under the old constants).
_SPECS: dict[tuple[str, int], DeviceSpec] = {}
_default_override: str | None = None


DEVICE_KINDS_FILE = "device_kinds.json"   # TPU device_kind -> spec name


def spec_name_for_kind(kind: str) -> str:
    """Committed spec name of a TPU `device_kind` (``specs/device_kinds.json``)."""
    for d in spec_dirs():
        path = os.path.join(d, DEVICE_KINDS_FILE)
        if os.path.exists(path):
            with open(path) as f:
                table = json.load(f)
            if kind not in table:
                raise SpecError(f"TPU device kind {kind!r} has no spec in "
                                f"{path} (known: {sorted(table)})")
            return table[kind]
    raise SpecError(f"no {DEVICE_KINDS_FILE} in {spec_dirs()}")


def _device_spec_name() -> str:
    import jax

    if jax.default_backend() != "tpu":
        return DEFAULT_SPEC_NAME
    return spec_name_for_kind(jax.devices()[0].device_kind)


def get_spec(name_or_path: str | None = None) -> DeviceSpec:
    """Resolve a device spec by committed name, file path, or default.

    `None` resolves the process default: ``$REPRO_DEVICE_SPEC``, then the
    ``--spec`` CLI override (`set_default_spec`), then the attached
    device's spec (see the module docstring). Parsed specs are memoized
    per (path, mtime), so repeated model calls never re-read the file
    while an edit is still picked up.
    """
    if name_or_path is None:
        name_or_path = (os.environ.get(ENV_SPEC) or _default_override
                        or _device_spec_name())
    path = _resolve_path(name_or_path)
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError as e:
        raise SpecError(f"cannot stat device spec {path}: {e}") from e
    key = (os.path.abspath(path), mtime)
    if key not in _SPECS:
        _SPECS[key] = load_spec_file(path)
    return _SPECS[key]


def set_default_spec(name_or_path: str | None) -> DeviceSpec:
    """Set (or with None, clear) the process-default spec; returns it.

    The launch CLIs call this from their ``--spec`` flag before any model
    or registry code runs, so every defaulted consumer — `models`,
    `autotune`, `registry`, the sweep — resolves the same machine model.
    ``$REPRO_DEVICE_SPEC`` still wins over this override, so a test/CI
    environment can pin a spec around any CLI.
    """
    global _default_override
    if name_or_path is not None:
        get_spec(name_or_path)          # validate before committing to it
    _default_override = name_or_path
    return get_spec()


def current_spec() -> DeviceSpec:
    """The process-default `DeviceSpec` (see `get_spec(None)`)."""
    return get_spec(None)


# ---------------------------------------------------------------------------
# Hardware fingerprint (registry invalidation key), memoized per spec
# ---------------------------------------------------------------------------

_JAX_ENV: list[str] | None = None
_FINGERPRINTS: dict[DeviceSpec, str] = {}


def _jax_env() -> list[str]:
    # jax version/backend/device kind+count are process constants (jax locks
    # the device topology at first init); enumerate them exactly once
    global _JAX_ENV
    if _JAX_ENV is None:
        import jax

        devs = jax.devices()
        _JAX_ENV = [jax.__version__, jax.default_backend(),
                    devs[0].device_kind if devs else "none", str(len(devs))]
    return _JAX_ENV


def fingerprint(spec: DeviceSpec | None = None) -> str:
    """Stable hash of (resolved device spec, JAX runtime) — memoized.

    The tuned-plan registry keys cached measurements by this value: a plan
    tuned on one machine model must not silently be reused on another, so
    any change to the spec constants (an edited spec file, a different
    ``--spec``) or the JAX runtime (backend, device kind/count, version)
    yields a different fingerprint. Memoized per (spec, process): registry
    lookups never re-import jax or re-enumerate devices after the first.
    """
    spec = spec or current_spec()
    fp = _FINGERPRINTS.get(spec)
    if fp is None:
        parts = _jax_env() + [
            spec.name,
            # every model constant feeds an analytic score somewhere;
            # retune if any of them moves
            f"{spec.peak_flops_bf16:.3e}",
            f"{spec.peak_flops_vpu_f32:.3e}",
            f"{spec.hbm_bw:.3e}",
            f"{spec.vmem_bw:.3e}",
            f"{spec.ici_bw_per_link:.3e}",
            f"{spec.vmem_bytes}",
            f"{spec.freq:.3e}",
            f"{spec.hbm_latency_cycles}",
        ]
        fp = hashlib.sha1("|".join(parts).encode()).hexdigest()[:16]
        _FINGERPRINTS[spec] = fp
    return fp


# ---------------------------------------------------------------------------
# CLI: schema-validate committed spec files (the CI spec-validation step)
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    """Validate spec files: ``python -m repro.core.specs [files...]``.

    With no arguments, validates every ``*.json`` in the first existing
    spec directory. Prints one line per spec (name, bandwidth, derived
    latency_bytes) and returns nonzero on the first schema violation.
    """
    import argparse
    import glob as _glob

    ap = argparse.ArgumentParser(
        prog="python -m repro.core.specs",
        description="Schema-validate declarative device spec files")
    ap.add_argument("files", nargs="*",
                    help="spec files (default: every specs/*.json)")
    args = ap.parse_args(argv)
    files = args.files
    if not files:
        for d in spec_dirs():
            files = sorted(f for f in _glob.glob(os.path.join(d, "*.json"))
                           if os.path.basename(f) != DEVICE_KINDS_FILE)
            if files:
                break
    if not files:
        print("no spec files found")
        return 1
    status = 0
    for path in files:
        try:
            spec = load_spec_file(path)
        except SpecError as e:
            print(f"FAIL {path}: {e}")
            status = 1
            continue
        print(f"ok   {path}: {spec.name} hbm_bw={spec.hbm_bw:.3e} B/s "
              f"latency_bytes={spec.latency_bytes:.1f}")
    return status


if __name__ == "__main__":
    raise SystemExit(main())
