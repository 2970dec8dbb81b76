"""Find a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``: a configuration (its file is named in
``configs``), a traffic mix (``chipbench/traffic/<traffic>.json``), the
entry adapter the traffic names (``chipbench/entries/<entry>.py``) and the
metric readers it reports (``chipbench/metrics/<metric>.py``). Everything is
resolved before any device work, so a name that does not resolve fails
fast, with no metrics line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CellError(Exception):
    """A cell, or one of the files it names, does not resolve."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise CellError(f"cannot read {path}: {e}") from e
    except ValueError as e:
        raise CellError(f"{path} is not JSON: {e}") from e


def load_module(kind: str, name: str, root: str = ROOT) -> types.ModuleType:
    """Import ``<root>/chipbench/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(root, "chipbench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} file {os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one workload of BENCHMARK.json resolves to."""

    name: str
    chips: int
    config: dict
    traffic: dict
    entry: types.ModuleType
    end_to_end: dict      # metric name -> (entry in BENCHMARK.json, reader)
    per_layer: dict


def metrics_for(entries: list, workload: str, root: str = ROOT) -> dict:
    """The metric entries that apply to `workload`, with their readers."""
    out = {}
    for m in entries:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        out[m["name"]] = (m, load_module("metrics", m["name"], root))
    return out


def load_cell(workload: str, bench: dict | None = None,
              root: str = ROOT) -> Cell:
    """Resolve `workload` of `bench` (default: ROOT/BENCHMARK.json)."""
    if bench is None:
        bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {workload!r} names config "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "chipbench", "traffic",
                                      f"{w['traffic']}.json"))
    if traffic["chips"] != w["chips"]:
        raise CellError(f"traffic {w['traffic']!r} is for {traffic['chips']} "
                        f"chips, workload {workload!r} asks {w['chips']}")
    return Cell(name=workload, chips=w["chips"], config=config,
                traffic=traffic,
                entry=load_module("entries", traffic["entry"], root),
                end_to_end=metrics_for(bench["end_to_end"], workload, root),
                per_layer=metrics_for(bench["per_layer"], workload, root))


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    """Published peaks of `device_kind` from chipbench/peaks.json.

    A kind that is not in the table is an error, never a default.
    """
    table = _load_json(path or os.path.join(HERE, "peaks.json"))
    if device_kind not in table["kinds"]:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json (have {sorted(table['kinds'])})")
    return table["kinds"][device_kind]
