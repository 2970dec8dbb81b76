"""A recorded chip trace, reduced again, reads what its run printed.

``data/7pt-var.n512.t64.xplane.pb.xz`` is the xplane of one traced run of
the cell on a TPU v5e (``--seconds 1 --trace 1``, two calls), trimmed by
``trim_regions.py``; ``data/7pt-var.n512.t64.result.json`` holds that run's
command, the lines `chipbench.regions` printed and its result line.
"""

import functools
import json
import lzma
import os

import pytest

from chipbench import cells, regions, run as runmod, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "7pt-var.n512.t64"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with open(os.path.join(DATA, f"{CELL}.result.json")) as f:
        doc = json.load(f)
    # where run.py leaves a traced run's xplane
    out = tmp_path_factory.mktemp("trace")
    path = out / CELL / "plugins" / "profile" / "recorded" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    with lzma.open(os.path.join(DATA, f"{CELL}.xplane.pb.xz")) as f:
        path.write_bytes(f.read())
    return doc, str(out), str(path)


def _run(cell, result, path):
    tr = trace.load(path)
    # the recorded run traced its window with the regions on: its calls
    # stand for both the window and the phase session
    return runmod.Run(
        config=cell.config, traffic=cell.traffic,
        peaks=cells.load_peaks(result["device"]["kind"]), chips=cell.chips,
        setup_s=0.0,
        calls=[(0.0, 0.0, _lups_per_call(cell))] * result["attempted"],
        trace=tr, attributions=[trace.attribute(d, tr.window)
                                for d in tr.devices[:cell.chips]],
        phases=regions.load(path).kernels,
        phase_lups=_lups_per_call(cell) * result["attempted"])


def _lups_per_call(cell):
    grid = cell.traffic["grid"]
    return grid[0] * grid[1] * grid[2] * cell.traffic["steps_per_call"]


def test_reduced_again_reads_the_printed_metrics(recorded, monkeypatch,
                                                 capsys):
    doc, out, path = recorded
    result = doc["result"]
    monkeypatch.setattr(regions, "for_run",
                        functools.partial(regions.for_run, traces=out))
    monkeypatch.setattr(regions, "_REPORTED", set())
    cell = cells.load_cell(CELL)
    run = _run(cell, result, path)
    regions.report_phases(run.phases)
    read = {name: reader.read(run)
            for name, (_, reader) in cell.per_layer.items()}
    want = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(want) == set(cell.per_layer)
    assert read == pytest.approx(want, rel=1e-12)
    assert capsys.readouterr().out.splitlines() == doc["printed"]
    got = trace.breakdown(run.trace, run.attributions)
    for key in ("device_ops", "idle_gaps"):
        assert [s for _, s in got[key]] == pytest.approx(
            [s for _, s in result["breakdown"][key]], rel=1e-12)
    assert [n for n, _ in got["device_ops"]] == [
        n for n, _ in result["breakdown"]["device_ops"]]
    # the run named each gap by the bench.* span over it; the program's
    # repro.* spans, where one lies inside, now name it more closely
    for (n, _), (was, _) in zip(got["idle_gaps"],
                                result["breakdown"]["idle_gaps"]):
        assert n == was or (was.startswith("bench.")
                            and n.startswith("repro.")), (n, was)


def test_recorded_regions_are_complete(recorded):
    path = recorded[2]
    reg = regions.load(path)
    assert regions.trusted(reg.kernels)
    counts = reg.kernels[0].count
    # 24 active tiles x 583 wavefront steps (plan dw70.nf1, 512^3)
    assert [counts[r] for r in regions.PER_STEP] == [24 * 583] * 3
    assert counts["mwd.emit"] == 24 * (583 - 70)
    assert set(reg.scopes.values()) == {"mwd.pad", "mwd.frame_sync",
                                        "mwd.crop"}
