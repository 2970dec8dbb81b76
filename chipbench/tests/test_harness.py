"""The harness's counts, peaks table and discovery, without a chip.

    python -m pytest chipbench/tests -q
"""

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells
from chipbench.metrics import mwd_roofline_pct

ROOT = cells.ROOT
BENCH = os.path.join(ROOT, "BENCHMARK.json")


# the paper's Table 1 FLOPs per LUP, and the arrays a call must touch:
# every input read once, both returned levels written once (a cross-check
# of the IR's counts, which test_work_counts_match_the_ir holds every
# configuration to)
HAND = {"7pt-var-f32": (13, 8 + 2), "25pt-const-f32": (33, 3 + 2)}


@pytest.mark.parametrize("name", sorted(HAND))
def test_work_counts_match_hand_counts(name):
    cfg = cells.load_config(name)
    flops, arrays = HAND[name]
    assert cfg["useful_flops_per_lup"] == flops
    a = cfg["compulsory_arrays_per_call"]
    assert a["read"] + a["write"] == arrays


@pytest.mark.parametrize("name", sorted(
    c["name"] for c in cells.load_bench()["configs"]))
def test_work_counts_match_the_ir(name):
    """The counts `mwd_roofline_pct` divides by, for any configuration."""
    from repro.core import ir
    cfg = cells.load_config(name)
    op = ir.OPS[cfg["op"]]
    assert cfg["useful_flops_per_lup"] == op.flops_per_lup
    assert (cfg["time_order"], cfg["radius"]) == (op.time_order, op.radius)
    assert cfg["coefficients"]["arrays"] == op.n_coeff_arrays
    a = cfg["compulsory_arrays_per_call"]
    assert a["read"] == op.n_coeff_arrays + op.time_order
    assert a["write"] == 2


def test_roofline_least_time_by_hand():
    """7pt-var 512^3 x64 is bound by its 10 arrays of bytes at 819 GB/s."""
    cfg = cells.load_config("7pt-var-f32")
    traffic = {"grid": [512, 512, 512], "steps_per_call": 64}
    peaks = cells.load_peaks("TPU v5 lite")
    t = mwd_roofline_pct.least_time_s(cfg, traffic, peaks, n_calls=3,
                                      chips=1)
    assert t == pytest.approx(3 * 10 * 512 ** 3 * 4 / 819e9, rel=1e-12)
    cfg = cells.load_config("25pt-const-f32")
    t = mwd_roofline_pct.least_time_s(cfg, traffic, peaks, 1, chips=4)
    assert t == pytest.approx(5 * 512 ** 3 * 4 / 819e9 / 4, rel=1e-12)
    flops = 33 * 512 ** 3 * 64 / 197e12 / 4
    assert flops < t        # the bytes bound, not the FLOPs


def test_peaks_table_and_unknown_kind():
    p = cells.load_peaks("TPU v5 lite")
    assert (p["hbm_bytes_per_s"], p["flops_per_s"], p["hbm_bytes"]) == (
        819e9, 197e12, 16e9)
    with pytest.raises(cells.CellError, match="no peaks for device kind"):
        cells.load_peaks("TPU v9 imaginary")


def test_every_cell_resolves():
    bench = cells.load_bench()
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], bench)
        assert "setup_s" in cell.end_to_end
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.config["dtype"] == "float32"
        assert math.prod(cell.traffic["grid"]) > 0


def _copy_tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(BENCH, root / "BENCHMARK.json")
    return root


def _break(bench, root, what):
    w = bench["workloads"][0]
    if what == "workload":
        return "no-such-cell"
    if what == "config":
        w["config"] = "no-such-config"
    elif what == "traffic":
        w["traffic"] = "no-such-traffic"
    elif what == "entry":
        path = root / "chipbench" / "traffic" / f"{w['traffic']}.json"
        t = json.loads(path.read_text())
        t["entry"] = "no_such_entry"
        path.write_text(json.dumps(t))
    elif what in ("reference", "draw"):
        cfg = cells.load_config(w["config"])
        os.remove(root / "chipbench" / (
            f"reference/ops/{cfg['op']}.py" if what == "reference"
            else f"draws/{cfg['coefficients']['draw']}.py"))
    elif what == "metric":
        bench["end_to_end"].append({"name": "no_such_metric", "unit": "s",
                                    "better": "lower", "bound": 0.1,
                                    "source": "host_clock"})
    return w["name"]


@pytest.mark.parametrize("what", ["workload", "config", "traffic", "entry",
                                  "metric", "reference", "draw"])
def test_unresolved_name_fails_before_device_work(tmp_path, what):
    root = _copy_tree(tmp_path)
    bench = cells.load_bench()
    name = _break(bench, root, what)
    with pytest.raises(cells.CellError):
        cells.load_cell(name, bench, root=str(root))
    # and from the command line: exit 2 from the look-up, before the run
    # draws anything (it would log the cell first), with no result line
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    proc = _run_cli(str(root), "--workload", name, "--seed", "1",
                    "--seconds", "1", env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert proc.stderr.startswith("chipbench: ")


def _run_cli(root, *args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), *args],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, **(env or {})))


def test_off_chip_exits_without_metrics_line():
    proc = _run_cli(ROOT, "--workload", "7pt-var.n512.t4", "--seed",
                    str(2 ** 33 + 1), "--seconds", "1",
                    env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_fails_without_the_program(tmp_path):
    """A checkout of only BENCHMARK.json and chipbench/ cannot run a cell."""
    root = _copy_tree(tmp_path)
    proc = _run_cli(str(root), "--workload", "7pt-var.n512.t64", "--seed",
                    "3", "--seconds", "1", env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
