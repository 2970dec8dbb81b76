"""The comparison that decides `correct` rejects its control and the faults.

At sizes a CPU test can hold, with the program's kernels interpreted:

* the program passes each configuration's limit, and the lower-precision
  control (the reference computed in bfloat16 in the program's place) and a
  state returned unchanged both fail it;
* a whole run of each kind of cell, with the chip check skipped and the
  timed path broken underneath, reports ``correct: false`` for every fault
  the cell can have: a call that returns its state unchanged, an answer
  altered where it is produced, and (on a four-device mesh, for the
  distributed cell PERF.md keeps under Open questions) the exchange
  between chips left out. The same run unbroken reports ``correct: true``.

The chip readings the limits were set from are in PERF.md; `limits.py`
takes them on the chip at the cells' own sizes.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, compare, problem, run
from chipbench.reference import stencils

SMALL = {"7pt-var-f32": ("7pt-var.n512.t64", [12, 16, 24], 4),
         "25pt-const-f32": ("25pt-const.n512.t64", [20, 24, 24], 4)}
DIST = ("25pt-const.n1024.dd4", [16, 64, 24], 4)


def _small_cell(workload, grid, steps, **traffic):
    bench = None
    if workload == DIST[0]:
        # the four-chip cell is not in BENCHMARK.json yet (PERF.md, Open
        # questions); its traffic file and entry adapter are
        with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        bench["workloads"].append({"name": DIST[0], "config": "25pt-const-f32",
                                   "traffic": "n1024.dd4", "chips": 4,
                                   "why": "-"})
    cell = cells.load_cell(workload, bench)
    cell.traffic = dict(cell.traffic, grid=grid, steps_per_call=steps,
                        **traffic)
    return cell


@pytest.mark.parametrize("config", sorted(SMALL))
def test_program_passes_and_control_fails(config):
    workload, grid, steps = SMALL[config]
    cell = _small_cell(workload, grid, steps)
    cfg = cell.config
    limit = cfg["correct"]["limit"]
    dev = jax.devices()[:1]
    st_sh, arr_sh = cell.entry.shardings(cfg, cell.traffic, dev)
    state, arrays = problem.draw(cfg, grid, 2 ** 33 + 17, st_sh, arr_sh)
    entry = cell.entry.prepare(cfg, cell.traffic, dev, arrays)
    scalars = tuple(cfg["coefficients"]["scalars"])
    state = entry.call(state)           # a chained call, as in the window
    got = entry.call(state)
    want = stencils.advance(cfg["op"], state, arrays, scalars, steps,
                            "float32")
    control = stencils.advance(cfg["op"], state, arrays, scalars, steps,
                               "bfloat16")
    assert compare.max_rel_gap(got, want) <= limit
    assert compare.max_rel_gap(control, want) > limit
    assert compare.max_rel_gap(state, want) > limit       # unchanged


def _altered(fn):
    """`fn`, with one interior value of its newest level changed by 1."""
    def wrapped(*args, **kwargs):
        cur, prev = fn(*args, **kwargs)
        mid = tuple(n // 2 for n in cur.shape)
        return cur.at[mid].add(1.0), prev
    return wrapped


def _unchanged(spec, mesh_or_state, *args, **kwargs):
    state = mesh_or_state if isinstance(mesh_or_state, tuple) else args[0]
    return state


def _local_pad(block, depth, *, axis_z, axis_y, z_dim=-3, y_dim=-2):
    """The halo exchange left out: every shard pads itself at its edges."""
    del axis_z, axis_y
    pads = [(0, 0)] * block.ndim
    pads[z_dim] = pads[y_dim] = (depth, depth)
    return jnp.pad(block, pads, mode="edge")


def _run(monkeypatch, cell):
    monkeypatch.setattr(run, "check_devices", lambda chips: jax.devices())
    monkeypatch.setattr(cells, "load_peaks", lambda kind: {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    return run.run_cell(cell, 2 ** 33 + 3, 0.0, False)


ONE_CHIP_FAULTS = ["none", "unchanged", "altered"]


@pytest.mark.parametrize("fault", ONE_CHIP_FAULTS)
@pytest.mark.parametrize("config", sorted(SMALL))
def test_one_chip_run_reports_faults(monkeypatch, config, fault):
    from repro.kernels import ops

    cell = _small_cell(*SMALL[config])
    if fault == "unchanged":
        monkeypatch.setattr(ops, "mwd", _unchanged)
    elif fault == "altered":
        monkeypatch.setattr(ops, "mwd", _altered(ops.mwd))
    res = _run(monkeypatch, cell)
    assert res["correct"] is (fault == "none"), res["compared"]
    assert res["attempted"] >= 1 and "glups" in res["metrics"]
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("fault", ["none", "unchanged", "altered",
                                   "no_exchange"])
def test_four_chip_run_reports_faults(monkeypatch, fault):
    from repro.distributed import halo, stepper

    assert len(jax.devices()) >= 4, "conftest.py sets four host devices"
    workload, grid, steps = DIST
    cell = _small_cell(workload, grid, steps, t_block=2)
    if fault == "unchanged":
        monkeypatch.setattr(stepper, "run_distributed", _unchanged)
    elif fault == "altered":
        monkeypatch.setattr(stepper, "run_distributed",
                            _altered(stepper.run_distributed))
    elif fault == "no_exchange":
        monkeypatch.setattr(halo, "exchange_2d", _local_pad)
    res = _run(monkeypatch, cell)
    assert res["correct"] is (fault == "none"), res["compared"]
