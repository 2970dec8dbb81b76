"""A configuration's reference step and coefficient draw are found by name.

* The cells of BENCHMARK.json draw and check exactly as they did when both
  lived in a table of `problem.py` and `reference/stencils.py`: that code is
  kept below as the oracle, and the seeded draw and a reference call are
  bitwise equal to it, for every configuration whose op and draw kind it
  holds.
* Every configuration, known to the oracle or not, draws the same problem
  from the same seed, of the shapes its file states, and its reference
  step moves the field and keeps it finite.
* A configuration whose op and draw kind the tree lacks joins with new
  files and new entries of BENCHMARK.json alone: in a copy of the tree,
  Listing 4's 25-point variable-coefficient stencil with a draw kind of its
  own resolves, gets every per-layer reader a cell of the same entry
  adapter gets, and a run of it on the CPU agrees with its reference.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, problem
from chipbench.reference import stencils

ROOT = cells.ROOT


# --- the code as it was before each op and draw kind got a file of its own


def _old_arrays(coef, key, shape, dtype):
    n = coef["arrays"]
    if n == 0:
        return None
    kind = coef["draw"]
    if kind == "diffusion":
        nb = jax.random.uniform(key, (n - 1,) + shape, dtype,
                                coef["low"], coef["high"])
        centre = 1.0 - jnp.sum(nb, axis=0, keepdims=True)
        return jnp.concatenate([centre, nb], axis=0)
    if kind == "wave_velocity":
        v = jax.random.uniform(key, (n,) + shape, dtype, coef["v_low"],
                               coef["v_high"])
        return coef["c_max"] * (v / coef["v_high"]) ** 2
    raise ValueError(f"unknown coefficient draw {kind!r}")


def _old_draw(config, shape, lo, hi):
    dtype = jnp.dtype(config["dtype"])
    key = jax.random.fold_in(jax.random.key(lo), hi)
    k_cur, k_prev, k_arr = jax.random.split(key, 3)
    scale = config["state"]["scale"]
    cur = scale * jax.random.normal(k_cur, shape, dtype)
    prev = (scale * jax.random.normal(k_prev, shape, dtype)
            if config["time_order"] == 2 else cur)
    return (cur, prev), _old_arrays(config["coefficients"], k_arr, shape,
                                    dtype)


def _core(a, r):
    return a[r:-r, r:-r, r:-r]


def _shift(a, r, axis, off):
    idx = []
    for ax in range(3):
        d = off if ax == axis else 0
        idx.append(slice(r + d, a.shape[ax] - r + d or None))
    return a[tuple(idx)]


def _old_step_7pt_var(cur, prev, arrays, scalars):
    del prev, scalars
    r = 1
    out = _core(arrays[0], r) * _core(cur, r)
    k = 1
    for ax in range(3):
        for o in (-1, 1):
            out = out + _core(arrays[k], r) * _shift(cur, r, ax, o)
            k += 1
    return cur.at[r:-r, r:-r, r:-r].set(out)


def _old_step_25pt_const(cur, prev, arrays, scalars):
    r = 4
    c = scalars
    lap = c[0] * _core(cur, r)
    for d in range(1, 5):
        acc = None
        for ax in range(3):
            for o in (-1, 1):
                v = _shift(cur, r, ax, o * d)
                acc = v if acc is None else acc + v
        lap = lap + c[d] * acc
    out = 2.0 * _core(cur, r) - _core(prev, r) + _core(arrays[0], r) * lap
    return cur.at[r:-r, r:-r, r:-r].set(out)


_OLD_STEPS = {"7pt-var": _old_step_7pt_var,
              "25pt-const": _old_step_25pt_const}
_OLD_DRAWS = ("diffusion", "wave_velocity")


@partial(jax.jit, static_argnames=("op", "scalars", "n_steps", "dtype"))
def _old_advance(op, state, arrays, scalars, n_steps, dtype):
    step = _OLD_STEPS[op]
    dt = jnp.dtype(dtype)
    cur, prev = (s.astype(dt) for s in state)
    arrays = arrays.astype(dt) if arrays is not None else None
    sc = tuple(jnp.asarray(v, dt) for v in scalars)

    def body(_, carry):
        c, p = carry
        return step(c, p, arrays, sc), c

    cur, prev = jax.lax.fori_loop(0, n_steps, body, (cur, prev))
    return cur.astype(jnp.float32), prev.astype(jnp.float32)


def _equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _names():
    return sorted(c["name"] for c in cells.load_bench()["configs"])


def _in_the_oracle(name):
    """Whether the old code held `name`'s op and coefficient draw."""
    cfg = cells.load_config(name)
    coef = cfg["coefficients"]
    return cfg["op"] in _OLD_STEPS and (coef["arrays"] == 0
                                        or coef["draw"] in _OLD_DRAWS)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", [n for n in _names() if _in_the_oracle(n)])
def test_draw_and_reference_equal_the_code_they_replaced(name, dtype):
    cfg = cells.load_config(name)
    grid, seed = (12, 16, 24), 2 ** 33 + 29
    lo, hi = problem.seed_words(seed)
    state, arrays = problem.draw(cfg, grid, seed)
    old_state, old_arrays = jax.jit(partial(_old_draw, cfg, grid))(
        jnp.uint32(lo), jnp.uint32(hi))
    assert _equal((state, arrays), (old_state, old_arrays))
    args = (cfg["op"], state, arrays, tuple(cfg["coefficients"]["scalars"]),
            3, dtype)
    got = stencils.advance(*args)
    assert _equal(got, _old_advance(*args))
    assert not _equal(got, state)       # the steps did move the field


@pytest.mark.parametrize("name", _names())
def test_every_config_draws_and_steps(name):
    """Any configuration, whether or not the oracle above knows its op."""
    cfg = cells.load_config(name)
    grid, seed = (4 * cfg["radius"] + 4, 16, 24), 2 ** 33 + 41
    state, arrays = problem.draw(cfg, grid, seed)
    assert _equal((state, arrays), problem.draw(cfg, grid, seed))
    assert not _equal(state, problem.draw(cfg, grid, seed + 1)[0])
    assert all(s.shape == grid for s in state)
    n = cfg["coefficients"]["arrays"]
    assert (arrays is None) if n == 0 else arrays.shape == (n, *grid)
    got = stencils.advance(cfg["op"], state, arrays,
                           tuple(cfg["coefficients"]["scalars"]), 4,
                           "float32")
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves((state, arrays, got)))
    assert not _equal(got, state)


# --- a new op and draw kind, as files and entries alone

NEW_OP, NEW_DRAW = "25pt-var", "axis_pairs"
NEW_CONFIG = {
    "op": NEW_OP, "dtype": "float32", "word_bytes": 4,
    "boundary": "dirichlet_frame", "radius": 4, "time_order": 1,
    "source": "Malas et al., arXiv:1510.04995, Listing 4",
    "state": {"draw": "normal", "scale": 1.0},
    "coefficients": {"arrays": 13, "draw": NEW_DRAW, "low": 0.005,
                     "high": 0.035, "scalars": []},
    "useful_flops_per_lup": 37,
    "compulsory_arrays_per_call": {"read": 14, "write": 2},
    "correct": {"number": "max_rel_gap", "limit": 1e-4}}
NEW_FILES = {
    f"chipbench/reference/ops/{NEW_OP}.py": '''
        """25pt-var (Listing 4): U = c0*V + sum over axes and d of c*(V+d + V-d)."""
        from chipbench.reference.stencils import core, shift


        def step(cur, prev, arrays, scalars):
            """arrays: [centre, z1..z4, y1..y4, x1..x4]."""
            del prev, scalars
            r = 4
            out = core(arrays[0], r) * core(cur, r)
            for ax in range(3):
                for d in range(1, 5):
                    c = core(arrays[1 + 4 * ax + d - 1], r)
                    out = out + c * (shift(cur, r, ax, d)
                                     + shift(cur, r, ax, -d))
            return cur.at[r:-r, r:-r, r:-r].set(out)
        ''',
    f"chipbench/draws/{NEW_DRAW}.py": '''
        """Pairs of neighbours share a weight U(low, high); centre = 1 - 2*sum."""
        import jax
        import jax.numpy as jnp


        def arrays(coef, key, shape, dtype):
            nb = jax.random.uniform(key, (coef["arrays"] - 1,) + shape, dtype,
                                    coef["low"], coef["high"])
            centre = 1.0 - 2.0 * jnp.sum(nb, axis=0, keepdims=True)
            return jnp.concatenate([centre, nb], axis=0)
        ''',
    "chipbench/configs/25pt-var-f32.json": json.dumps(NEW_CONFIG),
    "chipbench/traffic/n24.t4.json": json.dumps({
        "entry": "ops_mwd", "grid": [20, 24, 24], "steps_per_call": 4,
        "chips": 1, "plan": "auto", "why": "-"}),
}
NEW_CELL = "25pt-var.n24.t4"

# runs one cell of the copy on the CPU, as run.py would on the chip
_RUN = """
import json, sys
import jax
from chipbench import cells, run
run.check_devices = lambda chips: jax.devices()
cells.load_peaks = lambda kind: {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
res = run.run_cell(cells.load_cell(sys.argv[1]), 2 ** 33 + 5, 0.0, False)
print(json.dumps({"correct": res["correct"], "compared": res["compared"]}))
"""


def _hashes(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            if not f.endswith(".pyc"):
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def test_new_op_joins_with_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name in ("src", "specs"):       # the program and its device specs
        os.symlink(os.path.join(ROOT, name), root / name)
    for rel in NEW_FILES:               # the copy lacks them, whatever the tree
        if os.path.exists(root / rel):
            os.remove(root / rel)
    bench = cells.load_bench()          # less any 25pt-var-f32 it holds
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] != "25pt-var-f32"]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["config"] != "25pt-var-f32"]
    bench["configs"].append({"name": "25pt-var-f32", "source": "-",
                             "file": "chipbench/configs/25pt-var-f32.json",
                             "reduced": [], "why": "-"})
    bench["workloads"].append({"name": NEW_CELL, "config": "25pt-var-f32",
                               "traffic": "n24.t4", "chips": 1, "why": "-"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench" / "configs" / "25pt-var-f32.json").write_text(
        NEW_FILES["chipbench/configs/25pt-var-f32.json"])
    (root / "chipbench" / "traffic" / "n24.t4.json").write_text(
        NEW_FILES["chipbench/traffic/n24.t4.json"])
    with pytest.raises(cells.CellError, match="reference/ops"):
        cells.load_cell(NEW_CELL, root=str(root))
    before = _hashes(root)
    for rel, text in NEW_FILES.items():
        (root / rel).write_text(textwrap.dedent(text).lstrip())
    after = _hashes(root)
    assert {k: v for k, v in after.items() if k in before} == before
    new = cells.load_cell(NEW_CELL, root=str(root))
    assert new.config["op"] == NEW_OP
    for w in bench["workloads"]:        # the per-layer readers of the others
        cell = cells.load_cell(w["name"], root=str(root))
        if cell.traffic["entry"] == new.traffic["entry"]:
            assert set(cell.per_layer) <= set(new.per_layer), w["name"]

    proc = subprocess.run(
        [sys.executable, "-c", _RUN, NEW_CELL], cwd=root, capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join([str(root), str(root / "src")])))
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["compared"]["max_rel_gap"]["value"] < 1e-5, res
