"""The MWD call's profiler names: glue scopes, host spans, unchanged results.

`ops.mwd` names its pieces for the profiler: host spans ``repro.mwd``,
``repro.mwd.plan`` and ``repro.mwd.launch``; XLA scopes ``mwd.pad``,
``mwd.frame_sync`` and ``mwd.crop`` on the glue around the kernel; regions
``mwd.shift``, ``mwd.fetch``, ``mwd.update`` and ``mwd.emit`` inside it
(their Mosaic lowering is checked in test_tpu_compile.py). Names are
metadata: results stay those of the unnamed program.
"""

import glob
import os
import re

import jax
import pytest

from repro.core import ir, stencils as st
from repro.core.mwd import MWDPlan
from repro.kernels import ops, ref

SHAPE = {"7pt-var": (10, 20, 24), "25pt-const": (13, 21, 18)}
PLAN = {"7pt-var": MWDPlan(d_w=2, n_f=2), "25pt-const": MWDPlan(d_w=8, n_f=2)}
GLUE_SCOPES = ("mwd.pad", "mwd.frame_sync", "mwd.crop")


def _problem(name):
    spec = st.SPECS[name]
    state, coeffs = st.make_problem(spec, SHAPE[name], seed=7)
    return spec, state, coeffs


@pytest.mark.parametrize("name", list(SHAPE))
def test_glue_scopes_in_hlo_op_names(name):
    spec, state, coeffs = _problem(name)
    plan = PLAN[name]

    arrays, scalars = ir.split_coeffs(spec, coeffs)
    scalars = tuple(float(x) for x in scalars)

    def fwd(state, arrays):
        return ops.mwd(spec, state, ir.join_coeffs(spec, arrays, scalars), 3,
                       plan=plan)

    hlo = jax.jit(fwd).lower(state, arrays).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in GLUE_SCOPES:
        assert any(f"/{scope}/" in n for n in op_names), scope


def _host_events(log_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name) for e in line.events
                    if e.name.startswith("repro.mwd")]
    return sorted(out)


@pytest.mark.parametrize("name", list(SHAPE))
def test_profiled_mwd_equals_naive_and_nests_its_spans(name, tmp_path):
    spec, state, coeffs = _problem(name)
    plan = PLAN[name]
    want = ref.naive_steps(spec, state, coeffs, 3)
    jax.block_until_ready(ops.mwd(spec, state, coeffs, 3, plan=plan))
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = [jax.block_until_ready(ops.mwd(spec, state, coeffs, 3,
                                             plan=plan)) for _ in range(2)]
    finally:
        jax.profiler.stop_trace()
    for out in got:
        for w, g in zip(want, out):
            assert float(abs(w - g).max()) < 5e-4
    events = _host_events(str(tmp_path))
    entries = [e for e in events if e[2] == "repro.mwd"]
    assert len(entries) == 2
    for s, e, _ in entries:
        inner = [x[2] for x in events if s <= x[0] and x[1] <= e
                 and x[2] != "repro.mwd"]
        assert inner == ["repro.mwd.plan", "repro.mwd.launch"]
