"""Ahead-of-time Mosaic compiles of the MWD kernel for a described TPU v5e.

Nothing runs: each test lowers the public entry point against a described
(not attached) v5e chip and compiles it, which raises whatever the chip's
compiler would refuse (unaligned DMA windows, scatter in a kernel, VMEM
over the limit, a program too large for HBM). The topology is described
inside fixtures, so only the worker that runs this file loads the TPU
compiler.
"""

import base64
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ir, models, specs as devspecs
from repro.core import stencils as st
from repro.core.mwd import MWDPlan
from repro.kernels import config, ops

PLAN = MWDPlan(d_w=8, n_f=2)
HBM_BYTES = 16 * 1024 ** 3
# chip-filling grids: 7pt-const 512^3 and 25pt-var 384^3 are the on-chip
# smoke sizes; the other two paper ops fill the chip at 512^3
FORWARD = {"7pt-const": (512, 512, 512), "7pt-var": (512, 512, 512),
           "25pt-const": (512, 512, 512), "25pt-var": (384, 384, 384)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mosaic():
    """Compile kernels with Mosaic instead of the CPU interpreter."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(config, "interpret", lambda: False)
        jax.clear_caches()          # no interpret-mode trace may be reused
        yield
    jax.clear_caches()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _operands(spec, shape, sharding, lead=()):
    state = (_sds(lead + shape, sharding), _sds(lead + shape, sharding))
    arrays = (_sds(lead + (spec.n_coeff_arrays,) + shape, sharding)
              if spec.n_coeff_arrays else None)
    scalars = tuple(spec.default_scalars or
                    (0.1 / (j + 1) for j in range(spec.n_scalars)))
    return state, arrays, scalars


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES


@pytest.mark.parametrize("name", list(FORWARD))
def test_mwd_forward_compiles(name, one_chip, mosaic):
    spec = st.SPECS[name]
    state, arrays, scalars = _operands(spec, FORWARD[name], one_chip)

    def fwd(state, arrays):
        return ops.mwd(spec, state, ir.join_coeffs(spec, arrays, scalars),
                       16, plan=PLAN)

    _check(jax.jit(fwd).lower(state, arrays).compile())


def test_mwd_batched_compiles(one_chip, mosaic):
    spec = st.SPECS["7pt-var"]
    state, arrays, scalars = _operands(spec, (128, 128, 128), one_chip,
                                       lead=(4,))

    def fwd(state, arrays):
        return ops.mwd_batched(spec, state, list(arrays), 8, plan=PLAN)

    _check(jax.jit(fwd).lower(state, arrays).compile())


@pytest.mark.parametrize("name", ["7pt-var", "25pt-const"])
def test_mwd_diff_grad_compiles(name, one_chip, mosaic):
    spec = st.SPECS[name]
    state, arrays, scalars = _operands(spec, (128, 128, 128), one_chip)

    def loss(arrays, state):
        out = ops.mwd_diff(spec, state, ir.join_coeffs(spec, arrays, scalars),
                           3, plan=PLAN)
        return jnp.sum(out[0] ** 2)

    _check(jax.jit(jax.grad(loss)).lower(arrays, state).compile())


def test_vmem_prune_refuses_what_mosaic_refuses(one_chip, mosaic):
    """A plan over the chip's VMEM is pruned, and Mosaic refuses it too."""
    spec = st.SPECS["25pt-var"]
    shape = (16, 64, 512)
    big = MWDPlan(d_w=64, n_f=2)
    v5e = devspecs.get_spec("tpu-v5e")
    assert models.vmem_fits(spec, PLAN.d_w, PLAN.n_f, shape[2], v5e)
    assert not models.vmem_fits(spec, big.d_w, big.n_f, shape[2], v5e)
    state, arrays, _ = _operands(spec, shape, one_chip)

    def fwd(state, arrays):
        return ops.mwd(spec, state, arrays, 2, plan=big)

    with pytest.raises(Exception):
        jax.jit(fwd).lower(state, arrays).compile()


REGIONS = ("mwd.shift", "mwd.fetch", "mwd.update", "mwd.emit")


def _mosaic_texts(lowered):
    """The Mosaic modules of a lowered program's ``tpu_custom_call``s."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir as mlir_ir
    from jax.experimental.mosaic.dialects import tpu

    ctx = mlir.JaxIrContext()
    ctx.append_dialect_registry(mlir.upstream_dialects)
    ctx.load_all_available_dialects()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True   # the serialized stable_mosaic
    texts = []
    for cfg in re.findall(r'backend_config = "(\{.*?\})"', lowered.as_text()):
        body = json.loads(cfg.replace("\\22", '"'))["custom_call_config"]["body"]
        with ctx:
            texts.append(str(mlir_ir.Module.parse(base64.b64decode(body))))
    return texts


@pytest.mark.parametrize("name", ["7pt-var", "25pt-const"])
def test_mwd_kernel_has_balanced_phase_regions(name, one_chip, mosaic):
    """Each phase of a grid step is one profiler region of the kernel."""
    spec = st.SPECS[name]
    state, arrays, scalars = _operands(spec, FORWARD[name], one_chip)

    def fwd(state, arrays):
        return ops.mwd(spec, state, ir.join_coeffs(spec, arrays, scalars),
                       16, plan=PLAN)

    texts = _mosaic_texts(jax.jit(fwd).lower(state, arrays))
    assert len(texts) == 1
    ops_ = re.findall(r'tpu\.trace_(start|stop)"\(\)(?: \{[^}]*message = '
                      r'"([^"]*)"[^}]*\})?', texts[0])
    starts = [m for kind, m in ops_ if kind == "start"]
    assert sorted(starts) == sorted(REGIONS)
    depth = 0
    for kind, _ in ops_:
        depth += 1 if kind == "start" else -1
        assert depth in (0, 1)        # regions neither nest nor overlap
    assert depth == 0


@pytest.mark.parametrize("n_f", [1, 2])
@pytest.mark.parametrize("name", ["7pt-var", "25pt-const"])
def test_mwd_shift_region_moves_no_vectors(name, n_f, one_chip, mosaic):
    """The window advance is ring arithmetic: `mwd.shift` loads and stores
    nothing, so no grid step copies a VMEM window."""
    spec = st.SPECS[name]
    state, arrays, scalars = _operands(spec, FORWARD[name], one_chip)
    plan = MWDPlan(d_w=PLAN.d_w, n_f=n_f)

    def fwd(state, arrays):
        return ops.mwd(spec, state, ir.join_coeffs(spec, arrays, scalars),
                       16, plan=plan)

    (text,) = _mosaic_texts(jax.jit(fwd).lower(state, arrays))
    start = text.index('message = "mwd.shift"')
    body = text[start:text.index("tpu.trace_stop", start)]
    assert not re.search(r"\b(vector|tpu)\.\w*(load|store)", body)
    assert "arith.remsi" in body          # the region holds the ring's base
