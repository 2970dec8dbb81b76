"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Records memory_analysis / cost_analysis / collective-schedule numbers for
each cell into ``results/dryrun.json``; `benchmarks/experiments.py` folds
them into the dry-run and roofline tables of ``docs/REPRODUCTION.md``.
The ``XLA_FLAGS`` assignment below MUST precede any other import (jax locks
the device count on first init), which is why it sits above them.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import signal        # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import compile_cache, configs  # noqa: E402
from repro.configs.base import SHAPES, shape_applicable  # noqa: E402
from repro.core import stencils as stc  # noqa: E402
from repro.launch import roofline  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.params import tree_sds  # noqa: E402
from repro.training import sharding as shd  # noqa: E402
from repro.training import steps  # noqa: E402

MESHES = {"pod": False, "multipod": True}

# The paper's own "architectures": the four corner-case stencils at
# production grid sizes, lowered through the distributed deep-halo stepper.
GIRIH_GRIDS = {
    "grid_1k": (1024, 1024, 1024),
    "grid_2k": (2048, 2048, 2048),
}
GIRIH_ARCHS = tuple(f"girih-{s}" for s in stc.SPECS)


def mesh_name(multi_pod: bool) -> str:
    """Display/record name of the pod (16x16) or multi-pod (2x16x16) mesh."""
    return "2x16x16" if multi_pod else "16x16"


def lower_lm_cell(cfg, shape_name: str, mesh, *, chunk: int = 2048,
                  n_layers: int = 0, accum: int = 1, stacked: bool = True):
    """Returns (lowered, model_flops, model_bytes, notes).

    stacked=True scans layer-period stacks: full-size compiles stay fast
    (kimi-k2 unrolled needs >30 min on this host; stacked ~1 min). HLO cost
    analysis counts scan bodies once, so roofline flop/byte/collective totals
    come from UNROLLED small-L probes + slope extrapolation (probe_lm_cell).
    """
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    sinfo = SHAPES[shape_name]
    spec_tree = lm.param_specs(cfg, stacked=stacked)
    n_total, n_active = roofline.active_params(cfg, spec_tree)
    mflops = roofline.model_flops(cfg, sinfo, n_total, n_active)
    n_dev = mesh.devices.size
    mbytes = roofline.analytic_hbm_bytes(cfg, sinfo, n_total, n_active,
                                         n_dev, accum=accum)
    inputs, in_shard_fn = steps.input_specs(cfg, shape_name, stacked=stacked)
    params_sh = shd.param_shardings(mesh, spec_tree)
    notes = f"N={n_total/1e9:.2f}B active={n_active/1e9:.2f}B accum={accum}"

    with jax.set_mesh(mesh):
        if sinfo["kind"] == "train":
            state_sds, state_sh_fn = steps.train_state_specs(cfg,
                                                             stacked=stacked)
            _, train_step = steps.make_train_step(cfg, chunk=chunk,
                                                  accum=accum, stacked=stacked)
            state_sh = state_sh_fn(mesh)
            lowered = jax.jit(
                train_step,
                in_shardings=(state_sh, in_shard_fn(mesh)["batch"]),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_sds, inputs["batch"])
        elif sinfo["kind"] == "prefill":
            fn = steps.make_prefill_step(cfg, chunk=chunk)
            lowered = jax.jit(
                fn, in_shardings=(params_sh, in_shard_fn(mesh)["batch"]),
            ).lower(tree_sds(spec_tree), inputs["batch"])
        else:  # decode
            serve = steps.make_serve_step(cfg)
            sh = in_shard_fn(mesh)
            lowered = jax.jit(
                serve,
                in_shardings=(params_sh, sh["cache"], sh["tokens"]),
                donate_argnums=(1,),
            ).lower(tree_sds(spec_tree), inputs["cache"], inputs["tokens"])
    return lowered, mflops, mbytes, notes


def probe_lm_cell(cfg, shape_name: str, mesh, *, chunk: int = 2048,
                  accum: int = 1):
    """Unrolled small-L probes -> exact per-layer HLO cost slope.

    Compiles the cell at L = period and L = 2*period with layers python-
    unrolled, takes the difference to get exact per-layer (flops, bytes,
    collective bytes), and extrapolates to the full depth:
        total = C(P) + (L - P)/P * (C(2P) - C(P)).
    """
    p = cfg.pattern_period
    # long-period stacks (jamba: 8) compile too slowly at 2P unrolled on this
    # host; fall back to a single-point probe, total ~ C(P) * L/P (embed/
    # loss overhead over-scaled by L/P-1 — small vs the 400B block costs)
    points = (p,) if p >= 8 else (p, 2 * p)
    probes = []
    for nl in points:
        lowered, _, _, _ = lower_lm_cell(cfg, shape_name, mesh, chunk=chunk,
                                         n_layers=nl, accum=accum,
                                         stacked=False)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = roofline.collective_bytes(compiled.as_text())
        probes.append({
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll,
        })
    if len(probes) == 1:
        c1 = probes[0]
        scale = cfg.n_layers / p
        return {
            "flops": c1["flops"] * scale,
            "bytes": c1["bytes"] * scale,
            "coll": {k: v * scale for k, v in c1["coll"].items()},
        }
    c1, c2 = probes
    scale = (cfg.n_layers - p) / p

    def extrap(a, b):
        return a + scale * (b - a)

    return {
        "flops": extrap(c1["flops"], c2["flops"]),
        "bytes": extrap(c1["bytes"], c2["bytes"]),
        "coll": {k: extrap(c1["coll"][k], c2["coll"][k]) for k in c1["coll"]},
    }


def lower_girih_cell(arch: str, grid_name: str, mesh, *, t_block: int = 0,
                     hoisted: bool = False, dtype=None):
    """Distributed deep-halo super-step for one stencil at production size.

    `arch` is girih-<op> where <op> is anything repro.core.ir.resolve_op
    accepts: a paper stencil, a registered custom op, or module.path:ATTR.
    The coefficient ShapeDtypeStructs/shardings are IR-derived (the canonical
    stacked-arrays + scalar-vector pair), so custom ops lower with no edits.

    `dtype` lowers the cell at a reduced stream dtype (f32 default): the
    word size feeds the ghost-zone code balance, so the modeled HBM bytes
    column reflects the halved word.
    """
    from repro.core import ir, precision
    from repro.distributed import stepper

    spec = ir.resolve_op(arch.removeprefix("girih-"))
    nz, ny, nx = GIRIH_GRIDS[grid_name]
    tb = t_block or (4 if spec.radius == 1 else 2)
    gs = stepper.GridSharding(mesh)
    dt = jnp.dtype(precision.parse_dtype(dtype))
    word = precision.word_bytes(dt)
    sds3 = jax.ShapeDtypeStruct((nz, ny, nx), dt)
    if hoisted:
        coeff_sds = stepper.extended_coeff_sds(spec, mesh, (nz, ny, nx), tb,
                                               dt)
    else:
        coeff_sds = stepper.coeff_sds(spec, (nz, ny, nx), dt)
    coeff_sh = (gs.sharding(leading=1), NamedSharding(mesh, P()))

    with jax.set_mesh(mesh):
        step = stepper.make_super_step(spec, mesh, (nz, ny, nx), tb,
                                       hoisted=hoisted)
        lowered = jax.jit(
            step.__wrapped__ if hasattr(step, "__wrapped__") else step,
            in_shardings=(gs.sharding(), gs.sharding(), coeff_sh),
            donate_argnums=(0, 1),
        ).lower(sds3, sds3, coeff_sds)
    lups = float(nz) * ny * nx * tb
    mflops = spec.flops_per_lup * lups
    # deep-halo stepper HBM traffic model: ghost-zone code balance on the
    # local block (Eq. 5 family; see repro.core.models)
    from repro.core import models as cmodels
    n_z = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n_z *= mesh.shape[a]
    n_y = mesh.shape["model"]
    bc = cmodels.ghostzone_code_balance(spec, tb, ny // n_y, nz // n_z,
                                        word_bytes=word)
    mbytes = bc * lups / mesh.devices.size
    return lowered, mflops, mbytes, \
        (f"t_block={tb} hoisted={hoisted} "
         f"dtype={precision.dtype_name(dt)} Bc_gz={bc:.2f}B/LUP")


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             chunk: int = 2048, n_layers: int = 0, accum: int = 1,
             probe: bool = True, verbose: bool = True, t_block: int = 0,
             hoisted: bool = False, variant: dict | None = None,
             tag: str = "", dtype=None):
    """Lower + compile one dry-run cell and extract its roofline record.

    LM cells additionally run the unrolled small-L cost probe (see
    `probe_lm_cell`) where the compile budget allows; girih (stencil) cells
    lower the distributed super-step. Returns a `roofline.DryrunResult`.
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = 512 if multi_pod else 256
    t0 = time.time()
    probed = None
    if arch.startswith("girih-"):
        lowered, mflops, mbytes, notes = lower_girih_cell(
            arch, shape_name, mesh, t_block=t_block, hoisted=hoisted,
            dtype=dtype)
    else:
        cfg = configs.get(arch)
        if variant:
            cfg = dataclasses.replace(cfg, **variant)
        lowered, mflops, mbytes, notes = lower_lm_cell(
            cfg, shape_name, mesh, chunk=chunk, n_layers=n_layers,
            accum=accum)
        # roofline table is single-pod only (brief): probe-slope costs are
        # extracted on the 16x16 mesh; multi-pod cells prove shardability
        if probe and not n_layers and not multi_pod \
                and cfg.pattern_period < 8:
            # period>=8 (jamba): even one unrolled-period probe exceeds this
            # host's compile budget; those cells report MODEL_FLOPS-derived
            # compute terms instead (notes say 'model-flops')
            probed = probe_lm_cell(cfg, shape_name, mesh, chunk=chunk,
                                   accum=accum)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    res = roofline.analyze(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name(multi_pod),
        n_devices=n_dev, model_flops=mflops, model_bytes=mbytes,
        lower_s=t1 - t0, compile_s=t2 - t1,
        notes=(f"[{tag}] " if tag else "") + notes)
    if probed is not None:
        # replace once-counted scan-body costs with probe-slope totals
        res = roofline.DryrunResult(
            **{**res.__dict__,
               "flops_per_device": probed["flops"],
               "bytes_per_device": probed["bytes"],
               "coll_bytes": probed["coll"],
               "terms": roofline.roofline(probed["flops"], mbytes,
                                          sum(probed["coll"].values())),
               "terms_hlo": roofline.roofline(probed["flops"],
                                              probed["bytes"],
                                              sum(probed["coll"].values())),
               "notes": res.notes + " probe-slope"})
    elif not arch.startswith("girih-") and not multi_pod:
        # no probe (period>=8): derive the compute term from MODEL_FLOPS at
        # the fleet-median useful-flops ratio (0.45), scale the once-counted
        # collectives by n_rep (layer collectives dominate)
        cfg_l = configs.get(arch)
        n_rep = cfg_l.n_layers // cfg_l.pattern_period
        est_flops = mflops / 0.45 / n_dev
        coll = {k: v * n_rep for k, v in res.coll_bytes.items()}
        res = roofline.DryrunResult(
            **{**res.__dict__,
               "flops_per_device": est_flops,
               "coll_bytes": coll,
               "terms": roofline.roofline(est_flops, mbytes,
                                          sum(coll.values())),
               "terms_hlo": roofline.roofline(est_flops,
                                              res.bytes_per_device * n_rep,
                                              sum(coll.values())),
               "notes": res.notes + " model-flops scan-scaled"})
    if verbose:
        mem = compiled.memory_analysis()
        print(f"[{arch} x {shape_name} x {mesh_name(multi_pod)}] "
              f"lower {res.lower_s:.1f}s compile {res.compile_s:.1f}s")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}"
              f"GiB temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={mem.output_size_in_bytes/2**30:.2f}GiB "
              f"alias={mem.alias_size_in_bytes/2**30:.2f}GiB")
        print(f"  cost_analysis: flops/dev={res.flops_per_device:.3e} "
              f"hlo_bytes/dev={res.bytes_per_device:.3e} "
              f"model_bytes/dev={res.model_bytes_per_device:.3e}")
        print(f"  collectives/dev: " + ", ".join(
            f"{k}={v/2**20:.1f}MiB" for k, v in res.coll_bytes.items() if v))
        print(f"  roofline: compute={res.terms.t_compute*1e3:.2f}ms "
              f"memory={res.terms.t_memory*1e3:.2f}ms "
              f"collective={res.terms.t_collective*1e3:.2f}ms "
              f"-> dominant={res.terms.dominant} "
              f"useful_flops={res.useful_flops_ratio:.2f}")
    return res


def iter_cells(arch_sel: str, shape_sel: str):
    """Yield (arch, shape, skip_reason) cells matching the CLI selectors."""
    archs = list(configs.ARCH_IDS) + list(GIRIH_ARCHS) \
        if arch_sel == "all" else [arch_sel]
    for arch in archs:
        if arch.startswith("girih-"):
            shapes = list(GIRIH_GRIDS) if shape_sel == "all" else [shape_sel]
            for s in shapes:
                if s in GIRIH_GRIDS:
                    yield arch, s, ""
        else:
            cfg = configs.get(arch)
            shapes = list(SHAPES) if shape_sel == "all" else [shape_sel]
            for s in shapes:
                if s not in SHAPES:
                    continue
                ok, why = shape_applicable(cfg, s)
                yield arch, s, ("" if ok else why)


def main():
    """CLI entry point: run the selected cells, appending to --out."""
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id, girih-<stencil> (paper, registered custom "
                         "op, or girih-module.path:ATTR), or 'all'")
    ap.add_argument("--op-module", default=None,
                    help="import this module first (it registers custom "
                         "StencilOps via repro.core.ir.register)")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod",
                                                       "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--n-layers", type=int, default=0,
                    help="override layer count (cost probes)")
    ap.add_argument("--accum", type=int, default=0,
                    help="gradient-accumulation microbatches (train cells); "
                         "0 = auto (8 for the >=7168-wide giants)")
    ap.add_argument("--cell-timeout", type=int, default=1800,
                    help="seconds per cell before recording a timeout")
    # perf-variant knobs (compared via the docs/REPRODUCTION.md roofline)
    ap.add_argument("--tag", default="", help="variant label in notes")
    ap.add_argument("--t-block", type=int, default=0, help="girih t_block")
    ap.add_argument("--hoisted", action="store_true",
                    help="girih: hoist coefficient halo exchange")
    ap.add_argument("--dtype", default=None,
                    help="girih: stream dtype of the lowered cell (f32/"
                         "bf16/fp16); the modeled bytes column scales with "
                         "the word")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="LM: sequence-parallel attention")
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--grad-dtype", default="")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--spec", default=None,
                    help="device spec name or spec-file path the roofline "
                         "terms price against (default: $REPRO_DEVICE_SPEC "
                         "or tpu-v5e)")
    args = ap.parse_args()

    if args.spec:
        from repro.core import specs as devspecs
        devspecs.set_default_spec(args.spec)
    if args.op_module:
        import importlib
        importlib.import_module(args.op_module)
    cells = list(iter_cells(args.arch, args.shape))
    if args.list:
        for arch, s, skip in cells:
            print(f"{arch:24s} {s:12s} {'SKIP: ' + skip if skip else 'run'}")
        return

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    results, failures = [], []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r.get("tag", ""))
            for r in results if "skip" not in r}
    for arch, shape_name, skip in cells:
        for m in meshes:
            key = (arch, shape_name, mesh_name(MESHES[m]), args.tag)
            if key in done:
                print(f"[cached] {key}")
                continue
            if skip:
                print(f"[skip] {arch} x {shape_name}: {skip}")
                results.append({"arch": arch, "shape": shape_name,
                                "mesh": mesh_name(MESHES[m]), "skip": skip})
                continue
            try:
                accum = args.accum
                if accum == 0 and not arch.startswith("girih-"):
                    # auto: giant models need microbatching to fit HBM
                    accum = 8 if configs.get(arch).d_model >= 7168 \
                        and shape_name == "train_4k" else 1
                if args.cell_timeout:
                    def _alarm(signum, frame):
                        raise TimeoutError(
                            f"cell exceeded {args.cell_timeout}s")
                    signal.signal(signal.SIGALRM, _alarm)
                    signal.alarm(args.cell_timeout)
                variant = {}
                if args.seq_parallel:
                    variant["seq_parallel_attn"] = True
                if args.capacity_factor:
                    variant["capacity_factor"] = args.capacity_factor
                if args.grad_dtype:
                    variant["grad_dtype"] = args.grad_dtype
                res = run_cell(arch, shape_name, MESHES[m],
                               chunk=args.chunk, n_layers=args.n_layers,
                               accum=max(accum, 1), t_block=args.t_block,
                               hoisted=args.hoisted, variant=variant,
                               tag=args.tag, dtype=args.dtype)
                signal.alarm(0)
                results.append(dict(res.to_json(), tag=args.tag))
            except Exception as e:
                signal.alarm(0)
                traceback.print_exc()
                failures.append((key, str(e)))
                results.append({"arch": arch, "shape": shape_name,
                                "mesh": mesh_name(MESHES[m]),
                                "error": str(e)[:500]})
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"\n{len(results)} cells recorded, {len(failures)} failures")
    for k, e in failures:
        print(f"  FAIL {k}: {e[:200]}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
