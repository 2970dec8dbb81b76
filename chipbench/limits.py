#!/usr/bin/env python3
"""Readings a cell's correctness limit is set from, on the chip.

    python3 chipbench/limits.py --workload <cell> --seeds 1,2,3 \
        --seconds <run_seconds>

In one process, for each seed: draw the cell's problem, warm up, run the
window exactly as `run.py` does, then compare, against the float32
reference run on the last call's own input,

  program    the window's last output (the number a run compares);
  control    the reference computed in bfloat16 in the program's place;
  unchanged  the last call's input, as if the call returned it unchanged.

The limit lies above the largest `program` reading and below the smallest
`control` and `unchanged` readings (PERF.md gives them). One JSON line per
seed, then a summary line. The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(cell, seed: int, seconds: float) -> dict:
    """The three readings of one seed (see the module docstring)."""
    from chipbench import compare, problem, run
    from chipbench.reference import stencils

    cfg, trf = cell.config, cell.traffic
    devs = run.check_devices(cell.chips)
    st_sh, arr_sh = cell.entry.shardings(cfg, trf, devs)
    state, arrays = problem.draw(cfg, trf["grid"], seed, st_sh, arr_sh)
    entry = cell.entry.prepare(cfg, trf, devs, arrays)
    state = entry.call(state)
    calls, last_in, last_out = run.window(entry, state, seconds)
    del state
    args = (cfg["op"], last_in, arrays, tuple(cfg["coefficients"]["scalars"]),
            trf["steps_per_call"])
    want = stencils.advance(*args, cfg["dtype"])
    out = {"seed": seed, "calls": len(calls),
           "program": compare.max_rel_gap(last_out, want),
           "unchanged": compare.max_rel_gap(last_in, want)}
    del last_out
    out["control"] = compare.max_rel_gap(stencils.advance(*args, "bfloat16"),
                                         want)
    return out


def main(argv=None) -> int:
    """Print the readings of every seed, then their extremes."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == HERE:
        sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax

    from chipbench import cells
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = cells.load_cell(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows),
        "lower_program_max": max(r["program"] for r in rows),
        "upper_control_min": min(r["control"] for r in rows),
        "upper_unchanged_min": min(r["unchanged"] for r in rows),
        "limit": cell.config["correct"]["limit"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
