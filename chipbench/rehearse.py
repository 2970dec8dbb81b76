#!/usr/bin/env python3
"""Compile each cell's timed call for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py [workload ...]

For every workload of BENCHMARK.json (or those named) it builds the
programs one timed call runs, through the cell's entry adapter, against a
described ``v5e:2x2`` (one of its chips for a one-chip cell), compiles them
with Mosaic, and prints the resolved plan, ``memory_analysis()`` per
device, whether a ``tpu_custom_call`` (the MWD kernel) is present, and the
compile time. What the chip's compiler refuses surfaces here at no chip
time; nothing runs, so no time or result is measured.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _gib(n: int) -> str:
    return f"{n / 2 ** 30:.3f} GiB"


def rehearse(cell, topo) -> dict:
    """Compile `cell`'s lowerables for the described chips; a result dict."""
    import jax

    devices = list(topo.devices)[:cell.chips]
    out = {"workload": cell.name, "programs": []}
    for label, fn, args in cell.entry.lowerables(cell.config, cell.traffic,
                                                 devices):
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        secs = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        rec = {"program": label,
               "compile_s": round(secs, 1),
               "tpu_custom_call": "tpu_custom_call" in text,
               "per_device": {"argument": _gib(ma.argument_size_in_bytes),
                              "output": _gib(ma.output_size_in_bytes),
                              "temp": _gib(ma.temp_size_in_bytes),
                              "alias": _gib(ma.alias_size_in_bytes),
                              "total": _gib(used)}}
        out["programs"].append(rec)
        print(json.dumps(rec), flush=True)
        del compiled
        jax.clear_caches()
    return out


def main(argv=None) -> int:
    """Rehearse the named workloads (default: all); exit 1 on a refusal."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workloads", nargs="*")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if sys.path[0] == HERE:
        sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax
    from jax.experimental import topologies

    from chipbench import cells
    from repro.kernels import config

    if jax.default_backend() != "cpu":
        print("rehearse: run with JAX_PLATFORMS=cpu (it compiles for a "
              "described chip, not an attached one)", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_compilation_cache", False)
    config.interpret = lambda: False      # Mosaic, not the CPU interpreter
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = args.workloads or [w["name"] for w in bench["workloads"]]
    failed = 0
    for name in names:
        cell = cells.load_cell(name, bench)
        print(f"== {name} ({cell.chips} chip(s), entry "
              f"{cell.traffic['entry']})", flush=True)
        try:
            rehearse(cell, topo)
        except Exception as e:   # report every cell, then fail
            failed += 1
            print(f"REFUSED {name}: {type(e).__name__}: {e}"[:2000],
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
