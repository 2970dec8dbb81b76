#!/usr/bin/env python3
"""Run one benchmark cell on the chip(s) this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is a workload of BENCHMARK.json; its configuration, traffic mix,
entry adapter and metric readers are found by name (`chipbench.cells`).
In order, the run

  1. turns on the program's persistent compilation cache (inside the
     checkout), and fails with no metrics line unless JAX holds TPU chips,
     at least as many as the cell asks for;
  2. draws the cell's state and coefficients on the device from --seed;
  3. resolves the plan and warms up the cell's one call shape (set-up ends
     when the first timed call starts);
  4. runs chained calls, each advancing the previous call's output, until
     --seconds have passed (under the profiler with --trace 1);
  5. reads the peak device memory, then checks the window's last call
     against the plain float32 reference (`chipbench.reference`) run on
     that call's own input;
  6. prints the cell's end-to-end metrics (--trace 0) or per-layer metrics
     (--trace 1) as the last stdout line, one JSON object, and the numbers
     compared, each beside its limit, as the last stderr lines.

Exit codes: 0 with a result line (read `correct` in it), 1 without a TPU
or with too few chips, 2 when the cell or the program cannot be found.
"""

import time

_T_START = time.perf_counter()    # set-up is timed from process start

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".chipbench_out")     # traces; gitignored


class NoChip(Exception):
    """JAX holds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What the metric readers read (see chipbench/metrics/)."""

    config: dict
    traffic: dict
    peaks: dict
    chips: int
    setup_s: float
    calls: list                  # (start_s, end_s, lups) per timed call
    peak_bytes: int | None = None
    trace: object = None         # chipbench.trace.Trace of the window
    attributions: list | None = None

    @property
    def window_ns(self) -> float:
        """Length of the traced window (0 without a trace)."""
        return self.trace.window_ns if self.trace is not None else 0.0


class CompileCounter:
    """Counts traces, backend compiles and persistent-cache hits/misses."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}
    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                 "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = dict.fromkeys(
            list(self.EVENTS.values()) + list(self.DURATIONS.values()), 0)

    def install(self):
        """Register the listeners with jax.monitoring."""
        from jax import monitoring

        def on_event(event, **_):
            if event in self.EVENTS:
                self.counts[self.EVENTS[event]] += 1

        def on_duration(event, _secs, **_):
            if event in self.DURATIONS:
                self.counts[self.DURATIONS[event]] += 1

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    def take(self) -> dict:
        """The counts since the last take, then reset."""
        out = dict(self.counts)
        for k in self.counts:
            self.counts[k] = 0
        return out


def log(msg: str) -> None:
    """An informational line (stdout, before the result line)."""
    print(msg, flush=True)


def check_devices(chips: int):
    """The first `chips` TPU devices, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}; this "
                     "benchmark measures the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX holds {len(devs)}")
    return devs


def fullest_memory(devices) -> dict:
    """`memory_stats()` of the device with the highest peak ({} if none)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(stats, key=lambda m: m.get("peak_bytes_in_use", -1))


def window(entry, state, seconds: float):
    """Chained calls until `seconds` have passed since the first started.

    Returns (calls, last_input, last_output). Only the current call's input
    and output are alive at any time: the last input is kept for the check
    without holding a third state.
    """
    calls = []
    while True:
        last_in = state
        t0 = time.perf_counter()
        state = entry.call(state)
        t1 = time.perf_counter()
        calls.append((t0, t1, entry.lups_per_call))
        if t1 - calls[0][0] >= seconds:
            return calls, last_in, state


def traced_window(entry, state, seconds: float, workload: str):
    """`window` under the profiler; returns it with the reduced trace."""
    import jax

    from chipbench import trace

    log_dir = os.path.join(OUT, "trace", workload)
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir)
    jax.profiler.start_trace(log_dir, profiler_options=trace.capture_options())
    try:
        res = window(entry, state, seconds)
    finally:
        jax.profiler.stop_trace()
    path = trace.find_xplane(log_dir)
    log(f"trace: {os.path.relpath(path, ROOT)} "
        f"({os.path.getsize(path)} bytes)")
    return res, trace.load(path)


def check(cell, arrays, last_in, last_out) -> dict:
    """The compared numbers of the window's last call, with their limits."""
    from chipbench import compare
    from chipbench.reference import stencils

    cfg = cell.config
    want = stencils.advance(cfg["op"], last_in, arrays,
                            tuple(cfg["coefficients"]["scalars"]),
                            cell.traffic["steps_per_call"], cfg["dtype"])
    gap = compare.max_rel_gap(last_out, want)
    return {cfg["correct"]["number"]: {"value": gap,
                                       "limit": cfg["correct"]["limit"]}}


def run_cell(cell, seed: int, seconds: float, traced: bool,
             t_start: float = _T_START) -> dict:
    """Run `cell` (see the module docstring); the result line as a dict."""
    import jax

    from chipbench import cells, problem
    from chipbench import trace as tracemod
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    counter.install()
    devs = check_devices(cell.chips)
    used = devs[:cell.chips]
    kind = devs[0].device_kind
    peaks = cells.load_peaks(kind)
    cfg, trf = cell.config, cell.traffic
    st_sh, arr_sh = cell.entry.shardings(cfg, trf, devs)
    state, arrays = problem.draw(cfg, trf["grid"], seed, st_sh, arr_sh)
    entry = cell.entry.prepare(cfg, trf, devs, arrays)
    log(f"cell {cell.name}: {cfg['op']} {cfg['dtype']} grid "
        f"{tuple(trf['grid'])}, {trf['steps_per_call']} steps per call, "
        f"{cell.chips} chip(s) of kind {kind!r}, seed {seed}")
    log(f"plan: {entry.plan_text}")
    log(entry.program_counts())
    state = entry.call(state)                     # warm-up: compiles/loads
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; compile events in set-up: {counter.take()}")

    tr = None
    if traced:
        (calls, last_in, last_out), tr = traced_window(entry, state, seconds,
                                                       cell.name)
    else:
        calls, last_in, last_out = window(entry, state, seconds)
    del state
    in_window = counter.take()
    log(f"window: {len(calls)} calls in "
        f"{calls[-1][1] - calls[0][0]:.4f} s; compile events in the window: "
        f"{in_window}")
    mem = fullest_memory(used)
    log(f"memory_stats of the fullest device: {mem}")
    run = Run(config=cfg, traffic=trf, peaks=peaks, chips=cell.chips,
              setup_s=setup_s, calls=calls,
              peak_bytes=mem.get("peak_bytes_in_use"))
    if tr is not None:
        run.trace = tr
        run.attributions = [tracemod.attribute(d, tr.window)
                            for d in tr.devices[:cell.chips]]

    compared = check(cell, arrays, last_in, last_out)
    correct = all(v["value"] <= v["limit"] for v in compared.values())

    readers = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for name, (entry_def, reader) in readers.items():
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry_def["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": run.peak_bytes}
    result = {"correct": correct, "attempted": len(calls), "failed": 0,
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = (sum(a.busy for a in run.attributions)
                            / len(run.attributions) * 1e-9)
        device["window_s"] = tr.window_ns * 1e-9
        result["breakdown"] = tracemod.breakdown(tr, run.attributions)
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    """Command line entry; see the module docstring."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == HERE:
        sys.path[0] = ROOT      # import chipbench as a package, not its files
    sys.path.insert(1, os.path.join(ROOT, "src"))

    from chipbench import cells

    try:
        cell = cells.load_cell(args.workload)
    except cells.CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program (src/repro) is not in this checkout: "
              f"{e}", file=sys.stderr)
        return 2
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for name, v in result["compared"].items():
        print(f"compared {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
