"""Read the program's own profiler names out of a traced run's xplanes.

`chipbench.trace` reduces a traced window to device ops and host spans.
The program names more than that, and this module reads it, on the same
clock:

  regions     inside the MWD kernel, one per phase of a grid step:
              ``mwd.fetch`` (inbound slab DMAs), ``mwd.shift`` (the window
              shift), ``mwd.update`` (the in-tile updates of the time
              levels whose rows meet the diamond) and ``mwd.emit``
              (outbound slab DMAs, on the steps that emit). Mosaic turns
              each ``jax.named_scope`` of the kernel into a
              ``tpu.trace_start``/``trace_stop`` pair; each region event is
              assigned to the kernel event (``mwd_*`` on ``XLA Ops``) of
              its device that covers it.
  scopes      the ``jax.named_scope`` of each glue op (``mwd.pad``,
              ``mwd.frame_sync``, ``mwd.crop``), from the op path in the
              ``tf_op`` stat of its event metadata. `ProfileData` does not
              expose metadata stats, so `op_scopes` reads them from the
              file's protobuf wire format.
  host spans  ``repro.*`` (``repro.mwd``, ``repro.mwd.plan``,
              ``repro.mwd.launch``) beside ``bench.*``, as
              `chipbench.trace` reads them.

The regions reach the trace (each device plane's ``XLA TraceMe`` line)
only from a program compiled with `REGION_OPTION`. Such a program also
leaves an event for every block of code its kernel enters, on the plane's
``Tensor Core`` line: millions per call of a kernel that branches per time
level, which fill the profiler's trace buffers within two or three calls,
after which the device's later events are lost (whole calls, and regions
of the call it cuts). So the traced window runs the program as it is timed
and holds no regions; the regions come from a phase session of their own
(`chipbench.run`): after the window and the check, the same call compiled
with `REGION_OPTION` runs `run.PHASE_CALLS` times, each call profiled
alone. A reader gets None, never a smaller number, when the regions cannot
be trusted: a kernel event whose fetch, shift and update counts differ,
whose regions last longer than it, or whose counts differ from another
call's (dropped events). Each traced run prints, before its result line,
the regions per kernel event, the kernel time no region covers (split into
the head, the grid-step boundaries, the gaps between a step's phases and
the tail) and the idle time of the window by innermost host span.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
TRACES = os.path.join(os.path.dirname(HERE), ".chipbench_out", "trace")

REGION_LINE = "XLA TraceMe"            # of each /device:TPU:<n> plane
REGIONS = ("mwd.fetch", "mwd.shift", "mwd.update", "mwd.emit")
PER_STEP = ("mwd.fetch", "mwd.shift", "mwd.update")   # once per grid step
ENTRY_SPAN = "repro.mwd"
_SCOPE = re.compile(r"(?:^|/)(mwd\.[a-z_]+)(?=/|$)")
# compile option of the phase session's program (see the docstring)
REGION_OPTION = {"xla_enable_custom_call_region_trace": True}


@dataclasses.dataclass
class Kernel:
    """One kernel event with the regions it covers (count and ns each).

    `gaps` splits the time between its regions: ``head`` (kernel start to
    the first region), ``step`` (before each grid step's ``mwd.shift``:
    the grid-step boundary, with the skipped inactive tiles and each
    tile's first-step zeroing), ``phase`` (between the regions of one
    step) and ``tail`` (last region to kernel end).
    """

    device: int
    start: float
    end: float
    count: dict = dataclasses.field(default_factory=dict)
    ns: dict = dataclasses.field(default_factory=dict)
    gaps: dict = dataclasses.field(default_factory=dict)
    last: float | None = None         # end of the latest region

    @property
    def uncovered_ns(self) -> float:
        """Kernel time that no region covers."""
        return self.end - self.start - sum(self.ns.values())

    def add(self, start: float, end: float, region: str) -> None:
        """Count one region, which starts no earlier than the last one."""
        self.count[region] = self.count.get(region, 0) + 1
        self.ns[region] = self.ns.get(region, 0.0) + (end - start)
        kind = ("head" if self.last is None else
                "step" if region == "mwd.shift" else "phase")
        prev = self.start if self.last is None else self.last
        self.gaps[kind] = self.gaps.get(kind, 0.0) + (start - prev)
        self.last = end
        self.gaps["tail"] = self.end - end


@dataclasses.dataclass
class Regions:
    """What `reduce_planes` reads beyond `chipbench.trace`."""

    kernels: list        # Kernel per kernel event in the window
    scopes: dict         # op name -> innermost ``mwd.*`` scope (`op_scopes`)
    spans: list          # (start_ns, end_ns, name): repro.* and bench.*
    window: tuple        # (start_ns, end_ns), as chipbench.trace has it


def scope_of(stats: dict) -> str | None:
    """The innermost ``mwd.*`` scope of an op's ``tf_op`` stat, or None."""
    found = _SCOPE.findall(str(stats.get("tf_op", "")))
    return found[-1] if found else None


def reduce_planes(planes) -> Regions:
    """Reduce ProfileData planes to `Regions` (see the module docstring).

    The planes are read twice, so `planes` must be a list whose
    `ProfileData` is still alive (see `load`).
    """
    base = trace.reduce_planes(planes)
    t0, t1 = base.window
    kernels, regions = {}, {}
    for plane in planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name not in (REGION_LINE, trace.OPS_LINE):
                continue
            for e in line.events:
                start = float(e.start_ns)
                end = start + float(e.duration_ns)
                if line.name == REGION_LINE:
                    if e.name in REGIONS:
                        regions.setdefault(dev, []).append(
                            (start, end, e.name))
                    continue
                name = trace.op_name(e.name)
                if (trace.classify(name, dict(e.stats)) == trace.KERNEL
                        and start >= t0 and end <= t1):
                    kernels.setdefault(dev, []).append(
                        Kernel(dev, start, end))
    out = []
    for dev, ks in sorted(kernels.items()):
        ks.sort(key=lambda k: k.start)
        starts = [k.start for k in ks]
        for s, e, reg in sorted(regions.get(dev, [])):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= ks[i].end:
                ks[i].add(s, e, reg)
        out += ks
    return Regions(kernels=out, scopes={}, spans=base.spans,
                   window=base.window)


def trusted(kernels: list) -> bool:
    """Whether every kernel event's regions are complete (module docstring)."""
    if not kernels:
        return False
    first = kernels[0].count
    for k in kernels:
        n = k.count.get(PER_STEP[0], 0)
        if n == 0 or any(k.count.get(r, 0) != n for r in PER_STEP):
            return False
        if k.count != first or k.uncovered_ns < 0:
            return False
    return True


_CACHE = {}


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message, in wire order.

    Varints come as ints, length-delimited fields as memoryview slices
    (nested messages are not decoded until asked); fixed-width fields are
    skipped.
    """
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def op_scopes(xspace: bytes) -> dict:
    """Op name -> innermost ``mwd.*`` scope, from the device planes' metadata.

    XSpace field 1 holds the planes; XPlane 2 its name, 4 the event
    metadata map and 5 the stat metadata map (entries: 1 key, 2 value);
    XEventMetadata 2 the name and 5 its stats; XStat 1 the stat's metadata
    id and 5 a string value or 7 a reference to a stat metadata name;
    XStatMetadata 1 the id and 2 the name (tsl/profiler/protobuf/xplane.proto).
    """
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif f == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not trace.DEVICE_PLANE.match(name):
            continue
        for event in events:
            ev_name, stats = "", {}
            for f, v in _fields(event):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    value = (bytes(st[5]).decode() if 5 in st
                             else stat_names.get(st.get(7), ""))
                    stats[stat_names.get(st.get(1, 0), "")] = value
            scope = scope_of(stats)
            if scope is not None:
                out[trace.op_name(ev_name)] = scope
    return out


def load(path: str) -> Regions:
    """Read and reduce an xplane file (cached by path and mtime)."""
    from jax.profiler import ProfileData

    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        with open(path, "rb") as f:
            raw = f.read()
        data = ProfileData.from_serialized_xspace(raw)
        reg = reduce_planes(list(data.planes))   # while `data` is alive
        reg.scopes = op_scopes(raw)
        _CACHE[key] = reg
    return _CACHE[key]


def for_run(run, traces: str = TRACES) -> Regions | None:
    """The program's names in `run`'s traced window, or None.

    The run wrote its xplane under `traces`; the newest one is taken and
    used only if its window is the one `run.trace` reduced.
    """
    if run.trace is None:
        return None
    found = glob.glob(os.path.join(traces, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        return None
    reg = load(max(found, key=os.path.getmtime))
    if tuple(reg.window) != tuple(run.trace.window):
        return None
    report(run, reg)
    return reg


def region_ps_per_lup(run, names) -> float | None:
    """Device time of regions `names` in the phase session, ps per LUP.

    Summed over chips, over the LUPs of the session's calls.
    """
    if not run.phases or not trusted(run.phases) or not run.phase_lups:
        return None
    ns = sum(k.ns.get(n, 0.0) for k in run.phases for n in names)
    return ns * 1e3 / run.phase_lups


def scope_ns(run, reg: Regions, scope: str) -> float:
    """Self time of the glue ops under `scope`, summed over chips."""
    return sum(ns for a in run.attributions for op, ns in a.by_op.items()
               if reg.scopes.get(op) == scope)


def entry_spans(reg: Regions) -> list:
    """``repro.mwd`` spans that start in the window."""
    t0, t1 = reg.window
    return [s for s in reg.spans if s[2] == ENTRY_SPAN and t0 <= s[0] < t1]


def gap_names(run, reg: Regions) -> dict:
    """Idle time of the window by the innermost host span, in ns per chip.

    Each idle stretch is cut where a host span starts or ends, and each
    piece goes to the span innermost over it.
    """
    out = {}
    for att in run.attributions:
        for s, e in att.gaps:
            cuts = sorted({s, e} | {t for a, b, _ in reg.spans
                                    for t in (a, b) if s < t < e})
            for a, b in zip(cuts, cuts[1:]):
                name = trace.host_span_at(reg.spans, (a + b) / 2)
                out[name] = (out.get(name, 0.0)
                             + (b - a) / len(run.attributions))
    return out


_REPORTED = set()


def report_phases(kernels: list) -> None:
    """Print the phase session's regions per kernel event."""
    print(f"regions: {len(kernels)} kernel events; trusted "
          f"{trusted(kernels)}", flush=True)
    if not kernels:
        return

    ks = kernels

    def ms(values):      # mean over kernel events, in ms
        return f"{sum(values) / len(ks) * 1e-6:.6g}"

    print(f"regions per kernel event: counts {ks[0].count}; ms "
          + ", ".join(f"{r} {ms(k.ns.get(r, 0.0) for k in ks)}"
                      for r in REGIONS)
          + f"; kernel {ms(k.end - k.start for k in ks)}, no region "
          + f"{ms(k.uncovered_ns for k in ks)} ("
          + ", ".join(f"{g} {ms(k.gaps.get(g, 0.0) for k in ks)}"
                      for g in ("head", "step", "phase", "tail")) + ")",
          flush=True)


def report(run, reg: Regions) -> None:
    """Print the window's idle time by host span, once per trace."""
    key = tuple(reg.window)
    if key in _REPORTED:
        return
    _REPORTED.add(key)
    n = max(len(run.calls), 1)
    gaps = gap_names(run, reg)
    entries = entry_spans(reg)
    idle = sum(gaps.values()) / n * 1e-6
    disp = (sum(e - s for s, e, _ in entries) / len(entries) * 1e-6
            if entries else None)
    print(f"idle by host span (ms per call): "
          + ", ".join(f"{k} {v / n * 1e-6:.6g}" for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1]))
          + f"; idle {idle:.6g} ms per call, repro.mwd {disp} ms per call",
          flush=True)
