"""Reduce a profiler trace (xplane) to device timelines and host spans.

The run starts the JAX profiler around the traced window with
`capture_options`; `load` reads the ``*.xplane.pb`` it wrote. The reduction keeps, per device
(one ``/device:TPU:<n>`` plane each), the events of its ``XLA Ops`` line,
classified as

  kernel      the fused MWD kernel: name, or its ``hlo_op``/``long_name``
              stat, holds ``mwd_``
  collective  an HLO collective (all-reduce, all-gather, all-to-all,
              reduce-scatter, collective-permute, send/recv and their
              -start/-done halves)
  glue        every other device op (pad, frame sync, crop, copies, ...)

named by their HLO instruction name (`op_name`), and the host spans the
benchmark writes (``bench.call``, ``bench.wait``) and the program writes
(``repro.mwd``, ``repro.mwd.plan``, ...).
The traced window runs from the first ``bench.call`` start to the last
``bench.wait`` end. Device time is attributed by sweeping the window: each
instant an op runs counts once, for the innermost (latest-started) event
covering it, so nested events are never counted twice and busy time is the
union of the op intervals.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

KERNEL = "kernel"
COLLECTIVE = "collective"
GLUE = "glue"

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast|ragged-all-to-all|\bsend\b|\brecv\b|send-done|"
    r"recv-done)", re.IGNORECASE)
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench.", "repro.")     # the benchmark's, the program's


def op_name(event_name: str) -> str:
    """The HLO instruction name of an op event.

    TPU traces name an op event by its whole HLO text
    (``%mwd_7pt-var.1 = (f32[...]) custom-call(...), ...``); the name is
    the part before `` = ``. XLA names an instruction after its opcode or,
    for a Pallas kernel, after the kernel (``mwd_<op>``).
    """
    return event_name.split(" = ", 1)[0].lstrip("%")


def classify(name: str, stats: dict) -> str:
    """kernel, collective or glue, from an op's instruction name and stats.

    Operands are not read: a fusion that consumes a collective's result is
    glue, not a collective.
    """
    texts = [name] + [str(stats.get(k, "")) for k in ("hlo_op", "long_name",
                                                      "tf_op")]
    if any("mwd_" in t for t in texts):
        return KERNEL
    if any(_COLLECTIVE.search(t) for t in texts):
        return COLLECTIVE
    return GLUE


@dataclasses.dataclass
class Device:
    """One device's op events: (start_ns, end_ns, name, category)."""

    name: str
    events: list


@dataclasses.dataclass
class Trace:
    """A reduced trace: device timelines, host spans and the window."""

    devices: list
    spans: list          # (start_ns, end_ns, name), sorted by start
    window: tuple        # (start_ns, end_ns)

    @property
    def window_ns(self) -> float:
        """Length of the traced window."""
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read an xplane file and reduce it (see the module docstring)."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_planes(planes) -> Trace:
    """Reduce ProfileData planes to a `Trace`."""
    devices, spans = [], []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    stats = {k: v for k, v in e.stats}
                    start = float(e.start_ns)
                    name = op_name(e.name)
                    events.append((start, start + float(e.duration_ns),
                                   name, classify(name, stats)))
            events.sort()
            devices.append((int(m.group(1)), Device(plane.name, events)))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    start = float(e.start_ns)
                    spans.append((start, start + float(e.duration_ns),
                                  e.name))
    spans.sort()
    calls = [s for s in spans if s[2] == "bench.call"]
    waits = [s for s in spans if s[2] == "bench.wait"]
    if not calls or not waits:
        raise ValueError("the trace holds no bench.call / bench.wait spans")
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    window = (calls[0][0], max(w[1] for w in waits))
    return Trace(devices=[d for _, d in sorted(devices)], spans=spans,
                 window=window)


@dataclasses.dataclass
class Attribution:
    """One device's time in the window, in ns."""

    busy: float
    by_category: dict    # category -> self time
    by_op: dict          # op name -> self time
    exposed_collective: float
    gaps: list           # (start_ns, end_ns) of idle stretches


def attribute(dev: Device, window: tuple) -> Attribution:
    """Sweep `dev`'s events over `window` (see the module docstring).

    A collective instant is exposed when no non-collective op covers it.
    """
    t0, t1 = window
    evs = [(max(s, t0), min(e, t1), n, c) for s, e, n, c in dev.events
           if e > t0 and s < t1 and min(e, t1) > max(s, t0)]
    cuts = sorted({t0, t1} | {s for s, _, _, _ in evs}
                  | {e for _, e, _, _ in evs})
    by_cat, by_op, gaps = {}, {}, []
    busy = exposed = 0.0
    active = []          # events covering the current segment
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        active = [x for x in active if x[1] > a]
        while i < len(evs) and evs[i][0] <= a:
            if evs[i][1] > a:
                active.append(evs[i])
            i += 1
        seg = b - a
        if not active:
            if gaps and gaps[-1][1] == a:
                gaps[-1] = (gaps[-1][0], b)
            else:
                gaps.append((a, b))
            continue
        busy += seg
        inner = max(active, key=lambda x: x[0])
        by_cat[inner[3]] = by_cat.get(inner[3], 0.0) + seg
        by_op[inner[2]] = by_op.get(inner[2], 0.0) + seg
        if all(x[3] == COLLECTIVE for x in active):
            exposed += seg
    return Attribution(busy=busy, by_category=by_cat, by_op=by_op,
                       exposed_collective=exposed, gaps=gaps)


def host_span_at(spans: list, t: float) -> str:
    """Innermost host span covering `t` (latest start, then shortest)."""
    best = None
    for s, e, n in spans:
        if s <= t < e and (best is None or (s, -e) >= best[0]):
            best = ((s, -e), n)
    return best[1] if best else "no host span"


def breakdown(tr: Trace, atts: list, top: int = 10) -> dict:
    """Top device ops by self time and the longest idle gaps, in seconds.

    Op times are means over the devices; each gap (on any device) is named
    by the innermost host span at its midpoint, a ``repro.*`` span of the
    program where one covers it.
    """
    ops = {}
    for att in atts:
        for name, ns in att.by_op.items():
            ops[name] = ops.get(name, 0.0) + ns / len(atts)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for att in atts:
        for s, e in att.gaps:
            gaps.append((e - s, host_span_at(tr.spans, (s + e) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return {"device_ops": [[n, ns * 1e-9] for n, ns in top_ops],
            "idle_gaps": [[n, ns * 1e-9] for ns, n in gaps[:top]]}


def profile(fn) -> "ProfileData":
    """Run ``fn()`` under a profiler session of its own; the session's data.

    The data stays in memory: nothing is exported or written to disk.
    (`jax.profiler` exposes no public call that returns a session's data;
    jaxlib's session does.)
    """
    from jax._src.lib import _profiler

    sess = _profiler.ProfilerSession(capture_options())
    try:
        fn()
    finally:
        data = sess.stop_and_get_profile_data()
    return data


def capture_options():
    """Profiler options of the traced run: no Python tracer, no HLO protos."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts
