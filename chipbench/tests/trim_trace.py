#!/usr/bin/env python3
"""Trim a traced run's xplane to what `chipbench.trace` reads, for a test.

    python chipbench/tests/trim_trace.py <in.xplane.pb> <out.xplane.pb>

Keeps every ``/device:TPU:<n>`` plane's ``XLA Ops`` line and, from the
host planes, only the ``bench.*`` spans, with the metadata those events
name. Everything else (runtime threads, other device lines) goes, so the
recorded trace of a whole window stays small enough to commit. The
reduction of the trimmed file equals that of the full one.

Needs TensorFlow's ``xplane_pb2`` (to write the protobuf); the benchmark
itself reads traces with JAX alone.
"""

import os
import sys


def trim(space):
    """Drop, in place, what the reduction does not read from an XSpace."""
    from chipbench import trace

    keep_planes = []
    for plane in space.planes:
        device = trace.DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            if device:
                if line.name == trace.OPS_LINE:
                    lines.append(line)
                continue
            names = {k for k, m in plane.event_metadata.items()
                     if m.name.startswith(trace.SPAN_PREFIX)}
            kept = [e for e in line.events if e.metadata_id in names]
            if kept:
                del line.events[:]
                line.events.extend(kept)
                lines.append(line)
        if not lines:
            continue
        del plane.lines[:]
        plane.lines.extend(lines)
        used = {e.metadata_id for ln in plane.lines for e in ln.events}
        for k in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[k]
        stats = [s for ln in plane.lines for e in ln.events for s in e.stats]
        stats += [s for m in plane.event_metadata.values() for s in m.stats]
        stats += list(plane.stats)
        used_stats = {s.metadata_id for s in stats}
        # string stats may hold their value as a reference to a metadata id
        used_stats |= {s.ref_value for s in stats
                       if s.WhichOneof("value") == "ref_value"}
        for k in [k for k in plane.stat_metadata if k not in used_stats]:
            del plane.stat_metadata[k]
        keep_planes.append(plane)
    del space.planes[:]
    space.planes.extend(keep_planes)
    return space


def main(argv):
    """Trim argv[1] into argv[2]."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = argv[1], argv[2]
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    trim(space)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    sys.exit(main(sys.argv))
