#!/usr/bin/env python3
"""Trim a traced run's xplane to what `chipbench.regions` reads, for a test.

    python chipbench/tests/trim_regions.py <in.xplane.pb> <out.xplane.pb.xz>

Keeps, of every ``/device:TPU:<n>`` plane, the ``XLA Ops`` line and the
kernel's region events (``mwd.fetch``, ...) of its ``XLA TraceMe`` line,
and, of the host planes, the ``bench.*`` and ``repro.*`` spans, with the
metadata those events name (of a device op's metadata stats only
``tf_op``); the result is xz-compressed. Both `chipbench.trace` and
`chipbench.regions` reduce the trimmed file as they reduce the full one.

Needs TensorFlow's ``xplane_pb2`` (to write the protobuf); the benchmark
itself reads traces with JAX alone.
"""

import lzma
import os
import sys


def trim(space):
    """Drop, in place, what the two reductions do not read from an XSpace."""
    from chipbench import regions, trace

    keep_planes = []
    for plane in space.planes:
        device = trace.DEVICE_PLANE.match(plane.name) is not None
        names = {k: m.name for k, m in plane.event_metadata.items()}
        lines = []
        for line in plane.lines:
            if device and line.name == trace.OPS_LINE:
                lines.append(line)
                continue
            if device:
                kept = [e for e in line.events
                        if line.name == regions.REGION_LINE
                        and names.get(e.metadata_id) in regions.REGIONS]
            else:
                kept = [e for e in line.events if names.get(
                    e.metadata_id, "").startswith(trace.SPAN_PREFIXES)]
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
        if device:       # of the metadata stats only the scope path is read
            for m in plane.event_metadata.values():
                kept = [st for st in m.stats if plane.stat_metadata[
                    st.metadata_id].name == "tf_op"]
                del m.stats[:]
                m.stats.extend(kept)
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
    """Trim argv[1] into argv[2] (xz)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = argv[1], argv[2]
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    trim(space)
    with lzma.open(dst, "wb", preset=9 | lzma.PRESET_EXTREME) as f:
        f.write(space.SerializeToString())
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    sys.exit(main(sys.argv))
