"""Cut a recorded profiler trace down to what the reductions read, so a
recording can be checked in as test data: the device planes' "XLA Ops"
and "XLA Modules" lines, with their events' names and no other statistic,
and on the host only the ``bench.*`` and ``serve.*`` spans with their
arguments.  Every other plane, line, event and statistic (the programs'
HLO, the runtime's threads, cost estimates) is dropped; the times and
names of what is kept are the recording's own.

    python3 perfbench/tools/trim_trace.py <in.xplane.pb> <out.xplane.pb>

Needs TensorFlow's ``xplane_pb2`` (installed beside JAX here); run it
after the recording, not on the chip.
"""

from __future__ import annotations

import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINES = ("XLA Ops", "XLA Modules")
SPANS = ("bench.", "serve.")


def _keep(plane, line_ok, event_ok, keep_stats):
    """Copy ``plane`` with only the lines and events asked for, and the
    metadata those events use."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    out = xplane_pb2.XPlane(id=plane.id, name=plane.name)
    used_events, used_stats = set(), set()
    for line in plane.lines:
        if not line_ok(line):
            continue
        new = out.lines.add(id=line.id, display_id=line.display_id,
                            name=line.name, display_name=line.display_name,
                            timestamp_ns=line.timestamp_ns,
                            duration_ps=line.duration_ps)
        for e in line.events:
            md = plane.event_metadata[e.metadata_id]
            if not event_ok(md.name):
                continue
            kept = new.events.add(metadata_id=e.metadata_id,
                                  offset_ps=e.offset_ps,
                                  duration_ps=e.duration_ps)
            if keep_stats:
                kept.stats.extend(e.stats)
                used_stats.update(s.metadata_id for s in e.stats)
            used_events.add(e.metadata_id)
    for i in used_events:
        md = plane.event_metadata[i]
        out.event_metadata[i].CopyFrom(xplane_pb2.XEventMetadata(
            id=md.id, name=md.name, display_name=md.display_name))
    for i in used_stats:
        out.stat_metadata[i].CopyFrom(plane.stat_metadata[i])
    return out


def trim(src: str, dst: str) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if DEVICE_PLANE.match(plane.name):
            out.planes.append(_keep(plane, lambda l: l.name in DEVICE_LINES,
                                    lambda n: True, keep_stats=False))
        elif plane.name.startswith("/host"):
            out.planes.append(_keep(plane, lambda l: True,
                                    lambda n: n.startswith(SPANS),
                                    keep_stats=True))
    data = out.SerializeToString()
    with open(dst, "wb") as f:
        f.write(data)
    return len(data)


if __name__ == "__main__":
    print(trim(sys.argv[1], sys.argv[2]), "bytes")
