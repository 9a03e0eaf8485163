"""Cut a profiler trace down to its first few traced requests, for the
recorded trace that the reducer's check reads.

    python3 chipbench/tests/cut_trace.py <in.xplane.pb[.gz]> <out.xplane.pb.gz> [requests]

Keeps the device planes and the host plane, and in them the events that
start inside the first `requests` (default 2) `cb_request` annotations;
drops every event's stats, which the reducer does not read. Needs the
XPlane protobuf bindings (here: tensorflow's), so it is a tool run by
hand once, not part of the benchmark.
"""

from __future__ import annotations

import gzip
import sys

REQUEST = "cb_request"


def main(src: str, dst: str, requests: int = 2) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    raw = gzip.open(src, "rb").read() if src.endswith(".gz") else open(src, "rb").read()
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    host = next(p for p in space.planes if p.name == "/host:CPU")
    request_ids = {i for i, m in host.event_metadata.items() if m.name == REQUEST}
    spans = sorted(
        (line.timestamp_ns * 1000 + e.offset_ps, e.duration_ps)
        for line in host.lines
        for e in line.events
        if e.metadata_id in request_ids
    )[:requests]
    lo = spans[0][0]
    hi = max(start + dur for start, dur in spans)
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if plane.name != "/host:CPU" and not plane.name.startswith("/device:TPU:"):
            continue
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        used = set()
        for line in plane.lines:
            events = [
                e for e in line.events
                if lo <= line.timestamp_ns * 1000 + e.offset_ps <= hi
            ]  # fmt: skip
            if not events:
                continue
            new = kept.lines.add()
            new.id, new.name, new.timestamp_ns = line.id, line.name, line.timestamp_ns
            for e in events:
                ne = new.events.add()
                ne.metadata_id, ne.offset_ps, ne.duration_ps = e.metadata_id, e.offset_ps, e.duration_ps
                used.add(e.metadata_id)
        for i in used:
            kept.event_metadata[i].id = i
            kept.event_metadata[i].name = plane.event_metadata[i].name
    with gzip.open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{len(spans)} requests, {sum(len(l.events) for p in out.planes for l in p.lines)} events")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:4])))
