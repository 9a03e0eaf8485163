"""How unevenly the chips of the mesh were busy: for each device plane
of the profiler's trace the union of its "XLA Ops" intervals inside the
traced window (first `cb_request`'s start to the last one's end, as
trace_reduce.py has it), then 100 x (max - min) / max over the planes.
0 when every chip ran as long as the busiest; 100 when one ran nothing.
The batch axis is divided evenly, so a skew is padding that fell on one
shard, a chip that waits at the gather, or a chip left out."""

from chipbench import stage_time, trace_reduce


def busy_by_plane(profile) -> list:
    """Nanoseconds of operations inside the traced window, a device
    plane; [] without a request annotation."""
    ops, requests = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops.append([
                (e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events
            ])  # fmt: skip
        elif plane.name == "/host:CPU":
            requests += [
                (e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines for e in line.events
                if e.name == trace_reduce.REQUEST
            ]  # fmt: skip
    if not requests:
        return []
    lo, hi = min(s for s, _e in requests), max(e for _s, e in requests)
    return [trace_reduce.union_length(trace_reduce.clip(p, lo, hi)) for p in ops]


def read(ctx):
    if not ctx.trace or not ctx.trace["requests"]:
        return None
    path = stage_time.find_trace()
    if path is None:
        return None
    busy = busy_by_plane(trace_reduce.load(path))
    if not busy or not max(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
