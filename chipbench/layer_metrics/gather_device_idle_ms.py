"""Device time a request's gathers wait on a device that runs nothing:
the idle part of the `gather_ready` annotations (the host line that
holds them, the gather watchdog's on a TPU) inside the traced window
(first `cb_request`'s start to the last one's end, as trace_reduce.py
has it), averaged over the device planes that ran anything, over the
traced requests. What is left of `gather_ready_ms` is the device
running the program the gather waits for."""

import bisect
import itertools

from chipbench import stage_time, trace_reduce

READY = "gather_ready"


def idle_ns(profile):
    """Nanoseconds, a device plane that ran anything, in which the
    device was idle inside a `gather_ready` annotation of the window;
    None without a device plane, a request or such an annotation."""
    planes, ready, requests = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [
                (e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events
            ]  # fmt: skip
            if ops:
                planes.append(ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == READY:
                        ready.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name == trace_reduce.REQUEST:
                        requests.append((e.start_ns, e.start_ns + e.duration_ns))
    if not planes or not ready or not requests:
        return None
    lo, hi = min(s for s, _e in requests), max(e for _s, e in requests)
    ready = trace_reduce.merge(trace_reduce.clip(ready, lo, hi))
    idle = 0
    for ops in planes:
        busy = trace_reduce.merge(trace_reduce.clip(ops, lo, hi))
        ends = [e for _s, e in busy]
        for a, b in ready:
            # the busy intervals from the first that ends after `a`
            after = itertools.islice(busy, bisect.bisect_right(ends, a), None)
            idle += sum(y - x for x, y in trace_reduce.gaps(after, a, b))
    return idle / len(planes)


def read(ctx):
    if not ctx.trace or not ctx.trace["requests"]:
        return None
    path = stage_time.find_trace()
    if path is None:
        return None
    idle = idle_ns(trace_reduce.load(path))
    if idle is None:
        return None
    return idle / 1e6 / ctx.trace["requests"]
