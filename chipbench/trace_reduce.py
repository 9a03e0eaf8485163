"""From a JAX profiler trace (.xplane.pb) to the device's numbers.

What a trace of this program on a TPU v5e holds (looked at by hand,
PR 25; chipbench/README.md shows the listing):

  plane "/device:TPU:<n>"   one a chip, on the device's clock
    line "XLA Modules"      one event a program execution
                            (`jit__verify_tile(<fingerprint>)`, ...)
    line "XLA Ops"          one event an operation inside a program,
                            named by its HLO text; ~24,000 a 128-lane
                            ed25519 tile, so a trace is cut to a few
                            requests
  plane "/host:CPU"         one line a host thread; TraceAnnotations
                            appear on the thread that opened them

All planes share one time base (nanoseconds from the trace's start).
The harness wraps each traced request in `cb_request` and its parts in
`cb_decode` and `cb_entry`; the traced window runs from the first
`cb_request`'s start to the last one's end. The runtime's own host
events (`PjitFunction(...)`, `np.asarray(jax.Array)`, ...) nest inside
those on the same line and name the idle gaps more closely.

  busy_s      union of "XLA Ops" intervals inside the window, averaged
              over the device planes that ran anything
  program_s   summed "XLA Modules" durations inside the window (all
              chips): the device time of the programs
  idle_gaps   the idle intervals inside the window of every device
              plane that ran anything, split by what the host's request
              thread was doing (the innermost event open there), summed
              a name and averaged over those planes, as busy_s is: they
              add up to window_s - busy_s
  device_ops  summed "XLA Ops" time an operation, longest first, named
              `<program>:<operation>` by the "XLA Modules" event it ran
              inside (an operation inside a loop is counted in the loop's
              event too: the list is the trace's, not a partition)
"""

from __future__ import annotations

import bisect
import gzip

REQUEST = "cb_request"
BETWEEN = "between requests (harness loop)"


def merge(intervals) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def union_length(intervals) -> float:
    return sum(hi - lo for lo, hi in merge(intervals))


def gaps(busy, lo, hi) -> list:
    """The parts of [lo, hi] that no interval of merged `busy` covers."""
    out, at = [], lo
    for b_lo, b_hi in busy:
        if b_lo > at:
            out.append((at, min(b_lo, hi)))
        at = max(at, b_hi)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def innermost_segments(events) -> list:
    """[(start, end, name)] with no overlap: at each instant the name
    of the innermost of the nested `events` [(start, end, name)]."""
    out, stack = [], []  # stack of (end, name)
    at = None

    def emit(until):
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1][1]))
        at = until

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(start)
        at = start
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def name_gaps(busy, segments, lo, hi, idle: dict) -> None:
    """Add to `idle`, a name, the parts of [lo, hi] that one device's
    merged `busy` leaves uncovered, each split by the segment of
    `segments` (sorted, no overlap) open at the time."""
    i = 0
    for g_lo, g_hi in gaps(busy, lo, hi):
        covered = 0
        while i < len(segments) and segments[i][1] <= g_lo:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < g_hi:
            s, e, name = segments[j]
            part = min(e, g_hi) - max(s, g_lo)
            if part > 0:
                idle[name] = idle.get(name, 0) + part
                covered += part
            j += 1
        if g_hi - g_lo > covered:
            idle[BETWEEN] = idle.get(BETWEEN, 0) + (g_hi - g_lo - covered)


def short_op(name: str) -> str:
    """`%while.32 = (s32[]...) while(...)` -> `%while.32`."""
    return name.split(" = ", 1)[0][:80]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_file(path: str) -> dict:
    return reduce(load(path))


def reduce(profile) -> dict:
    devices, annotations = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "op_names": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        dev["ops"].append((e.start_ns, e.start_ns + e.duration_ns))
                        dev["op_names"].append(e.name)
                elif line.name == "XLA Modules":
                    dev["modules"] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events
                    ]
            if dev["ops"]:
                devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                found = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                ]
                if any(name == REQUEST for _s, _e, name in found):
                    annotations = found  # the request thread's line
    requests = [(s, e) for s, e, name in annotations if name == REQUEST]
    out = {
        "requests": len(requests), "window_s": 0.0, "busy_s": 0.0,
        "program_s": 0.0, "programs": [], "device_ops": [], "idle_gaps": [],
        "devices": len(devices),
    }  # fmt: skip
    if not requests or not devices:
        return out
    lo, hi = min(s for s, _e in requests), max(e for _s, e in requests)
    out["window_s"] = (hi - lo) / 1e9
    busy_by_device = [merge(clip(d["ops"], lo, hi)) for d in devices]
    out["busy_s"] = sum(
        sum(b - a for a, b in busy) for busy in busy_by_device
    ) / len(devices) / 1e9
    by_program: dict = {}
    by_op: dict = {}
    for d in devices:
        for s, e, name in d["modules"]:
            if e > lo and s < hi:
                took = (min(e, hi) - max(s, lo)) / 1e9
                out["program_s"] += took
                key = name.split("(", 1)[0]
                by_program[key] = by_program.get(key, 0.0) + took
        modules = sorted(d["modules"])
        starts = [m[0] for m in modules]
        for (s, e), name in zip(d["ops"], d["op_names"]):
            if e > lo and s < hi:
                # two programs number their operations alike: name an
                # operation with the program execution it ran inside
                at = bisect.bisect_right(starts, s) - 1
                inside = at >= 0 and s < modules[at][1]
                key = (modules[at][2].split("(", 1)[0] if inside else "?", short_op(name))
                by_op[key] = by_op.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    out["programs"] = sorted(by_program.items(), key=lambda kv: -kv[1])
    out["programs"] = [[k, v] for k, v in out["programs"]]
    out["device_ops"] = [
        [f"{program}:{op}", v]
        for (program, op), v in sorted(by_op.items(), key=lambda kv: -kv[1])
    ]
    segments = innermost_segments(annotations)
    idle: dict = {}
    for busy in busy_by_device:
        name_gaps(busy, segments, lo, hi, idle)
    out["idle_gaps"] = [
        [k, v / len(devices) / 1e9] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])
    ]
    return out
