"""Device time of the named stages of the tile programs, from a JAX
profiler trace (.xplane.pb).

The program names the stages of its device programs with
`jax.named_scope` (tendermint_tpu/ops/): `decode_points` /
`ristretto_decode`, `scalar_prep`, `neg_a_table`, `dual_mult`,
`final_check`, `sha512_blocks`. A scope is part of an operation's
`op_name` in the compiled program, and the profiler hands it on with
the operation's events on a device plane's "XLA Ops" line — as a stat
of the event or of the event's metadata, under a name that differs
between profiler versions. So this reader asks for no stat by name: an
operation belongs to a stage when any string the trace holds for it
(its metadata's names, its string stats, the strings its stats refer
to) has the scope as one segment of a `/`-separated path.

On a TPU v5e with this installation (looked at by hand, PR 26) it is
the `tf_op` stat of the event's *metadata*
(`jit(_verify_tile)/dual_mult/while/body/closed_call/mul:`); the events
themselves carry only their device offsets. To reach both places the
file is read as what it is, a protobuf (tsl/profiler/protobuf/
xplane.proto; the field numbers below are that file's), by hand and
with nothing but the standard library.

A stage's time is the union of its operations' intervals inside the
traced window (first `cb_request`'s start to the last one's end, as
trace_reduce.py has it), summed over the device planes: a loop's event
covers its body's events, and a union counts that time once.

    python3 chipbench/stage_time.py [trace.xplane.pb]

prints what a trace holds — planes, lines, the stats an "XLA Ops" event
and its metadata carry — and each stage's seconds: the by-hand look
that comes before trusting the reduction.
"""

from __future__ import annotations

import functools
import gzip
import os
import re
import struct
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.trace_reduce import REQUEST, clip, union_length  # noqa: E402

TRACE_DIR = os.path.join(HERE, ".trace")  # where run.Tracer writes
STAGES = (
    "decode_points", "ristretto_decode", "scalar_prep", "neg_a_table",
    "dual_mult", "final_check", "sha512_blocks",
)  # fmt: skip
_STAGE = re.compile(r"(?:^|/)(" + "|".join(STAGES) + r")(?:/|$)")


# -- protobuf, by hand --------------------------------------------------


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, wire type, value) of one message: a varint's
    value, a fixed field's bytes, or a length-delimited field's
    (start, end) in `buf`."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif wire == 1:
            value = bytes(buf[i : i + 8])
            i += 8
        elif wire == 5:
            value = bytes(buf[i : i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0] : span[1]]).decode("utf-8", "replace")


def _stat(buf, span) -> dict:
    """XStat: metadata_id = 1, then one of double = 2, uint64 = 3,
    int64 = 4, str = 5, bytes = 6, ref = 7."""
    out: dict = {}
    for f, _w, v in _fields(buf, *span):
        if f == 1:
            out["id"] = v
        elif f == 2:
            out["value"] = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            out["value"] = v
        elif f == 5:
            out["str"] = _text(buf, v)
        elif f == 7:
            out["ref"] = v
    return out


def _map_value(buf, span):
    """The value (field 2) of a map<int64, Message> entry."""
    for f, _w, v in _fields(buf, *span):
        if f == 2:
            return v
    return None


class Plane:
    """XPlane: name = 2, lines = 3, event_metadata = 4 (a map),
    stat_metadata = 5 (a map)."""

    def __init__(self, buf, span) -> None:
        self.buf = buf
        self.name = ""
        self.lines: list = []  # (start, end) of each XLine
        self.events: dict = {}  # metadata id -> {name, display, stats}
        self.stat_names: dict = {}  # stat metadata id -> name
        for f, _w, v in _fields(buf, *span):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                self.lines.append(v)
            elif f == 4:
                self._event_metadata(_map_value(buf, v))
            elif f == 5:
                self._stat_metadata(_map_value(buf, v))

    def _event_metadata(self, span) -> None:
        """XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5."""
        if span is None:
            return
        meta = {"id": 0, "name": "", "display": "", "stats": []}
        for f, _w, v in _fields(self.buf, *span):
            if f == 1:
                meta["id"] = v
            elif f == 2:
                meta["name"] = _text(self.buf, v)
            elif f == 4:
                meta["display"] = _text(self.buf, v)
            elif f == 5:
                meta["stats"].append(_stat(self.buf, v))
        self.events[meta["id"]] = meta

    def _stat_metadata(self, span) -> None:
        """XStatMetadata: id = 1, name = 2."""
        if span is None:
            return
        sid, name = 0, ""
        for f, _w, v in _fields(self.buf, *span):
            if f == 1:
                sid = v
            elif f == 2:
                name = _text(self.buf, v)
        self.stat_names[sid] = name

    def line(self, span) -> tuple:
        """XLine: name = 2, timestamp_ns = 3, events = 4. Returns
        (name, timestamp in ps, [(start, end) of each XEvent])."""
        name, t0_ns, events = "", 0, []
        for f, _w, v in _fields(self.buf, *span):
            if f == 2:
                name = _text(self.buf, v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        return name, t0_ns * 1000, events

    def event(self, span) -> tuple:
        """XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3,
        stats = 4. Returns (metadata id, offset, duration, [stat spans])."""
        mid = offset = duration = 0
        stats = None
        for f, _w, v in _fields(self.buf, *span):
            if f == 1:
                mid = v
            elif f == 2:
                offset = v
            elif f == 3:
                duration = v
            elif f == 4:
                if stats is None:
                    stats = []
                stats.append(v)
        return mid, offset, duration, stats

    def strings(self, stats: list):
        """Every string a list of parsed stats holds or refers to."""
        for st in stats:
            if "str" in st:
                yield st["str"]
            elif "ref" in st:
                yield self.stat_names.get(st["ref"], "")

    def shown(self, stats: list) -> dict:
        """Parsed stats as {stat name: value}, for the by-hand look."""
        out = {}
        for st in stats:
            value = st.get("str", st.get("value"))
            if "ref" in st:
                value = self.stat_names.get(st["ref"], st["ref"])
            out[self.stat_names.get(st.get("id"), st.get("id"))] = value
        return out


def planes(path: str) -> list:
    """XSpace: planes = 1."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = f.read()
    else:
        with open(path, "rb") as f:
            data = f.read()
    buf = memoryview(data)
    return [Plane(buf, v) for f, _w, v in _fields(buf, 0, len(buf)) if f == 1]


# -- the reduction ------------------------------------------------------


def _stage_of(strings) -> str:
    for text in strings:
        found = _STAGE.search(text)
        if found:
            return found.group(1)
    return ""


def _window(all_planes: list):
    """(lo, hi) in ps and the number of `cb_request` events."""
    for plane in all_planes:
        if plane.name != "/host:CPU":
            continue
        request_ids = {
            mid for mid, m in plane.events.items() if m["name"] == REQUEST
        }
        for span in plane.lines:
            _name, t0, events = plane.line(span)
            found = []
            for ev in events:
                mid, offset, duration, _stats = plane.event(ev)
                if mid in request_ids:
                    found.append((t0 + offset, t0 + offset + duration))
            if found:
                return min(s for s, _e in found), max(e for _s, e in found), len(found)
    return None


@functools.lru_cache(maxsize=2)
def _reduced(path: str, _mtime: float) -> dict:
    all_planes = planes(path)
    window = _window(all_planes)
    out = {"requests": 0, "stages": {}, "ops": 0, "staged_ops": 0}
    if window is None:
        return out
    lo, hi, out["requests"] = window
    for plane in all_planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        by_meta = {
            mid: _stage_of(
                [m["name"], m["display"], *plane.strings(m["stats"])]
            )
            for mid, m in plane.events.items()
        }
        intervals: dict = {}
        for span in plane.lines:
            name, t0, events = plane.line(span)
            if name != "XLA Ops":
                continue
            for ev in events:
                mid, offset, duration, stats = plane.event(ev)
                stage = by_meta.get(mid, "")
                if not stage and stats:
                    stage = _stage_of(
                        plane.strings([_stat(plane.buf, s) for s in stats])
                    )
                out["ops"] += 1
                if stage:
                    out["staged_ops"] += 1
                    start = t0 + offset
                    intervals.setdefault(stage, []).append((start, start + duration))
        for stage, found in intervals.items():
            out["stages"][stage] = out["stages"].get(stage, 0.0) + (
                union_length(clip(found, lo, hi)) / 1e12
            )
    return out


def find_trace():
    """The newest traced run's file, found as run.Tracer.xplane() does."""
    for base, _dirs, files in os.walk(TRACE_DIR):
        for name in files:
            if name.endswith(".xplane.pb"):
                return os.path.join(base, name)
    return None


def stage_seconds(path: str) -> dict:
    """{"requests", "stages": {stage: seconds of device time inside the
    traced window}, "ops", "staged_ops"} of one trace file."""
    return _reduced(path, os.path.getmtime(path))


def ms_a_request(ctx, *stages):
    """Device milliseconds a traced request spent under these scopes;
    None without a device trace, or where no operation carries them (a
    program without the scopes, or one read back from a compile cache
    that was filled before they existed)."""
    if not ctx.trace or not ctx.trace["requests"]:
        return None
    path = find_trace()
    if path is None:
        return None
    found = stage_seconds(path)["stages"]
    seconds = sum(found.get(stage, 0.0) for stage in stages)
    if not seconds:
        return None
    return seconds * 1e3 / ctx.trace["requests"]


# -- the by-hand look ---------------------------------------------------


def look(path: str, out=sys.stdout) -> None:
    for plane in planes(path):
        print(f"plane {plane.name!r}: {len(plane.lines)} lines, "
              f"{len(plane.events)} event names", file=out)
        print(f"  stat names: {sorted(plane.stat_names.values())}", file=out)
        for span in plane.lines:
            name, _t0, events = plane.line(span)
            print(f"  line {name!r}: {len(events)} events", file=out)
            counts: dict = {}
            for n, ev in enumerate(events):
                mid, offset, duration, stats = plane.event(ev)
                meta = plane.events.get(mid, {"name": "?", "display": "", "stats": []})
                short = meta["name"].split(" = ", 1)[0][:60]
                entry = counts.setdefault(short, [0, 0])
                entry[0] += 1
                entry[1] += duration
                if n < 4 or (name == "XLA Ops" and n % 5000 == 0):
                    own = [_stat(plane.buf, s) for s in stats or []]
                    print(f"    [{n}] {meta['name'][:100]!r} display={meta['display'][:60]!r} "
                          f"+{offset}ps {duration}ps", file=out)
                    print(f"        event stats   : {plane.shown(own)}", file=out)
                    print(f"        metadata stats: {plane.shown(meta['stats'])}", file=out)
            longest = sorted(counts.items(), key=lambda kv: -kv[1][1])[:25]
            for short, (count, total) in longest:
                print(f"    {total / 1e9:12.3f} ms  x{count:<7d} {short}", file=out)
    print(f"stage seconds: {stage_seconds(path)}", file=out)


if __name__ == "__main__":
    target = sys.argv[1] if len(sys.argv) > 1 else find_trace()
    if target is None:
        sys.exit(f"no .xplane.pb under {TRACE_DIR}")
    look(target)
