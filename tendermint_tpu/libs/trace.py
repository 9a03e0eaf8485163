"""Span tracing: nestable context managers over a bounded in-memory ring.

Zero-dependency sibling of libs/metrics.py. Where metrics answer "how
often / how long on average", spans answer "what happened inside THIS
call": each `span(name, **attrs)` records one timed interval with its
parent (nesting follows the asyncio task / thread via contextvars), so
a single commit verification decomposes into
addVote -> batch_accumulate -> its phases (docs/metrics.md draws the
tree) -> tpu_dispatch -> tpu_gather, with per-stage attributes (batch
size, pad waste, host prep, and the verified-signature cache's
sigcache_hits / sigcache_misses on batch_accumulate — the count of
triples that skipped crypto entirely vs. those actually assembled into
the batch). The motivation: a run that dies midway must leave every
surviving number attributable to a stage.

Completed spans land in a bounded ring (old spans are evicted, never
blocked on) and export as Chrome-trace JSON (chrome://tracing /
Perfetto "traceEvents" format). Spans can additionally feed an existing
metrics Histogram (`span(..., hist=h)`), replacing `h.time()` at the
call site; the histogram is observed whether or not tracing is enabled.

Spans of one tree share a `root_id` (the id of the tree's outermost
span), so one commit verification is one group without a parent walk.
While tracing is on, every collection of Python's garbage collector
that lands inside an open span is itself a `gc_collect` child span, so
a phase's self time leaves the collector's pauses out.

A span can follow a span of another thread (`span(..., follows=s)`):
the gather's job on its watchdog thread (crypto/tpu_verifier.py)
follows the caller's `tpu_gather`. It joins the followed span's tree
(`root_id`) and records `follows` (that span's id) among its attrs, but
is no child of it (`parent_id` 0): the followed span's self time keeps
the wait whole. On its own thread it is the current span as any other,
so its children nest under it.

A mirror (`set_mirror`) puts every span on a second timeline as well:
crypto/tpu_verifier.install() registers jax.profiler.TraceAnnotation,
so a profiler capture of a traced process shows the program's phases
on the host thread's line, in the capture's own time base, beside the
device's events. This module itself imports nothing outside the
standard library.

Tracing is OFF by default. The disabled path is consensus-grade cheap:
`span()` returns a shared no-op singleton — no Span object, no ring
write, no contextvar touch, no mirror call, no gc hook.
"""

from __future__ import annotations

import gc
import itertools
import json
import threading
import time
from collections import deque
from contextvars import ContextVar
from typing import Any, Dict, List, Optional

__all__ = [
    "DEFAULT_CAPACITY",
    "DEFAULT_EXEMPLAR_CAPACITY",
    "NOOP_SPAN",
    "Span",
    "add_attrs",
    "current",
    "disable",
    "disable_exemplars",
    "enable",
    "enable_exemplars",
    "exemplar_snapshot",
    "exemplars_enabled",
    "exemplars_to_json",
    "is_enabled",
    "record_slow_request",
    "reset",
    "reset_exemplars",
    "set_capacity",
    "set_exemplar_capacity",
    "set_mirror",
    "snapshot",
    "span",
    "to_chrome_trace",
]

DEFAULT_CAPACITY = 8192
DEFAULT_EXEMPLAR_CAPACITY = 64

_enabled = False
# deque.append is atomic in CPython — writers never take a lock; the
# lock only guards ring replacement (set_capacity/reset vs export).
_ring: deque = deque(maxlen=DEFAULT_CAPACITY)
_ring_lock = threading.Lock()
_next_id = itertools.count(1).__next__
_current: ContextVar[Optional["Span"]] = ContextVar(
    "tt_trace_current", default=None
)
# perf_counter epoch: Chrome-trace ts is relative anyway, and
# perf_counter is the only clock monotonic enough to nest spans.
_EPOCH = time.perf_counter()
# factory(name) -> context manager entered and left alongside every
# real Span (set_mirror); None costs one global load a span
_mirror = None


class Span:
    """One timed interval. Use as a context manager; re-entry is not
    supported (spans are one-shot, like the histograms they feed)."""

    __slots__ = (
        "name",
        "attrs",
        "span_id",
        "parent_id",
        "root_id",
        "tid",
        "start_us",
        "dur_us",
        "_hist",
        "_hist_labels",
        "_t0",
        "_token",
        "_mirrored",
        "_follows",
    )

    def __init__(
        self,
        name: str,
        hist=None,
        hist_labels: Optional[Dict[str, str]] = None,
        attrs: Optional[Dict[str, Any]] = None,
        follows: Optional["Span"] = None,
    ) -> None:
        self.name = name
        self.attrs: Dict[str, Any] = attrs if attrs is not None else {}
        self.span_id = _next_id()
        self.parent_id = 0
        self.root_id = self.span_id
        self._follows = follows
        if follows is not None:
            self.attrs["follows"] = follows.span_id
        self.tid = 0
        self.start_us = 0.0
        self.dur_us = 0.0
        self._hist = hist
        self._hist_labels = hist_labels
        self._t0 = 0.0
        self._token = None
        self._mirrored = None

    def set(self, **attrs: Any) -> "Span":
        """Attach attributes mid-span (batch sizes known only after
        accumulation, device timings known only after gather)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        if self._follows is not None:
            self.root_id = self._follows.root_id
        else:
            parent = _current.get()
            if parent is not None:
                self.parent_id = parent.span_id
                self.root_id = parent.root_id
        self.tid = threading.get_ident()
        self._token = _current.set(self)
        # the mirror opens before the clock is read and closes after,
        # so on its timeline a child always lies inside its parent
        if _mirror is not None:
            self._mirrored = _mirror(self.name)
            self._mirrored.__enter__()
        self._t0 = time.perf_counter()
        self.start_us = (self._t0 - _EPOCH) * 1e6
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        self.dur_us = (t1 - self._t0) * 1e6
        if self._mirrored is not None:
            self._mirrored.__exit__(exc_type, exc, tb)
            self._mirrored = None
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        if self._hist is not None:
            self._hist.observe(
                t1 - self._t0, **(self._hist_labels or {})
            )
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        if _enabled:
            # tmlint: disable=lock-global-mutation — deque.append is
            # GIL-atomic; _ring_lock guards ring *replacement* only
            # (module docstring, line ~55)
            _ring.append(self)
        return False


class _NoopSpan:
    """Shared do-nothing span: the disabled path allocates no Span and
    touches neither the ring nor the contextvar."""

    __slots__ = ()

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


def span(name: str, hist=None, hist_labels=None, follows=None, **attrs: Any):
    """A nestable timed span. With `hist`, the elapsed seconds are also
    observed into that Histogram — so instrumented call sites keep
    their metrics series when tracing is off (the span then degrades to
    exactly `hist.time()`). With `follows` (a Span: another thread's
    `current()`), the span joins that span's tree without being its
    child (module docstring); None opens an ordinary span."""
    if not _enabled:
        if hist is not None:
            return hist.time(**(hist_labels or {}))
        return NOOP_SPAN
    return Span(name, hist, hist_labels, attrs, follows)


def add_attrs(**attrs: Any) -> None:
    """Attach attributes to the innermost live span, if any. A no-op
    when tracing is disabled or no span is open — hot paths call this
    unconditionally."""
    s = _current.get()
    if s is not None:
        s.attrs.update(attrs)


def current() -> Optional[Span]:
    """The innermost live span of this task/thread (None if tracing is
    off or no span is open)."""
    return _current.get()


def set_mirror(factory):
    """Mirror every span onto a second timeline: `factory(name)`
    returns a context manager that a real Span enters and leaves with
    itself; None clears it. The no-op span and `hist.time()` of the
    disabled path never call it. Returns the mirror it replaces, for a
    caller that restores it."""
    global _mirror
    held, _mirror = _mirror, factory
    return held


# the collection in progress, as a span: collections neither nest nor
# overlap (the collector holds the interpreter throughout), so one slot
_gc_span: Optional[Span] = None


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    """gc.callbacks hook while tracing is on: a collection that lands
    inside an open span is a `gc_collect` child of it."""
    global _gc_span
    if phase == "start":
        if _current.get() is not None:
            _gc_span = Span(
                "gc_collect", attrs={"generation": info["generation"]}
            )
            _gc_span.__enter__()
    elif _gc_span is not None:
        s, _gc_span = _gc_span, None
        s.attrs["collected"] = info["collected"]
        s.__exit__(None, None, None)


def enable(capacity: Optional[int] = None) -> None:
    """Turn the recorder on (optionally resizing the ring first)."""
    global _enabled
    if capacity is not None:
        set_capacity(capacity)
    # tmrace: race-ok — GIL-atomic bool latch: a span another thread
    # opens while it flips (the breaker's probe, crypto/tpu_verifier.py)
    # is recorded or is the no-op singleton, both whole
    _enabled = True
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def disable() -> None:
    """Kill switch: spans created after this return the no-op
    singleton; spans already open stop recording at exit. The
    collector's hook goes with it."""
    global _enabled
    _enabled = False
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def is_enabled() -> bool:
    return _enabled


def set_capacity(capacity: int) -> None:
    """Resize the ring, keeping the most recent spans."""
    global _ring
    if capacity < 1:
        raise ValueError(f"trace ring capacity must be >= 1: {capacity}")
    with _ring_lock:
        _ring = deque(_ring, maxlen=capacity)


def reset() -> None:
    """Drop every recorded span (tests; debug-dump isolation)."""
    with _ring_lock:
        _ring.clear()


def snapshot() -> List[Span]:
    """The recorded spans, oldest first."""
    with _ring_lock:
        return list(_ring)


# -- slow-request SLO exemplars ------------------------------------------
#
# When an RPC request blows past its per-route SLO threshold
# (rpc/metrics.py slo_for), the server captures the request's span
# subtree from the ring into this second bounded ring — so a p99
# outlier in the latency sketch arrives with its own flame
# decomposition instead of a bare number. Kill-switched exactly like
# the span recorder itself (off by default; `record_slow_request` is a
# cheap boolean check when disabled), and capacity-bounded (old
# exemplars are evicted, never blocked on). With span tracing disabled
# the exemplar still records route/duration/threshold — just with an
# empty span tree.

_exemplars_enabled = False
_exemplars: deque = deque(maxlen=DEFAULT_EXEMPLAR_CAPACITY)
_exemplar_lock = threading.Lock()


def enable_exemplars(capacity: Optional[int] = None) -> None:
    """Turn slow-request exemplar capture on (optionally resizing)."""
    global _exemplars_enabled
    if capacity is not None:
        set_exemplar_capacity(capacity)
    _exemplars_enabled = True


def disable_exemplars() -> None:
    """Kill switch: record_slow_request becomes a no-op."""
    global _exemplars_enabled
    _exemplars_enabled = False


def exemplars_enabled() -> bool:
    return _exemplars_enabled


def set_exemplar_capacity(capacity: int) -> None:
    """Resize the exemplar ring, keeping the most recent entries."""
    global _exemplars
    if capacity < 1:
        raise ValueError(
            f"exemplar ring capacity must be >= 1: {capacity}"
        )
    with _exemplar_lock:
        _exemplars = deque(_exemplars, maxlen=capacity)


def reset_exemplars() -> None:
    """Drop every captured exemplar (tests; debug-dump isolation)."""
    with _exemplar_lock:
        _exemplars.clear()


def _span_dict(s: Span) -> Dict[str, Any]:
    return {
        "name": s.name,
        "span_id": s.span_id,
        "parent_id": s.parent_id,
        "root_id": s.root_id,
        "start_us": round(s.start_us, 3),
        "dur_us": round(s.dur_us, 3),
        "attrs": dict(s.attrs),
    }


def record_slow_request(
    route: str, dur_s: float, threshold_s: float, root=None
) -> None:
    """Capture one SLO-breach exemplar. `root` is the request's Span
    (anything else — the no-op singleton, a histogram timer — yields an
    exemplar without a tree). The root's recorded descendants are
    collected from the span ring; children exit before their parent, so
    the newest-first walk sees the root, then its children, then their
    children. O(ring) per capture — SLO breaches are rare by
    definition, and the disabled path is one boolean check."""
    if not _exemplars_enabled:
        return
    spans = []
    if isinstance(root, Span):
        ids = {root.span_id}
        for s in reversed(snapshot()):
            if s.span_id in ids or s.parent_id in ids:
                ids.add(s.span_id)
                spans.append(_span_dict(s))
        spans.reverse()  # chronological (oldest first)
    exemplar = {
        "route": route,
        "dur_ms": round(dur_s * 1e3, 3),
        "slo_ms": round(threshold_s * 1e3, 3),
        "spans": spans,
    }
    # tmlint: disable=lock-global-mutation — deque.append is
    # GIL-atomic; _exemplar_lock guards ring *replacement* only (same
    # contract as the span ring above)
    _exemplars.append(exemplar)


def exemplar_snapshot() -> List[Dict[str, Any]]:
    """The captured exemplars, oldest first."""
    with _exemplar_lock:
        return list(_exemplars)


def exemplars_to_json() -> str:
    """Export the exemplar ring (debug bundle `slow_requests.json`)."""
    return json.dumps(
        {"slow_requests": exemplar_snapshot()}, default=str
    )


def to_chrome_trace() -> str:
    """Export the ring as Chrome-trace JSON ("traceEvents" complete
    events, loadable in chrome://tracing and Perfetto). `span_id` /
    `parent_id` / `root_id` ride in args so the exact nesting survives
    export even across interleaved asyncio tasks on one thread."""
    events = []
    for s in snapshot():
        args = dict(s.attrs)
        args["span_id"] = s.span_id
        args["parent_id"] = s.parent_id
        args["root_id"] = s.root_id
        events.append(
            {
                "name": s.name,
                "ph": "X",
                "ts": round(s.start_us, 3),
                "dur": round(s.dur_us, 3),
                "pid": 0,
                "tid": s.tid,
                "args": args,
            }
        )
    return json.dumps(
        {"traceEvents": events, "displayTimeUnit": "ms"}, default=str
    )
