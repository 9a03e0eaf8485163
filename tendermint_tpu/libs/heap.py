"""The heap behind a traced device program, taken out of the collector's reach.

Tracing, lowering and compiling (or loading) one device program leaves
about a quarter of a million tracked objects that never die: the jaxprs
and lowerings `jit`'s caches keep. CPython's full collections walk
every one of them, each time: with five programs warm a full collection
is ≈ 0.4 s, and a 10,000-vote commit promotes enough objects to bring
one on every ≈ 13 requests (PERF.md §6, PR 29).

So whoever sees a program compiled or loaded calls `mark_dirty()` (the
device seam's `jax.monitoring` listener, crypto/tpu_verifier.py), and
the thread that was dispatching calls `settle()` once its seam call is
quiet again: one full collection, so that no garbage is frozen (a cycle
frozen while garbage is never freed), then `gc.freeze()`, which moves
every survivor into the permanent generation. The collector stays on
and its thresholds stay the interpreter's; it walks only what was
allocated since. `thaw()` gives the heap back.

Process-global like the collector itself. A process that never
compiles a device program never marks, never settles, never freezes.
"""

from __future__ import annotations

import contextlib
import gc
import threading

from . import metrics as M

__all__ = ["deferred", "mark_dirty", "settle", "thaw", "stats"]

_m_settles = M.new_counter(
    "heap",
    "settles_total",
    "Full collections followed by gc.freeze(), one after each seam call "
    "that traced, compiled or loaded a device program.",
)
_m_frozen = M.new_gauge(
    "heap",
    "frozen_objects",
    "gc.get_freeze_count() after the last settle: tracked objects no "
    "collection walks any more (0 before the first settle and after a "
    "thaw).",
)

_lock = threading.Lock()
_dirty = False  # guarded by _lock
_deferrals = 0  # open deferred() scopes, guarded by _lock


def mark_dirty() -> None:
    """A device program was just traced, compiled or loaded: the heap
    has grown by objects that will not die. Cheap and safe inside a
    `jax.monitoring` listener; the collection waits for `settle()`,
    when the compile's own temporaries are dead."""
    global _dirty
    with _lock:
        _dirty = True


def settle() -> bool:
    """Collect and freeze if the heap was marked since the last settle;
    otherwise nothing, at the cost of one lock. True when it froze.
    Inside a `deferred()` scope the mark stays and nothing is frozen."""
    global _dirty
    with _lock:
        if not _dirty or _deferrals:
            return False
        _dirty = False
    gc.collect()
    gc.freeze()
    # the survivors just counted are in the permanent generation now:
    # without this (empty, instant) collection the interpreter's "full
    # collection only once a quarter of the last survivors is pending"
    # rule would go on dividing by them
    gc.collect()
    _m_settles.inc()
    _m_frozen.set(gc.get_freeze_count())
    return True


@contextlib.contextmanager
def deferred():
    """Hold every settle back to the end of this scope, and settle
    there once. For a seam call that keeps several batches in flight:
    the first batch's gather is not the moment "every handle is
    gathered", and a freeze there would take the other batches' live
    handles and rows into the permanent generation."""
    global _deferrals
    with _lock:
        _deferrals += 1
    try:
        yield
    finally:
        with _lock:
            _deferrals -= 1
        settle()


def thaw() -> None:
    """Back into the collector's reach: everything frozen returns to
    the oldest generation, and a pending mark is dropped."""
    global _dirty
    with _lock:
        _dirty = False
    gc.unfreeze()
    _m_frozen.set(0)


def stats() -> dict:
    return {
        "heap_settles": int(_m_settles.value()),
        "heap_frozen_objects": int(_m_frozen.value()),
    }
