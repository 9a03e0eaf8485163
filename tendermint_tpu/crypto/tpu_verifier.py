"""Device-backed BatchVerifier — the TPU side of the plugin boundary.

The reference gates all batch verification behind crypto.BatchVerifier
(crypto/crypto.go:53-61) with curve25519-voi underneath
(crypto/ed25519/ed25519.go:202-237). Here the implementation underneath
is the XLA program in tendermint_tpu.ops.ed25519_kernel; install() makes
crypto.batch.create_batch_verifier return it for ed25519 keys when the
batch is large enough to beat host latency. CPU remains the default
until install() is called, exactly like the reference defaults to pure
Go.

Device-fault containment: the device is treated as an UNRELIABLE
coprocessor (docs/resilience.md). Every dispatch/gather is a fault
point of crypto/faults.py; gathers run under a deadline watchdog
(a hung device surfaces as DeviceTimeout instead of wedging consensus);
a faulted batch is transparently re-verified through the registered CPU
factory with byte-identical result semantics (same bitmap alignment,
so the same wrong-signature index) and is never allowed to populate the
verified-signature cache. Each route consults a named circuit breaker
(crypto/breaker.py): a tripped breaker sends new work straight to the
CPU factories — zero per-call device touches, zero per-call warnings —
until a single-flight background probe proves the device again.
"""

from __future__ import annotations

import os
import threading
import time
from typing import List, Optional, Tuple

from ..config import DEFAULT_BUCKET_SIZES, bucket_for
from ..libs import heap
from ..libs import metrics as M
from ..libs import trace
from . import breaker as _breaker_mod
from . import faults
from .batch import cpu_factory, register_device_factory
from .faults import DeviceFault, DeviceTimeout
from .keys import BatchVerifier, PubKey

# device-offload observability (no reference analog — this is the
# north-star seam's instrumentation). Deliberately process-global on
# DEFAULT_REGISTRY, unlike the per-node subsystem metrics: there is one
# device runtime per process, and multi-node embedders share it.
_m_batches = M.new_counter(
    "tpu", "verify_batches_total", "Device batch-verify invocations."
)
_m_sigs = M.new_counter(
    "tpu", "verify_sigs_total", "Signatures verified on device."
)
_m_device_faults = M.new_counter(
    "tpu",
    "device_faults_total",
    "Device faults contained (raise/timeout/mis-shape/disproven result).",
)
_m_verify_time = M.new_histogram(
    "tpu",
    "verify_seconds",
    "Wall time of one batch verification.",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
)
# dispatch telemetry: decompose verify_seconds into the host-side
# assembly (packing triples into device arrays + async launch) and the
# host's wait at the gather barrier — a wait, not device time: the
# device's own time is read from a profiler trace — plus bucket-padding
# waste and warm-generation hit/miss for compile-stall attribution.
_m_host_prep = M.new_histogram(
    "tpu",
    "host_prep_seconds",
    "Host-side packing + async dispatch of one batch.",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25),
)
_m_gather_wait = M.new_histogram(
    "tpu",
    "gather_wait_seconds",
    "Host time blocked at the gather barrier of one batch.",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5),
)
_m_pad_waste = M.new_counter(
    "tpu",
    "pad_waste_slots_total",
    "Signature slots wasted padding batches to bucket shapes.",
)
_m_warm_hits = M.new_counter(
    "tpu",
    "warm_bucket_hits_total",
    "Dispatches into a bucket already run this install generation.",
)
_m_warm_misses = M.new_counter(
    "tpu",
    "warm_bucket_misses_total",
    "First dispatches into a bucket (likely paying an XLA compile).",
)
_m_mesh_devices = M.new_gauge(
    "tpu",
    "mesh_devices",
    "Chips the installed verifiers shard a batch over (1 without a "
    "mesh, 0 when nothing is installed).",
)

__all__ = [
    "TpuEd25519BatchVerifier",
    "TpuSr25519BatchVerifier",
    "DeviceFault",
    "DeviceTimeout",
    "install",
    "installed",
    "stats",
    "DEFAULT_MIN_BATCH",
    "DEFAULT_GATHER_DEADLINE_S",
]

# Below this many signatures the fixed dispatch cost (host packing +
# device roundtrip, ~100s of µs) exceeds CPU verify time; let CPU win.
DEFAULT_MIN_BATCH = 8

# Gather deadline when none is configured. XLA compiles block in
# dispatch() (tracing + compile are synchronous), so the gather barrier
# only ever waits for an already-launched program: 60 s of silence
# there means a hung device, not a slow batch. Seen on a TPU v5e
# (chip_smoke.py, PR 21): cold compiles of up to 117 s all blocked in
# dispatch(), and the slowest whole verification of a 10,000-signature
# mixed commit — six dispatches and their gathers — took about a second.
DEFAULT_GATHER_DEADLINE_S = 60.0

# lazily cached "is the backend a real accelerator" decision
_STREAMING: Optional[bool] = None

# (key type, backing verifier id, bucket) triples dispatched at least
# once since the last install()/uninstall(): first touch of a bucket
# shape likely pays an XLA compile, so dispatch telemetry labels it a
# warm miss. Cleared on install/uninstall — a new generation's programs
# are cold again.
_WARM_BUCKETS: set = set()


def _bucket_of(verifier, n: int) -> int:
    """The padded bucket `n` signatures land in: the backing verifier's
    own answer (ops/verifier.py `_bucket`, mesh rounding included). An
    injected verifier that promises dispatch()/gather() alone is asked
    for its sizes, read through the same rule (config.bucket_for:
    telemetry must not import the jax-backed ops modules)."""
    rule = getattr(verifier, "_bucket", None)
    if rule is not None:
        return rule(n)
    sizes = getattr(verifier, "bucket_sizes", None) or DEFAULT_BUCKET_SIZES
    return bucket_for(n, sorted(sizes))


def _mesh_devices(verifier) -> int:
    """How many chips a backing verifier divides a dispatch over: the
    size of its mesh (parallel/sharding.py), 1 without one."""
    mesh = getattr(verifier, "mesh", None)
    return 1 if mesh is None else int(mesh.devices.size)


def _note_bucket_warmth(key_type: str, verifier, bucket: int) -> bool:
    """Record (and count) whether this bucket shape has been dispatched
    before in this install generation. Returns the hit/miss verdict for
    the span attributes."""
    key = (key_type, id(verifier), bucket)
    if key in _WARM_BUCKETS:
        _m_warm_hits.inc()
        return True
    # tmlint: disable=lock-global-mutation — telemetry-only set;
    # a racing probe thread at worst double-counts one warm miss
    _WARM_BUCKETS.add(key)
    _m_warm_misses.inc()
    return False


def on_accelerator() -> bool:
    """True when this process's jax backend is a TPU.

    CPU-pinned processes (jax_platforms == "cpu" — the test suite, any
    CPU-only node) are answered from the config STRING without
    initializing a backend, so consensus-critical callers like
    sr25519's single-verify route never stall on backend init just to
    learn they should use the Python path. Everything else pays one
    backend query, latched for the life of the process — those
    processes are about to dispatch to the device anyway. The
    installation ships libtpu, so a CPU-only deployment that leaves
    jax_platforms unset pays jax's failed TPU discovery on that first
    query; it should set jax_platforms=cpu."""
    global _STREAMING
    if _STREAMING is None:
        import jax

        plats = jax.config.jax_platforms  # no backend init
        if plats and set(plats.split(",")) == {"cpu"}:
            # tmrace: race-ok — idempotent latch: every racer computes
            # the same value from process-wide config; bool store is
            # GIL-atomic
            _STREAMING = False
        else:
            # tmrace: race-ok — same idempotent latch (jax backend init
            # is internally synchronized)
            _STREAMING = jax.default_backend() == "tpu"
    return _STREAMING


# -- fault containment plumbing --------------------------------------


# (env string, parsed deadline) — the string is still read per call so
# tests can flip the env var, but the float parse is paid once per value
_DEADLINE_CACHE: tuple = (None, None)


def gather_deadline() -> Optional[float]:
    """The gather watchdog deadline, or None (direct call, no watchdog
    thread). TM_TPU_GATHER_DEADLINE_S pins it explicitly (0 disables);
    otherwise the default applies only where a gather can actually
    hang — a real accelerator — or while the
    fault plane is armed (chaos tests exercise the hang mode). Plain
    CPU-backed processes keep a thread-free hot path."""
    global _DEADLINE_CACHE
    env = os.environ.get("TM_TPU_GATHER_DEADLINE_S")
    if env is not None:
        if _DEADLINE_CACHE[0] != env:
            try:
                dl = float(env)  # tmlint: disable=dev-host-sync — env-var string, host data
            except ValueError:
                dl = DEFAULT_GATHER_DEADLINE_S
            # tmrace: race-ok — idempotent per env value; racers
            # parse the same string and the tuple store is GIL-atomic
            _DEADLINE_CACHE = (env, dl if dl > 0 else None)
        return _DEADLINE_CACHE[1]
    if faults.armed() or on_accelerator():
        return DEFAULT_GATHER_DEADLINE_S
    return None


# Abandoned watchdog workers still blocked inside a wedged gather.
# Bounded: once the cap is hit, further deadline calls fail fast with
# DeviceTimeout instead of stacking another forever-blocked thread —
# otherwise the breaker's periodic probes against a dead device would
# leak one thread per probe for the life of the process. Healthy
# workers are recycled through a small free-list, so the steady-state
# hot path pays one Event set/wait per gather, not a thread spawn.
_MAX_WEDGED_GATHERS = 8
_MAX_IDLE_WATCHDOGS = 4
_IDLE_WATCHDOGS: list = []  # guarded by _wedged_lock
_wedged_gathers = 0
_wedged_lock = threading.Lock()


class _Watchdog:
    """One reusable daemon worker: runs one job at a time, parks on an
    Event between jobs. A worker whose job wedged is abandoned (never
    returned to the free-list) and retires itself if the job ever
    finishes; a daemon thread cannot block process exit either way."""

    __slots__ = ("_job", "_wake", "thread")

    def __init__(self) -> None:
        self._job = None
        self._wake = threading.Event()
        self.thread = threading.Thread(
            target=self._loop, daemon=True, name="tpu-gather-watchdog"
        )
        self.thread.start()

    def run(self, job: tuple) -> None:
        # tmrace: race-ok — Event handshake: the _job store
        # happens-before _wake.set(), and a worker is owned by exactly
        # one caller between its free-list pop (under _wedged_lock) and
        # its requeue, so no second run() can interleave
        self._job = job
        self._wake.set()

    def _loop(self) -> None:
        global _wedged_gathers
        while True:
            # tmlive: block-ok — parked watchdog worker between jobs:
            # blocking HERE is this daemon thread's whole job (it
            # exists so the *caller* can bound its wait with
            # done.wait(deadline_s)); an idle worker must cost zero CPU
            self._wake.wait()
            # tmrace: race-ok — other half of the run() Event
            # handshake: wait() returned, so the owner's _job store is
            # visible, and nobody re-runs this worker until it requeues
            self._wake.clear()
            fn, result, done, state = self._job
            self._job = None  # tmrace: race-ok — same handshake
            try:
                result["val"] = fn()
            except BaseException as e:  # delivered to the caller
                result["exc"] = e
            with _wedged_lock:
                done.set()  # inside the lock: atomic vs timeout path
                if state["abandoned"]:
                    # the wedge finally resolved; the slot frees but
                    # this worker retires (its result was discarded)
                    _wedged_gathers -= 1
                    return
                if len(_IDLE_WATCHDOGS) >= _MAX_IDLE_WATCHDOGS:
                    return
                _IDLE_WATCHDOGS.append(self)


def _deadline_call(fn, deadline_s: float):
    """Run fn on a watchdog worker, bounded by deadline_s. On expiry
    the worker is ABANDONED (a blocked gather cannot be interrupted
    from Python) and DeviceTimeout raises in the caller — the breaker
    then keeps everyone else off the hung device. Abandoned-but-
    still-blocked workers are counted and capped (_MAX_WEDGED_GATHERS):
    at the cap, calls fail fast, so a permanently dead device costs a
    fixed number of parked threads, not one per probe."""
    global _wedged_gathers
    with _wedged_lock:
        if _wedged_gathers >= _MAX_WEDGED_GATHERS:
            raise DeviceTimeout(
                f"device gather skipped: {_wedged_gathers} wedged "
                f"gathers already outstanding"
            )
        w = _IDLE_WATCHDOGS.pop() if _IDLE_WATCHDOGS else None
    if w is None:
        w = _Watchdog()
    result: dict = {}
    state = {"abandoned": False}
    done = threading.Event()
    w.run((fn, result, done, state))
    if not done.wait(deadline_s):
        with _wedged_lock:
            if not done.is_set():  # really wedged, not a photo finish
                state["abandoned"] = True
                _wedged_gathers += 1
        if state["abandoned"]:
            raise DeviceTimeout(
                f"device gather exceeded its {deadline_s}s deadline"
            )
    if "exc" in result:
        raise result["exc"]
    return result["val"]


def _gather_guarded(v, handle, key_type: str) -> List[bool]:
    """One gather with the full containment stack: fault-plane hooks
    (raise/hang fire inside the watchdog so a hang surfaces as
    DeviceTimeout), the deadline, and data-fault mangling applied to
    the bitmap exactly where a broken device would corrupt it.

    The job is a `gather_job` span on the thread that runs it (the
    watchdog's, or the caller's where there is no deadline) following
    the caller's span (`tpu_gather`, `tpu_probe`): the caller's span
    keeps the whole wait, and what the job does not cover of it is the
    handoff to the thread and back."""
    waiting = trace.current()

    def call():
        with trace.span("gather_job", follows=waiting, key=key_type) as job:
            if faults.armed():
                faults.fire("tpu.gather", key=key_type)
            out = [bool(b) for b in v.gather(handle)]
            job.set(lanes=len(out))
            return out

    dl = gather_deadline()
    bits = call() if dl is None else _deadline_call(call, dl)
    if faults.armed():
        bits = faults.mangle("tpu.gather", bits, key=key_type)
    return bits


def _breaker(key_type: str):
    return _breaker_mod.breaker_for(key_type)


class _RoutedToCpu(Exception):
    """Internal: the breaker is open — reroute silently, no fault."""


class _TpuBatchVerifier(BatchVerifier):
    """Queues triples on host, verifies on device.

    Same verify() contract as the CPU path: (all_ok, bitmap), bitmap
    aligned with add() order, malformed entries reported invalid
    per-index rather than raising at verify time.

    On a TPU backend, full STREAM_CHUNK-sized slices are dispatched
    asynchronously AS add() (or add_many(), a column at a time) fills
    them, so the host-side assembly loop
    (sign-bytes, address lookups — ~2 us/sig in VerifyCommit) overlaps
    device compute instead of serializing in front of it; verify()
    dispatches the remainder and gathers every in-flight handle in add
    order. The chunk matches a configured bucket so no new program
    shapes are compiled. On any backend launch() dispatches the
    remainder ahead of verify(), at the bucket verify() would have
    used, so that a caller with several key classes
    (crypto.batch.drain_classes) has them all in flight before it
    blocks at the first gather.

    Fault containment: every triple is retained (as references) until
    verify() returns, so ANY device failure — a raising dispatch, a
    gather past its deadline, a mis-shaped bitmap, a device-invalidated
    lane the CPU disproves — drains the batch through the registered
    CPU factory instead. The CPU bitmap is add-order aligned, so
    callers see the same wrong-signature index either way; `faulted`
    is left True so crypto.batch.drain_and_cache refuses to populate
    the verified-signature cache from a batch the device touched and
    lied about (or died under)."""

    KEY_TYPE = ""  # subclasses set
    STREAM_CHUNK = 2048  # == a DEFAULT_BUCKET_SIZES entry

    def __init__(self, verifier=None) -> None:
        self._verifier = verifier
        self._kernel = self._kernel_module()
        # authoritative add-order record, kept until verify() returns
        # (the CPU re-verify fallback needs the PubKey objects)
        self._all: List[Tuple[PubKey, bytes, bytes]] = []
        # pending window awaiting dispatch (bytes for the kernel)
        self._pks: List[bytes] = []
        self._msgs: List[bytes] = []
        self._sigs: List[bytes] = []
        self._handles: List[tuple] = []  # (backing, handle, n), add order
        self._stream_fault: Optional[BaseException] = None
        self.faulted = False  # True once a device fault was contained
        # dispatch telemetry accumulated across THIS one-shot batch
        # (streaming chunks launch from add(), before verify() runs)
        self._last_bucket = 0
        self._pad_waste = 0
        self._cold_dispatch = False
        # seconds launch() spent packing and launching the remainder:
        # verify() reports them in its host_prep_s
        self._launch_s = 0.0

    @staticmethod
    def _kernel_module():
        raise NotImplementedError

    def _backing(self):
        return (
            self._verifier
            if self._verifier is not None
            else self._kernel.default_verifier()
        )

    @staticmethod
    def _streaming() -> bool:
        """Chunked dispatch only pays on an accelerator (CPU 'device'
        programs are the bottleneck themselves, and extra bucket shapes
        would mean extra test-suite compiles)."""
        return on_accelerator()

    def _account_dispatch(self, v, n: int) -> None:
        """Telemetry for ONE device dispatch of n triples: bucket
        padding waste and warm-generation hit/miss. Called on every
        launch — streaming chunks from add() included, since that is
        exactly where a first-touch XLA compile stalls the hot path."""
        bucket = _bucket_of(v, n)
        waste = bucket - n
        self._last_bucket = bucket
        if waste:
            self._pad_waste += waste
            _m_pad_waste.inc(waste)
        if not _note_bucket_warmth(self.KEY_TYPE, v, bucket):
            self._cold_dispatch = True

    def _dispatch_pending(self, v) -> None:
        """Asynchronously launch the queued triples on `v` and clear
        the queue; the handle is gathered in verify(). Each dispatch is
        one device invocation for the metrics."""
        if faults.armed():
            faults.fire("tpu.dispatch", key=self.KEY_TYPE)
        self._account_dispatch(v, len(self._pks))
        self._handles.append(
            (v, v.dispatch(self._pks, self._msgs, self._sigs),
             len(self._pks))
        )
        self._pks, self._msgs, self._sigs = [], [], []
        _m_batches.inc()

    def _launch_window(self, span_name: str) -> None:
        """Launch the pending window ahead of verify(), under a span of
        `span_name`, if the route can take it: injected verifiers only
        promise verify(), so the dispatch()/gather() pair has to be
        there, and the route fully healthy (state(), not allow(): such
        a launch must never consume the one half-open admission ticket
        the factory gate hands out). A faulted async launch must not
        raise out of add() or launch(): the window stays queued, and
        verify() sees the recorded fault and drains everything on CPU."""
        v = self._backing()
        if not (
            hasattr(v, "dispatch")
            and hasattr(v, "gather")
            and _breaker(self.KEY_TYPE).state() == _breaker_mod.CLOSED
        ):
            return
        with trace.span(
            span_name,
            key=self.KEY_TYPE,
            n=len(self._pks),
            chunk=len(self._handles),
            mesh_devices=_mesh_devices(v),
        ) as span:
            try:
                self._dispatch_pending(v)
            except Exception as e:
                self._stream_fault = e
            span.set(bucket=self._last_bucket)

    def host_operand(self, n: int) -> bool:
        """Whether launching `n` triples makes a tile's third operand on
        the host: the backing's answer (ops/verifier.py `host_operand`:
        sr25519's merlin challenges in narrow launches; ed25519's
        SHA-512 is a device program) for the narrowest launch, the
        remainder past the chunks that stream."""
        ask = getattr(self._backing(), "host_operand", None)
        if ask is None:
            return False
        if n > self.STREAM_CHUNK and self._streaming():
            n = n % self.STREAM_CHUNK or self.STREAM_CHUNK
        return bool(ask(n))

    def launch(self) -> bool:
        """Dispatch the remainder now and return without gathering: the
        first half of verify()'s work, for a caller that has another
        class to fill. verify() then finds the handles and an empty
        window and goes straight to the gather. The triples stay until
        verify() returns, so every containment property is verify()'s."""
        if self._pks and self._stream_fault is None:
            t0 = time.perf_counter()
            self._launch_window("tpu_early_dispatch")
            self._launch_s += time.perf_counter() - t0
        return (
            bool(self._handles)
            and not self._pks
            and self._stream_fault is None
        )

    def abandon(self) -> None:
        """Drop the in-flight handles and the queue (the device ends
        what it was given; nobody reads the result)."""
        self._handles = []
        self._pks, self._msgs, self._sigs = [], [], []
        self._all = []
        self._stream_fault = None

    def add(self, pub_key: PubKey, message: bytes, signature: bytes) -> None:
        if pub_key.type() != self.KEY_TYPE:
            raise TypeError(
                f"{type(self).__name__} requires {self.KEY_TYPE} keys"
            )
        if len(signature) != 64:
            raise ValueError("malformed signature size")
        message = bytes(message)
        signature = bytes(signature)
        self._all.append((pub_key, message, signature))
        self._pks.append(pub_key.bytes())
        self._msgs.append(message)
        self._sigs.append(signature)
        if (
            len(self._pks) >= self.STREAM_CHUNK
            and self._streaming()
            and self._stream_fault is None
        ):
            self._launch_window("tpu_stream_dispatch")

    def add_many(
        self, pub_keys, messages, signatures, key_bytes=None
    ) -> None:
        """add() a column at a time: the same checks, made over the
        whole columns before anything is queued, and the same streaming
        rule, the window filled by slices. A key's type is asked of one
        key a Python class (PubKey.type() names the class's curve, not
        the instance); with `key_bytes` no method is called a key."""
        n = len(pub_keys)
        if not (len(messages) == len(signatures) == n):
            raise ValueError("columns of unequal length")
        if key_bytes is None:
            key_bytes = [pub_key.bytes() for pub_key in pub_keys]
        elif len(key_bytes) != n:
            raise ValueError("columns of unequal length")
        for cls in set(map(type, pub_keys)):
            pub_key = next(pk for pk in pub_keys if type(pk) is cls)
            if pub_key.type() != self.KEY_TYPE:
                raise TypeError(
                    f"{type(self).__name__} requires {self.KEY_TYPE} keys"
                )
        if not set(map(len, signatures)) <= {64}:
            raise ValueError("malformed signature size")
        # add()'s bytes() of each, paid only where something is not
        if not set(map(type, messages)) <= {bytes}:
            messages = list(map(bytes, messages))
        if not set(map(type, signatures)) <= {bytes}:
            signatures = list(map(bytes, signatures))
        self._all.extend(zip(pub_keys, messages, signatures))
        chunk = self.STREAM_CHUNK
        # asked only of a column that can fill a window, as add() asks
        # only at a full one: the answer may start the backend
        stream = len(self._pks) + n >= chunk and self._streaming()
        at = 0
        while at < n:
            streaming = stream and self._stream_fault is None
            # up to a full window; one triple at a time past a window
            # the route would not take, each trying again as add() does
            end = (
                min(n, at + max(chunk - len(self._pks), 1))
                if streaming
                else n
            )
            self._pks.extend(key_bytes[at:end])
            self._msgs.extend(messages[at:end])
            self._sigs.extend(signatures[at:end])
            at = end
            if streaming and len(self._pks) >= chunk:
                self._launch_window("tpu_stream_dispatch")

    def verify(self) -> Tuple[bool, List[bool]]:
        """Drains the queue: a verifier is a one-shot batch (matching
        the reference's use — one BatchVerifier per commit); calling
        verify() again without new add()s reports (False, []) on every
        backend. In streaming mode verify_seconds times the remainder
        dispatch + gather barrier (chunk dispatches already ran inside
        add, overlapped with the caller's assembly loop).

        The tpu_dispatch span splits at the async-launch boundary:
        everything before the last handle exists is host packing
        (`host_prep_s`, tpu_host_prep_seconds), everything after is the
        `tpu_gather` child span, the host blocked on the device
        (tpu_gather_wait_seconds). The chunks add() streamed earlier
        are `tpu_stream_dispatch` spans of their own; a remainder that
        launch() dispatched earlier is a `tpu_early_dispatch` span, and
        its seconds are in `host_prep_s` all the same: the remainder's
        packing and launch are one quantity wherever they run. Backings
        without the dispatch()/gather() pair (injected test verifiers)
        report one undivided wall time.

        Any device fault — including a mis-shaped bitmap or a lane the
        device invalidated that the CPU disproves — re-verifies the
        WHOLE batch through the CPU factory (a faulted device's earlier
        answers are not trusted either), records the fault on this key
        type's breaker, and marks the batch `faulted` so its results
        never reach the verified-signature cache. tpu_verify_sigs_total
        counts only work the device actually completed."""
        if not self._all and not self._handles:
            return False, []
        t0 = time.perf_counter()
        with trace.span(
            "tpu_dispatch", hist=_m_verify_time, key=self.KEY_TYPE
        ):
            work = self._all
            total = len(work)
            v = self._backing()
            bits: Optional[List[bool]] = None
            fault: Optional[BaseException] = None
            device_sigs = 0  # lanes with a COMPLETED device verdict
            host_prep: Optional[float] = None
            try:
                if self._stream_fault is not None:
                    raise self._stream_fault
                # side-effect-free OPEN check (not allow(): this
                # verifier was already admitted at creation — possibly
                # holding the route's one half-open ticket, which a
                # second allow() here would have burned, wedging the
                # breaker in HALF_OPEN forever). A HALF_OPEN attempt
                # proceeds and reports its outcome below: the admitted
                # verifier IS the probe on probe-less breakers.
                if (
                    not self._handles
                    and _breaker(self.KEY_TYPE).state() == _breaker_mod.OPEN
                ):
                    raise _RoutedToCpu()
                if self._handles or (
                    hasattr(v, "dispatch") and hasattr(v, "gather")
                ):
                    # the remainder, unless launch() sent it ahead (one
                    # launch of the whole batch where nothing streamed)
                    if self._pks:
                        self._dispatch_pending(v)
                    host_prep = time.perf_counter() - t0 + self._launch_s
                    got: List[bool] = []
                    try:
                        with trace.span(
                            "tpu_gather",
                            hist=_m_gather_wait,
                            handles=len(self._handles),
                            sigs=total,
                        ):
                            for bv, handle, n in self._handles:
                                lane = _gather_guarded(
                                    bv, handle, self.KEY_TYPE
                                )
                                if len(lane) != n:
                                    raise DeviceFault(
                                        f"mis-shaped device result: "
                                        f"{len(lane)} lanes for {n} "
                                        f"signatures"
                                    )
                                got.extend(lane)
                                device_sigs += n
                    finally:
                        # a gather that raises mid-loop must still
                        # leave the verifier drained: a retry would
                        # otherwise re-gather stale handles, and
                        # __len__ would keep reporting in-flight work
                        self._handles = []
                    bits = got
                else:
                    self._account_dispatch(v, len(self._pks))
                    if faults.armed():
                        faults.fire("tpu.dispatch", key=self.KEY_TYPE)
                    raw = v.verify(self._pks, self._msgs, self._sigs)
                    _m_batches.inc()
                    bits = [bool(b) for b in raw]
                    if faults.armed():
                        bits = faults.mangle(
                            "tpu.gather", bits, key=self.KEY_TYPE
                        )
                    if len(bits) != total:
                        raise DeviceFault(
                            f"mis-shaped device result: {len(bits)} "
                            f"lanes for {total} signatures"
                        )
                    device_sigs = total
                if not all(bits):
                    self._disprove_invalid_lanes(work, bits)
            except _RoutedToCpu:
                bits = None  # silent reroute: breaker already open
            except Exception as e:
                bits = None
                fault = e
            finally:
                # one-shot on every path: success, fault, or reroute
                self._handles = []
                self._pks, self._msgs, self._sigs = [], [], []
                self._all = []
                self._stream_fault = None
            if bits is None:
                _m_sigs.inc(device_sigs)
                return self._cpu_fallback(work, fault, total)
            _breaker(self.KEY_TYPE).record_success()
            if host_prep is not None:
                _m_host_prep.observe(host_prep)
                trace.add_attrs(host_prep_s=round(host_prep, 6))
            trace.add_attrs(
                batch=total,
                bucket=self._last_bucket,
                pad_waste=self._pad_waste,
                warm=not self._cold_dispatch,
                mesh_devices=_mesh_devices(v),
            )
            # every handle of this batch is gathered: if a dispatch of
            # it (a chunk add() streamed, or the remainder) traced,
            # compiled or loaded a program, its temporaries are dead
            # and what it left behind is not going to die. A caller
            # with another class still in flight holds the settle back
            # to its last gather (heap.deferred)
            heap.settle()
        _m_sigs.inc(device_sigs)
        return all(bits), bits

    def _disprove_invalid_lanes(self, work, bits: List[bool]) -> None:
        """Cross-examine every lane the device called invalid against a
        CPU verify. A genuinely wrong signature fails both ways (the
        normal cost: one CPU verify per bad lane, on an exceptional
        path); a lane the CPU verifies is a device lie — a bit-flipped
        result — and the whole batch is escalated to a fault. The
        asymmetric flip (bad signature reported GOOD) cannot be caught
        without re-verifying everything; it is excluded by the batch
        equation itself on a correct program, and chaos coverage pins
        the symmetric case (tests/test_faults.py).

        The oracle must be HOST-ONLY: key types whose verify_signature
        routes singles back to the device (sr25519) expose
        verify_signature_cpu for exactly this — an oracle that asked
        the device about the device's own verdict could never catch it
        lying (and would recurse through the single route)."""
        with trace.span("cpu_disprove", lanes=bits.count(False)):
            for i, ok in enumerate(bits):
                if ok:
                    continue
                pub_key, msg, sig = work[i]
                oracle = getattr(
                    pub_key, "verify_signature_cpu",
                    pub_key.verify_signature,
                )
                if oracle(msg, sig):
                    raise DeviceFault(
                        f"device invalidated lane {i} but the CPU "
                        f"verifies it: result disproven"
                    )

    def _cpu_fallback(self, work, fault, total: int) -> Tuple[bool, List[bool]]:
        """Drain `work` through the registered CPU factory. With
        `fault` set this is containment (breaker notified, fault
        counted, batch marked so the sigcache never learns from it);
        with fault=None the breaker was already open and this is just
        the quiet degraded route."""
        if fault is not None:
            self.faulted = True
            _m_device_faults.inc()
            _breaker(self.KEY_TYPE).record_failure()
            from ..libs.log import get_logger

            get_logger("crypto.tpu").warning(
                "device batch fault contained; re-verifying on CPU",
                key=self.KEY_TYPE,
                sigs=total,
                err=repr(fault),
            )
        trace.add_attrs(batch=total, fallback="cpu")
        cpu = cpu_factory(self.KEY_TYPE)
        if cpu is None:  # no CPU fallback registered: surface the fault
            if fault is not None:
                raise fault
            raise RuntimeError(
                f"no CPU batch factory for {self.KEY_TYPE!r}"
            )
        bv = cpu()
        for pub_key, msg, sig in work:
            bv.add(pub_key, msg, sig)
        return bv.verify()

    def __len__(self) -> int:
        return len(self._all)


class TpuEd25519BatchVerifier(_TpuBatchVerifier):
    KEY_TYPE = "ed25519"

    @staticmethod
    def _kernel_module():
        from ..ops import ed25519_kernel

        return ed25519_kernel


class TpuSr25519BatchVerifier(_TpuBatchVerifier):
    """Device sr25519 batch verifier (reference: crypto/sr25519/batch.go
    backed by curve25519-voi; here ops/sr25519_kernel.py — ristretto
    decode + schnorrkel equation on the shared curve core)."""

    KEY_TYPE = "sr25519"

    @staticmethod
    def _kernel_module():
        from ..ops import sr25519_kernel

        return sr25519_kernel


_SHARED_VERIFIER = None
_SHARED_VERIFIER_SR = None
_MIN_BATCH = DEFAULT_MIN_BATCH
_INSTALLED = False
# what the live install was asked for and the breakers it registered:
# an install that asks for the same again changes nothing
_INSTALLED_MESH = None
_INSTALLED_BREAKERS: tuple = ()

# The route breakers (crypto/breaker.py), by name:
#   "ed25519" / "sr25519"     the batch factories + streaming dispatch
#   "sr25519-single"          the per-vote single-verify device route
# The single route's breaker starts OPEN — "cold" and "tripped" are the
# same state: not currently proven. install() arms a probe that
# compiles/verifies the smallest bucket off the critical path and
# closes the breaker, replacing the old _SR_WARM flag; a device fault
# re-opens it with the same never-pile-onto-a-hung-device backoff the
# old trip_sr_singles delay implemented by hand.
_SR_SINGLE = "sr25519-single"

# cached self-signed probe triples, one per key type
_PROBE_TRIPLES: dict = {}


def installed() -> Optional[int]:
    """The currently-installed min_batch threshold, or None if the
    device factory has never been registered. Install state is
    process-global (one device runtime per process); multi-node
    embedders share whichever install ran last."""
    return _MIN_BATCH if _INSTALLED else None


def stats() -> dict:
    """Device-path usage counters — lets the node (and tests) assert the
    batch path actually runs on device in the served configuration."""
    return {
        "batches": int(_m_batches.value()),
        "sigs": int(_m_sigs.value()),
        "faults": int(_m_device_faults.value()),
        "pad_waste": int(_m_pad_waste.value()),
        "warm_misses": int(_m_warm_misses.value()),
        "mesh_devices": int(_m_mesh_devices.value()),
        **heap.stats(),
    }


def _factory(size_hint: int) -> Optional[BatchVerifier]:
    if 0 < size_hint < _MIN_BATCH:
        return None  # CPU fallback for tiny batches
    if not _breaker("ed25519").allow():
        return None  # tripped breaker: CPU, silently
    return TpuEd25519BatchVerifier(_SHARED_VERIFIER)


def _factory_sr(size_hint: int) -> Optional[BatchVerifier]:
    # per-curve threshold: the sr25519 CPU fallback is pure-Python
    # ristretto (~6 ms/sig), so on a real accelerator ANY batch —
    # including a single signature — wins on device; the shared
    # min-batch gate only applies where the CPU path is native-fast
    min_b = 1 if on_accelerator() else _MIN_BATCH
    if 0 < size_hint < min_b:
        return None
    if not _breaker("sr25519").allow():
        return None  # tripped breaker: CPU, silently
    return TpuSr25519BatchVerifier(_SHARED_VERIFIER_SR)


def single_sr_verifier() -> Optional[BatchVerifier]:
    """A device batch verifier for ONE sr25519 signature, or None when
    the device path is not installed / not worthwhile (CPU backend).
    Used by PubKeySr25519.verify_signature so per-vote and evidence
    verifies ride the kernel — through the installed (possibly
    mesh-sharded) verifier and the tpu metrics, same as batches.
    Gated on the single-route breaker: until install()'s probe has
    compiled and proven the smallest sr25519 bucket the breaker stays
    open and singles stay on the CPU path — a vote can never stall
    behind the first XLA compile or pile onto a hung device."""
    if not _INSTALLED:
        return None
    if not sr_single_breaker().allow():
        return None
    return _factory_sr(1)


def sr_single_breaker():
    """The breaker guarding the sr25519 single-verify device route
    (created cold/OPEN if install() has not armed it yet)."""
    return _breaker_mod.breaker_for(_SR_SINGLE, start_open=True)


def _probe_triple(key_type: str) -> tuple:
    cached = _PROBE_TRIPLES.get(key_type)
    if cached is None:
        if key_type == "sr25519":
            from .sr25519 import PrivKeySr25519 as Priv
        else:
            from .ed25519 import PrivKeyEd25519 as Priv
        priv = Priv.from_seed(b"\x77" * 32)
        msg = b"breaker-probe-" + key_type.encode()
        cached = (priv.pub_key().bytes(), msg, priv.sign(msg))
        # tmlint: disable=lock-global-mutation — idempotent memo;
        # racing fills compute byte-identical values
        # tmlive: bounded=keyed by key_type, a fixed two-element set
        # (ed25519/sr25519); one cached probe triple per key type
        _PROBE_TRIPLES[key_type] = cached
    return cached


def _device_probe(key_type: str, backing) -> bool:
    """One self-signed signature end-to-end through the device path,
    with the SAME fault hooks and gather deadline as production
    traffic — so a probe against a still-faulty device fails exactly
    like the traffic it stands in for, and a probe against a healed
    one proves the route. Used single-flight by the breakers; never
    called from consensus threads."""
    pk, msg, sig = _probe_triple(key_type)
    v = backing()
    with trace.span("tpu_probe", key=key_type):
        if faults.armed():
            faults.fire("tpu.dispatch", key=key_type)
        if hasattr(v, "dispatch") and hasattr(v, "gather"):
            handle = v.dispatch([pk], [msg], [sig])
            bits = _gather_guarded(v, handle, key_type)
        else:
            raw = v.verify([pk], [msg], [sig])
            bits = [bool(b) for b in raw]
            if faults.armed():
                bits = faults.mangle("tpu.gather", bits, key=key_type)
        # the install's probe is the first touch of the smallest
        # sr25519 bucket: settle before the verdict closes the breaker,
        # so the route never opens over a heap still to be collected
        heap.settle()
    return len(bits) == 1 and bool(bits[0])


def _ed_backing():
    if _SHARED_VERIFIER is not None:
        return _SHARED_VERIFIER
    from ..ops import ed25519_kernel

    return ed25519_kernel.default_verifier()


def _sr_backing():
    if _SHARED_VERIFIER_SR is not None:
        return _SHARED_VERIFIER_SR
    from ..ops import sr25519_kernel

    return sr25519_kernel.default_verifier()


def _sr_single_probe() -> bool:
    """The single-route warm/re-arm probe: on a CPU process with the
    min-batch gate keeping singles off the kernel there is nothing to
    compile or prove — close immediately (the factory gate returns
    None for singles there anyway). Otherwise one real device verify
    of the smallest sr25519 bucket."""
    if not on_accelerator() and _MIN_BATCH > 1:
        return True
    return _device_probe("sr25519", _sr_backing)


def _on_compile_event(event: str, _duration: float, **_kw) -> None:
    """jax.monitoring listener, registered between install() and
    uninstall(): the event fires once for every program the backend
    compiled or read back from the persistent cache — what a first
    dispatch of a bucket does, and a re-install over programs `jit`
    still holds does not."""
    if event == "/jax/core/compile/backend_compile_duration":
        heap.mark_dirty()


def _same_install(min_batch: int, mesh) -> bool:
    """Whether install(min_batch, mesh) would only rebuild what is
    live: the settings are the live install's and nobody has replaced
    or dropped its breakers since (breaker.reset_all, a test's own
    fresh())."""
    if not _INSTALLED or min_batch != _MIN_BATCH or mesh != _INSTALLED_MESH:
        return False
    return all(
        _breaker_mod.registered(b.name) is b for b in _INSTALLED_BREAKERS
    )


def install(
    min_batch: int = DEFAULT_MIN_BATCH, mesh=None
) -> None:
    """Register the device factories (ed25519 + sr25519). With a mesh,
    ed25519 batches are sharded across it
    (tendermint_tpu.parallel.sharding); otherwise single-chip.

    An install at the live install's settings (the same min_batch over
    the same mesh, its breakers still the registered ones) changes
    nothing: the second Node of a process, a restart in place or a
    localnet, keeps the verifiers, the breakers' states and the warm
    buckets, and starts no probe beside traffic. Any other install is
    a new breaker generation: fresh instances replace
    the registered ones, so a probe still in flight from a superseded
    install publishes into an orphaned object nobody consults — the
    atomicity the old _SR_WARM_GEN counter provided by hand.

    From here to uninstall() the process listens for JAX's compile
    events: a seam call in which a program was traced, compiled or
    loaded ends with one full collection and gc.freeze() (libs/heap.py),
    so the collector never walks the traced programs again."""
    global _SHARED_VERIFIER, _SHARED_VERIFIER_SR, _MIN_BATCH, _INSTALLED
    global _INSTALLED_MESH, _INSTALLED_BREAKERS
    if _same_install(min_batch, mesh):
        return
    if not _INSTALLED:  # an install over an install is already listening
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event
        )
    # tmrace: race-ok — install() runs on the startup/main thread; the
    # only cross-thread readers are breaker probes, and a probe from a
    # superseded generation publishes into an orphaned breaker (see
    # docstring), so a GIL-atomic old-or-new read mid-install is benign
    _MIN_BATCH = min_batch
    _INSTALLED = True  # tmrace: race-ok — same generation protocol
    # warm the native keccak library here (a subprocess cc compile on
    # first use) so the first consensus-critical sr25519 verify never
    # stalls behind a compiler
    from .merlin import _native_lib

    _native_lib()
    if mesh is not None:
        from ..parallel.sharding import (
            ShardedEd25519Verifier,
            ShardedSr25519Verifier,
        )

        new_ed = ShardedEd25519Verifier(mesh)
        new_sr = ShardedSr25519Verifier(mesh)
    else:
        new_ed = None
        new_sr = None
    # tmrace: race-ok — same generation protocol: a stale probe
    # reading the new verifier mid-swap still reports into an
    # orphaned breaker nobody consults
    _SHARED_VERIFIER = new_ed
    _SHARED_VERIFIER_SR = new_sr  # tmrace: race-ok — same protocol
    _m_mesh_devices.set(_mesh_devices(new_ed))
    # new generation: every bucket is cold again
    # tmlint: disable=lock-global-mutation — install() runs on the
    # startup/main thread before traffic
    _WARM_BUCKETS.clear()
    b_ed = _breaker_mod.fresh("ed25519")
    b_ed.set_probe(lambda: _device_probe("ed25519", _ed_backing))
    b_sr = _breaker_mod.fresh("sr25519")
    b_sr.set_probe(lambda: _device_probe("sr25519", _sr_backing))
    b_single = _breaker_mod.fresh(_SR_SINGLE, start_open=True)
    b_single.set_probe(_sr_single_probe)
    # warm the single route off the install path: install() itself must
    # never touch the backend (a device that hangs at backend init
    # would hang node startup); a probe that stalls only delays the
    # device upgrade of single verifies, never a vote
    b_single.probe_now()
    _INSTALLED_MESH = mesh
    _INSTALLED_BREAKERS = (b_ed, b_sr, b_single)
    register_device_factory("ed25519", _factory)
    register_device_factory("sr25519", _factory_sr)
    # merged multi-commit batches (light sequential windows) only pay
    # off on an accelerator ONCE THIS FACTORY IS INSTALLED: _factory
    # serves every >=_MIN_BATCH batch regardless of backend, and on a
    # CPU-backed JAX kernel the bucket padding of a merged window
    # inverts the win (measured 5x slower). Uninstalled processes get
    # batch.native_cpu_affinity's module default instead (the native
    # RLC equation is exact-size, so merging wins there). The decision
    # needs jax.default_backend(), which initializes the backend —
    # deferred to first use so a device that hangs at backend init
    # cannot hang install() itself at node startup.
    from .batch import set_group_affinity_fn

    def _affinity() -> int:
        import jax

        return 32 if jax.default_backend() == "tpu" else 1

    set_group_affinity_fn(_affinity)
    # while span tracing is on, every program span is also a host event
    # of a running jax.profiler capture, on the device's time base
    # (importing jax.profiler initializes no backend; a TraceAnnotation
    # outside a capture costs one atomic load)
    import jax.profiler

    trace.set_mirror(jax.profiler.TraceAnnotation)


def uninstall() -> None:
    """Remove the device factories and reset install state — the
    counterpart of install(), mirroring ops/merkle_kernel.uninstall()
    (tests and embedders switching a node back to the CPU seam). The
    breakers are discarded — an in-flight probe publishes into an
    orphaned object — and the merged-window affinity falls back to the
    module default (batch.native_cpu_affinity) unless an operator
    pinned a value explicitly. The compile listener goes and the heap
    is thawed (gc.unfreeze()): the CPU seam gets the collector's whole
    heap back, and a later install() freezes again at its next compile."""
    global _SHARED_VERIFIER, _SHARED_VERIFIER_SR, _MIN_BATCH, _INSTALLED
    global _INSTALLED_MESH, _INSTALLED_BREAKERS
    from .batch import (
        native_cpu_affinity,
        set_group_affinity_fn,
        unregister_device_factory,
    )

    unregister_device_factory("ed25519")
    unregister_device_factory("sr25519")
    _SHARED_VERIFIER = None
    _SHARED_VERIFIER_SR = None
    # tmlint: disable=lock-global-mutation — uninstall() is a
    # main-thread test/embedder seam, never concurrent with traffic
    _WARM_BUCKETS.clear()
    if _INSTALLED:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(
            _on_compile_event
        )
    _MIN_BATCH = DEFAULT_MIN_BATCH
    _INSTALLED = False
    _INSTALLED_MESH = None
    _INSTALLED_BREAKERS = ()
    _m_mesh_devices.set(0)
    for name in ("ed25519", "sr25519", _SR_SINGLE):
        _breaker_mod.discard(name)
    set_group_affinity_fn(native_cpu_affinity)
    trace.set_mirror(None)
    heap.thaw()
