"""Injectable fault plane — seeded, scoped chaos for unreliable edges.

The north star puts consensus-critical crypto on an accelerator, which
makes the dispatch/gather boundary of crypto/tpu_verifier.py a new
Byzantine surface: the XLA runtime can raise, the device can hang,
and a mis-compiled or mis-sharded program can return
wrong-shaped or bit-flipped results. Tendermint tolerates 1/3 Byzantine
validators; this module exists so the test suite can prove the port
tolerates Byzantine *devices* too — the same treat-the-offload-engine-
as-unreliable stance as the FPGA ECDSA engine (arXiv:2112.02229) and
the committee-consensus measurements (arXiv:2302.00418), both of which
keep a mandatory software fallback.

Fault points are NAMED strings consulted at the boundary they model:

    tpu.dispatch   crypto/tpu_verifier.py, before every device launch
    tpu.gather     crypto/tpu_verifier.py, inside the gather barrier
    wal.write      consensus/wal.py, the framed append (short writes)
    wal.fsync      consensus/wal.py, every fsync (rotation included)
    privval.save   privval/file.py, the last-sign-state checkpoint
                   write (io_error = fsync failure, raise = crash
                   before persist), keyed by the node home's basename
    privval.release privval/file.py, between the last-sign-state fsync
                   and the signature leaving the signer — a raise here
                   IS the SIGKILL-between-sign-and-send arc the
                   double-sign invariant is proven across (same key)
    rpc.route      rpc/jsonrpc.py _dispatch, keyed by method name —
                   inside the per-route latency measurement, so an
                   injected hang produces an honest SLO-breach
                   exemplar and an injected raise exercises the
                   error-counting path (loadgen smoke tests)
    p2p.send       p2p/router.py _send_peer, keyed (src, dst, ch) —
                   outbound link faults per asymmetric direction and
                   channel
    p2p.recv       p2p/router.py _recv_peer, keyed (src, dst, ch) —
                   inbound link faults (src = the remote peer)
    p2p.dial       p2p/transport.py dial(), keyed (src, dst) — the
                   connection-establishment boundary

Modes (the fault taxonomy, docs/resilience.md):

    raise       the point raises DeviceFault (an XlaRuntimeError-alike)
    hang        the point sleeps `hang_s` — under the gather deadline
                watchdog this surfaces as DeviceTimeout
    misshape    mangle() drops a result lane (wrong-shaped output)
    bitflip     mangle() inverts one result lane (silent corruption)
    io_error    the point raises OSError (fsync failure)
    short_write clip() truncates the buffer (torn record on crash)

Network modes (consulted via net_plan(), interpreted by the p2p
router/transport — the plane never sleeps the event loop itself):

    drop        the message / dial is discarded (packet loss)
    delay       the caller sleeps `delay_s` before proceeding (latency)
    duplicate   the message is delivered `dup` extra times (gossip echo)
    reorder     the message is held and swapped behind its successor
                (the send side only parks a frame when a successor is
                already queued; a recv-side hold is flushed after
                0.5 s if no successor arrives — so on an idle link
                reorder delays, it never silently drops)

Network rules take extra (src, dst, ch) filters so asymmetric links
and channel-targeted loss are expressible:

    TM_TPU_FAULT="p2p.send:drop:p=0.4:seed=7:src=load0:dst=load1:ch=34"

`src`/`dst` match a node's net labels (moniker, node ID, listen host)
exactly, or as a prefix when the member is >= 8 chars (node-ID
prefixes). On top of per-message rules, named PARTITION SETS cut whole
links: `TM_TPU_PARTITION="load0,load1|load2,load3"` blocks every
send/recv/dial between members of different groups (members in no
group are unaffected). The partition is runtime-mutable —
`set_partition()` in-process, or point TM_TPU_PARTITION_FILE at a
file whose content is re-read on change (throttled stat), so a chaos
scenario can HEAL a partition mid-run, including across process
boundaries (the e2e process-net runner uses the file form).

Every rule owns a `random.Random(seed)`, so whether a given consult
fires is a pure function of (seed, consult index) — chaos runs
reproduce exactly, the same way libs/schedulefuzz.py seeds orderings.
Rules are scoped: the `inject()` context manager removes its rule on
exit, and `TM_TPU_FAULT` arms rules process-wide for black-box runs:

    TM_TPU_FAULT="tpu.dispatch:raise:p=0.3:seed=7;tpu.gather:hang:hang_s=0.5"

The hot path pays one module-global boolean (`armed()`) when the plane
is empty — production traffic never touches a rule list.
"""

from __future__ import annotations

import contextlib
import os
import random
import threading
import time
from typing import List, Optional

__all__ = [
    "DeviceFault",
    "DeviceTimeout",
    "NetPlan",
    "Rule",
    "armed",
    "clip",
    "fire",
    "inject",
    "load_env",
    "mangle",
    "net_armed",
    "net_plan",
    "partition_blocked",
    "partition_spec",
    "reset",
    "rules",
    "set_partition",
]


class DeviceFault(RuntimeError):
    """A device dispatch/gather failed — the XlaRuntimeError-alike the
    fault plane raises, and the type crypto/tpu_verifier.py uses for
    faults it detects itself (mis-shaped results, disproven lanes)."""


class DeviceTimeout(DeviceFault):
    """A gather exceeded its deadline (hung device)."""


_RAISE_MODES = {"raise", "io_error"}
_DATA_MODES = {"misshape", "bitflip"}
_CLIP_MODES = {"short_write"}
_NET_MODES = {"drop", "delay", "duplicate", "reorder"}
_ALL_MODES = (
    _RAISE_MODES | _DATA_MODES | _CLIP_MODES | _NET_MODES | {"hang"}
)


class Rule:
    """One armed fault: a point pattern, a mode, and a seeded RNG that
    decides — reproducibly — which consults fire."""

    def __init__(
        self,
        point: str,
        mode: str,
        p: float = 1.0,
        seed: int = 0,
        times: Optional[int] = None,
        hang_s: float = 30.0,
        key: Optional[str] = None,
        src: Optional[str] = None,
        dst: Optional[str] = None,
        ch: Optional[int] = None,
        delay_s: float = 0.05,
        dup: int = 1,
    ) -> None:
        if mode not in _ALL_MODES:
            raise ValueError(f"unknown fault mode {mode!r}")
        self.point = point
        self.mode = mode
        self.p = float(p)
        self.seed = int(seed)
        self.times = times  # None = unlimited
        self.hang_s = float(hang_s)
        self.key = key  # key-type filter for tpu points (None = any)
        # network filters/knobs (p2p.* points; None = match any)
        self.src = src
        self.dst = dst
        self.ch = int(ch) if ch is not None else None
        self.delay_s = float(delay_s)
        self.dup = int(dup)
        self.rng = random.Random(self.seed)
        self.fired = 0  # consults that actually faulted

    def _matches(self, point: str, key: Optional[str]) -> bool:
        if self.point != point:
            return False
        if self.key is not None and key is not None and self.key != key:
            return False
        return True

    def _matches_net(
        self,
        point: str,
        src_labels: tuple,
        dst_labels: tuple,
        ch: Optional[int],
    ) -> bool:
        if self.point != point:
            return False
        if self.ch is not None and ch is not None and self.ch != ch:
            return False
        if self.src is not None and not _label_match(self.src, src_labels):
            return False
        if self.dst is not None and not _label_match(self.dst, dst_labels):
            return False
        return True

    def _roll(self) -> bool:
        """One seeded decision. The RNG advances on every matching
        consult — fired or not — so the fire pattern depends only on
        (seed, consult index), never on wall time."""
        if self.times is not None and self.fired >= self.times:
            return False
        if self.p < 1.0 and self.rng.random() >= self.p:
            return False
        self.fired += 1
        return True

    def __repr__(self) -> str:  # failure messages name the seed
        return (
            f"Rule({self.point}:{self.mode} p={self.p} seed={self.seed} "
            f"fired={self.fired})"
        )


_HEX_DIGITS = frozenset("0123456789abcdef")


def _label_match(member: str, labels: tuple) -> bool:
    """A spec member names a node if it equals one of the node's net
    labels exactly, or — ONLY when the member looks like a node-ID
    prefix (>= 8 lowercase hex chars) — prefixes one. Monikers and
    hosts match exactly, so "validator1" can never swallow
    "validator10"; node IDs are 40-char hex and an 8+-char prefix is
    unambiguous in any real deployment."""
    id_prefix = len(member) >= 8 and all(c in _HEX_DIGITS for c in member)
    for label in labels:
        if member == label:
            return True
        if id_prefix and label.startswith(member):
            return True
    return False


class NetPlan:
    """The combined verdict of every fired network rule at one consult:
    what the router should do with this message/dial."""

    __slots__ = ("drop", "delay_s", "dup", "reorder")

    def __init__(self) -> None:
        self.drop = False
        self.delay_s = 0.0
        self.dup = 0  # EXTRA copies to deliver
        self.reorder = False

    def __repr__(self) -> str:
        return (
            f"NetPlan(drop={self.drop} delay_s={self.delay_s} "
            f"dup={self.dup} reorder={self.reorder})"
        )


_RULES: List[Rule] = []
_LOCK = threading.Lock()
_ARMED = False  # mirrors bool(_RULES); read lock-free on hot paths
_NET_ARMED = False  # p2p rules or a live/file partition; ditto
_ENV_LOADED = False
# named partition sets: groups of net-label members; links between
# members of DIFFERENT groups are cut, everything else flows.
# tmlive: bounded= replaced wholesale by set_partition (size = the
# operator's parsed spec), never grown incrementally
_PARTITION: List[List[str]] = []
_PARTITION_SPEC = ""
_PARTITION_FILE: Optional[str] = None
_PARTITION_FILE_SIG: Optional[tuple] = None  # (mtime_ns, size)
_PARTITION_NEXT_POLL = 0.0
_PARTITION_POLL_S = 0.2  # stat() throttle for the file form


def armed() -> bool:
    """Cheap hot-path gate: False means no rule is armed and no fault
    code runs at all. The env var is parsed on the first call so test
    processes that set TM_TPU_FAULT after import still arm."""
    if not _ENV_LOADED:
        # load_env sets the latch under _LOCK only AFTER the rules are
        # parsed and _ARMED refreshed: a racing caller either sees the
        # latch down and blocks on _LOCK itself, or sees it up with the
        # armed state already published (tmrace found the old
        # flag-first ordering, where a racer could answer False between
        # the flag write and the parse)
        load_env()
    return _ARMED


def net_armed() -> bool:
    """Cheap hot-path gate for the p2p fault points: False means no
    network rule or partition is live and the router/transport run
    fault-free code only (same contract as armed())."""
    if not _ENV_LOADED:
        load_env()
    return _NET_ARMED


def load_env() -> None:
    """(Re-)parse TM_TPU_FAULT into armed rules (and TM_TPU_PARTITION /
    TM_TPU_PARTITION_FILE into the partition state). Idempotent per
    value: clears previously env-loaded rules first (inject() rules
    survive)."""
    global _ENV_LOADED, _PARTITION_SPEC, _PARTITION_FILE
    global _PARTITION_FILE_SIG, _PARTITION_NEXT_POLL
    spec = os.environ.get("TM_TPU_FAULT", "")
    with _LOCK:
        _RULES[:] = [r for r in _RULES if not getattr(r, "_from_env", False)]
        try:
            # partition env FIRST: a malformed TM_TPU_FAULT must not
            # strip the partition plane as collateral (an e2e child
            # whose partition file silently never armed would measure
            # an un-partitioned net)
            _PARTITION[:] = _parse_partition(
                os.environ.get("TM_TPU_PARTITION", "")
            )
            _PARTITION_SPEC = os.environ.get("TM_TPU_PARTITION", "")
            _PARTITION_FILE = (
                os.environ.get("TM_TPU_PARTITION_FILE") or None
            )
            _PARTITION_FILE_SIG = None
            _PARTITION_NEXT_POLL = 0.0
            parsed = []
            for part in spec.split(";"):
                part = part.strip()
                if not part:
                    continue
                rule = _parse_rule(part)
                rule._from_env = True
                parsed.append(rule)
            _RULES.extend(parsed)
        finally:
            # latch + refresh even when a malformed spec raises: the
            # ValueError surfaces ONCE (from the first armed() call),
            # after which the plane runs disarmed — without this, every
            # hot-path armed() check re-enters the parse and re-raises
            # forever. parsed is appended all-or-nothing so a spec
            # that fails mid-list arms none of its rules.
            _refresh_armed()
            _ENV_LOADED = True


def _parse_rule(spec: str) -> Rule:
    """`point:mode[:p=..][:seed=..][:times=..][:hang_s=..][:key=..]
    [:src=..][:dst=..][:ch=..][:delay_s=..][:dup=..]`"""
    fields = spec.split(":")
    if len(fields) < 2:
        raise ValueError(f"bad TM_TPU_FAULT rule {spec!r} (want point:mode)")
    kwargs = {}
    for opt in fields[2:]:
        if "=" not in opt:
            raise ValueError(f"bad fault option {opt!r} in {spec!r}")
        k, v = opt.split("=", 1)
        if k == "p":
            kwargs["p"] = float(v)
        elif k == "seed":
            kwargs["seed"] = int(v)
        elif k == "times":
            kwargs["times"] = int(v)
        elif k == "hang_s":
            kwargs["hang_s"] = float(v)
        elif k == "key":
            kwargs["key"] = v
        elif k == "src":
            kwargs["src"] = v
        elif k == "dst":
            kwargs["dst"] = v
        elif k == "ch":
            kwargs["ch"] = int(v)
        elif k == "delay_s":
            kwargs["delay_s"] = float(v)
        elif k == "dup":
            kwargs["dup"] = int(v)
        else:
            raise ValueError(f"unknown fault option {k!r} in {spec!r}")
    return Rule(fields[0], fields[1], **kwargs)


def _parse_partition(spec: str) -> List[List[str]]:
    """`"a,b|c,d"` → [[a, b], [c, d]]. Empty spec = no partition."""
    groups: List[List[str]] = []
    for part in spec.split("|"):
        members = [m.strip() for m in part.split(",") if m.strip()]
        if members:
            groups.append(members)
    return groups


def _refresh_armed() -> None:
    global _ARMED, _NET_ARMED
    _ARMED = bool(_RULES)
    _NET_ARMED = (
        bool(_PARTITION)
        or _PARTITION_FILE is not None
        or any(r.point.startswith("p2p.") for r in _RULES)
    )


@contextlib.contextmanager
def inject(
    point: str,
    mode: str,
    p: float = 1.0,
    seed: int = 0,
    times: Optional[int] = None,
    hang_s: float = 30.0,
    key: Optional[str] = None,
    src: Optional[str] = None,
    dst: Optional[str] = None,
    ch: Optional[int] = None,
    delay_s: float = 0.05,
    dup: int = 1,
):
    """Arm one rule for the duration of the scope (chaos tests). Yields
    the Rule so the test can assert how often it actually fired."""
    rule = Rule(point, mode, p=p, seed=seed, times=times,
                hang_s=hang_s, key=key, src=src, dst=dst, ch=ch,
                delay_s=delay_s, dup=dup)
    with _LOCK:
        _RULES.append(rule)
        _refresh_armed()
    try:
        yield rule
    finally:
        with _LOCK:
            try:
                _RULES.remove(rule)
            except ValueError:  # pragma: no cover - double-removal
                pass
            _refresh_armed()


def reset() -> None:
    """Disarm everything — rules AND partition state (tests)."""
    global _PARTITION_SPEC, _PARTITION_FILE, _PARTITION_FILE_SIG
    with _LOCK:
        _RULES.clear()
        _PARTITION.clear()
        _PARTITION_SPEC = ""
        _PARTITION_FILE = None
        _PARTITION_FILE_SIG = None
        _refresh_armed()


def set_partition(spec: str) -> None:
    """Install (or with "" heal) the named partition sets at runtime —
    the in-process half of the runtime-mutable contract; process nets
    mutate via TM_TPU_PARTITION_FILE instead."""
    global _PARTITION_SPEC
    if not _ENV_LOADED:
        # latch the env first or a later lazy load_env() would clobber
        # the runtime spec with the (stale) env value
        load_env()
    groups = _parse_partition(spec)
    with _LOCK:
        _PARTITION[:] = groups
        _PARTITION_SPEC = spec
        _refresh_armed()


def partition_spec() -> str:
    """The currently installed spec (diagnostics/tests)."""
    with _LOCK:
        return _PARTITION_SPEC


def _poll_partition_file_locked() -> None:
    """File form of the runtime-mutable partition: re-read the spec
    when the file changes, stat()ing at most every _PARTITION_POLL_S.
    Callers hold _LOCK."""
    global _PARTITION_FILE_SIG, _PARTITION_NEXT_POLL, _PARTITION_SPEC
    now = time.monotonic()
    if now < _PARTITION_NEXT_POLL:
        return
    _PARTITION_NEXT_POLL = now + _PARTITION_POLL_S
    try:
        st = os.stat(_PARTITION_FILE)
        sig = (st.st_mtime_ns, st.st_size)
        if sig == _PARTITION_FILE_SIG:
            return
        with open(_PARTITION_FILE, "r") as f:
            spec = f.read().strip()
        _PARTITION_FILE_SIG = sig
    except OSError:
        # missing/unreadable file = no partition (a scenario that
        # deletes the file heals the net)
        _PARTITION_FILE_SIG = None
        spec = ""
    _PARTITION[:] = _parse_partition(spec)
    _PARTITION_SPEC = spec


def _group_of(labels: tuple) -> Optional[int]:
    for i, group in enumerate(_PARTITION):
        for member in group:
            if _label_match(member, labels):
                return i
    return None


def partition_blocked(src_labels: tuple, dst_labels: tuple) -> bool:
    """True when the live partition cuts the src→dst link: both
    endpoints are named, in different groups. Callers gate on
    net_armed()."""
    with _LOCK:
        if _PARTITION_FILE is not None:
            _poll_partition_file_locked()
        if not _PARTITION:
            return False
        a = _group_of(src_labels)
        if a is None:
            return False
        b = _group_of(dst_labels)
        return b is not None and a != b


def net_plan(
    point: str,
    src: tuple = (),
    dst: tuple = (),
    ch: Optional[int] = None,
) -> Optional[NetPlan]:
    """Consult the network rules at a p2p fault point. Returns None
    when nothing fired (the common armed-but-filtered case), else the
    combined NetPlan. The plane never sleeps or raises here — the
    router/transport interpret the plan (delay via asyncio.sleep, so
    the event loop is never blocked). Each matching rule's seeded RNG
    advances exactly once per consult, fired or not, so the fault
    schedule is a pure function of (seed, consult index)."""
    plan: Optional[NetPlan] = None
    with _LOCK:
        for r in _RULES:
            if r.mode not in _NET_MODES:
                continue
            if not r._matches_net(point, src, dst, ch):
                continue
            if not r._roll():
                continue
            if plan is None:
                plan = NetPlan()
            if r.mode == "drop":
                plan.drop = True
            elif r.mode == "delay":
                plan.delay_s = max(plan.delay_s, r.delay_s)
            elif r.mode == "duplicate":
                plan.dup += max(r.dup, 0)
            elif r.mode == "reorder":
                plan.reorder = True
    return plan


def rules() -> List[Rule]:
    """Snapshot of the armed rules (diagnostics/tests)."""
    with _LOCK:
        return list(_RULES)


def fire(point: str, key: Optional[str] = None) -> None:
    """Consult the plane at a control-flow fault point. May raise
    (`raise` → DeviceFault, `io_error` → OSError) or stall (`hang`);
    data modes are left to mangle()/clip(). Callers gate on armed()."""
    with _LOCK:
        actions = [
            r for r in _RULES
            if r.mode in ("raise", "hang", "io_error")
            and r._matches(point, key) and r._roll()
        ]
    for r in actions:
        if r.mode == "raise":
            raise DeviceFault(
                f"injected device fault at {point} (seed={r.seed})"
            )
        if r.mode == "io_error":
            raise OSError(
                f"injected I/O fault at {point} (seed={r.seed})"
            )
        if r.mode == "hang":
            # tmlive: block-ok — the injected hang IS the fault under
            # test: it simulates a wedged device/disk so the watchdog,
            # breaker and chaos suites can prove containment; duration
            # is the rule's hang_s, chosen by the test, and the plane
            # is never armed in production (TM_TPU_FAULT unset)
            time.sleep(r.hang_s)


def mangle(point: str, bits: list, key: Optional[str] = None) -> list:
    """Apply data faults to a gather result: `misshape` drops the last
    lane (wrong-shaped device output), `bitflip` inverts one seeded
    lane (silent result corruption). Returns the (possibly) mangled
    bitmap; the containment layer must detect and recover."""
    with _LOCK:
        actions = [
            r for r in _RULES
            if r.mode in _DATA_MODES and r._matches(point, key) and r._roll()
        ]
    for r in actions:
        if r.mode == "misshape" and bits:
            bits = bits[:-1]
        elif r.mode == "bitflip" and bits:
            i = r.rng.randrange(len(bits))
            bits = list(bits)
            bits[i] = not bits[i]
    return bits


def clip(point: str, data: bytes) -> bytes:
    """Apply a `short_write` fault: return a strict seeded prefix of
    `data` — the shape a crash mid-write leaves on disk."""
    with _LOCK:
        actions = [
            r for r in _RULES
            if r.mode in _CLIP_MODES and r._matches(point, None) and r._roll()
        ]
    for r in actions:
        data = data[: r.rng.randrange(len(data))] if data else data
    return data
