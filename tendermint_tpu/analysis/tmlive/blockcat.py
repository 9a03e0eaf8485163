"""Blocking-primitive catalog + site discovery for tmlive.

A serving node dies two ways under load: it *stalls* (a blocking call
on the event loop or under a hot lock) or it *ooms* (a shared
container that only grows). This module owns the first half's ground
truth: a reviewed catalog of blocking primitives, each classified
bounded/unbounded, and an AST pass that finds every call site of one
in the package — resolved through the same from-import/alias machinery
tmcheck's call graph uses, so `from time import sleep as nap` or
`import os as _os; _os.fsync(...)` cannot evade the catalog.

Two resolution shapes:

- **Module-function primitives** (`time.sleep`, `os.fsync`,
  `subprocess.run`, `jax.block_until_ready`, `sys.stdin.readline`,
  `urllib.request.urlopen`, `input`): matched against the *external
  dotted name* the call graph already resolves per call site, which
  folds aliases and from-imports back to canonical names.
- **Method primitives** (`Event.wait`, `Lock.acquire`, `Queue.get`,
  `Thread.join`, `Popen.wait/communicate`, socket verbs, file
  `flush`): matched by method name **plus receiver birth** — the
  receiver must resolve to an object created by a cataloged
  *blocking-class constructor* (`threading.Event()`, `queue.Queue()`,
  `socket.socket()`, `subprocess.Popen(...)`, `open(...)`) as a local
  variable, a `self.<attr>` field (birth sites collected across the
  class, base classes included), or a module global. An `asyncio.Event`
  never matches (its ctor module is asyncio), so the package's
  await-based idiom produces no noise, and an *unresolvable* receiver
  produces NO site — like tmcheck's edges, the catalog is deliberately
  under-approximate and docs/static_analysis.md says so.

`await`-wrapped calls and coroutine constructions are excluded up
front: an awaited `.wait()` parks a task, not the thread.

Boundedness is decided per *call site*, not per primitive: `ev.wait()`
is unbounded, `ev.wait(2.0)` bounded; `lock.acquire()` unbounded,
`lock.acquire(timeout=1)` bounded, `lock.acquire(blocking=False)` not
blocking at all; `subprocess.run(cmd)` unbounded,
`subprocess.run(cmd, timeout=30)` bounded; `time.sleep(0.1)` bounded,
`time.sleep(x)` unbounded (nothing proves x small). `os.fsync` has no
timeout form and is always unbounded — a saturated disk parks the
caller indefinitely, which is exactly the stall class the gate exists
for. Buffered `.flush()` is cataloged but classified bounded: it hands
bytes to the page cache; the durability stall lives in fsync.

The harness prefixes below are excluded from *rule* evaluation (their
sites still land in stats): the e2e process runner deliberately blocks
on subprocess lifecycles — it drives a localnet from a test, it is not
the serving path.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..tmlint import dotted_name as _dotted
from ..tmcheck.callgraph import FuncInfo, ModuleIndex, Package, _body_walk

__all__ = [
    "BOUNDED",
    "UNBOUNDED",
    "NONBLOCKING",
    "HARNESS_PREFIXES",
    "BlockSite",
    "collect_sites",
]

FuncKey = Tuple[str, str]

BOUNDED = "bounded"
UNBOUNDED = "unbounded"
NONBLOCKING = "nonblocking"  # resolved to a cataloged primitive's
# explicitly non-blocking form (acquire(blocking=False), get_nowait)

# package paths whose blocking sites are catalogued but exempt from the
# serving-path rules: the e2e runners orchestrate OS subprocesses from
# a test-driven event loop — blocking on child lifecycles is their job,
# and nothing in them is reachable from a real node's serving path.
HARNESS_PREFIXES = ("e2e/",)


class BlockSite:
    """One blocking-primitive call site."""

    __slots__ = (
        "key", "path", "lineno", "col", "primitive", "kind", "detail"
    )

    def __init__(self, key, path, lineno, col, primitive, kind, detail):
        self.key = key  # enclosing FuncInfo key
        self.path = path
        self.lineno = lineno
        self.col = col
        self.primitive = primitive  # canonical name, e.g. "time.sleep"
        self.kind = kind  # BOUNDED | UNBOUNDED | NONBLOCKING
        self.detail = detail  # why it got that classification

    def render(self) -> str:
        return (
            f"{self.path}:{self.lineno} {self.primitive} "
            f"[{self.kind}] {self.detail}"
        )


# ---------------------------------------------------------------------------
# the module-function catalog (canonical external dotted name -> classifier)


def _has_timeout_kw(call: ast.Call, *names: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg in names:
            return kw.value
    return None


def _is_none(node: Optional[ast.AST]) -> bool:
    return isinstance(node, ast.Constant) and node.value is None


def _classify_sleep(call: ast.Call):
    arg = call.args[0] if call.args else _has_timeout_kw(call, "secs")
    if isinstance(arg, ast.Constant) and isinstance(arg.value, (int, float)):
        return BOUNDED, f"constant {arg.value}s sleep"
    return UNBOUNDED, "sleep duration is not a constant"


def _classify_fsync(call: ast.Call):
    return UNBOUNDED, "fsync has no timeout form; a saturated disk parks the caller"


def _classify_subprocess(call: ast.Call):
    t = _has_timeout_kw(call, "timeout")
    if t is not None and not _is_none(t):
        return BOUNDED, "timeout= passed"
    return UNBOUNDED, "no timeout= on a child-process wait"


def _classify_device_sync(call: ast.Call):
    return (
        UNBOUNDED,
        "device sync point: a hung device parks the caller until the "
        "runtime gives up",
    )


def _classify_stdin(call: ast.Call):
    return UNBOUNDED, "waits for operator/peer input"


def _classify_urlopen(call: ast.Call):
    t = _has_timeout_kw(call, "timeout")
    if t is not None and not _is_none(t):
        return BOUNDED, "timeout= passed"
    return UNBOUNDED, "no timeout= on a synchronous HTTP fetch"


# canonical dotted name -> (classifier, note). The note is the reviewed
# rationale --list-rules/docs surface; classification happens per-site.
MODULE_PRIMITIVES = {
    "time.sleep": _classify_sleep,
    "os.fsync": _classify_fsync,
    "os.fdatasync": _classify_fsync,
    "subprocess.run": _classify_subprocess,
    "subprocess.call": _classify_subprocess,
    "subprocess.check_call": _classify_subprocess,
    "subprocess.check_output": _classify_subprocess,
    "jax.block_until_ready": _classify_device_sync,
    "jax.device_get": _classify_device_sync,
    "sys.stdin.readline": _classify_stdin,
    "sys.stdin.read": _classify_stdin,
    "input": _classify_stdin,
    "urllib.request.urlopen": _classify_urlopen,
    "socket.create_connection": _classify_urlopen,  # same timeout= form
}


# ---------------------------------------------------------------------------
# the method catalog: method name -> (blocking classes, classifier)

_THREADING_WAITABLES = {"Event", "Condition", "Barrier"}
_THREADING_LOCKS = {"Lock", "RLock", "Semaphore", "BoundedSemaphore"}
_QUEUE_CLASSES = {"Queue", "LifoQueue", "PriorityQueue", "SimpleQueue"}
_SOCKET_CLASSES = {"socket"}
_POPEN_CLASSES = {"Popen"}
_THREAD_CLASSES = {"Thread", "Timer"}
_FILE_CLASSES = {"open"}  # open() births; annotations add Buffered*/TextIO*


def _classify_wait(call: ast.Call):
    arg = call.args[0] if call.args else _has_timeout_kw(call, "timeout")
    if arg is not None and not _is_none(arg):
        return BOUNDED, "timeout passed to wait()"
    return UNBOUNDED, "wait() with no timeout"


def _classify_acquire(call: ast.Call):
    blocking = (
        call.args[0] if call.args else _has_timeout_kw(call, "blocking")
    )
    if isinstance(blocking, ast.Constant) and blocking.value is False:
        return NONBLOCKING, "acquire(blocking=False)"
    timeout = (
        call.args[1]
        if len(call.args) >= 2
        else _has_timeout_kw(call, "timeout")
    )
    if timeout is not None and not _is_none(timeout):
        # acquire(timeout=-1) is the unbounded sentinel
        if isinstance(timeout, ast.Constant) and timeout.value in (-1,):
            return UNBOUNDED, "acquire(timeout=-1) blocks forever"
        return BOUNDED, "timeout passed to acquire()"
    return UNBOUNDED, "acquire() with no timeout"


def _classify_queue_get(call: ast.Call):
    return _classify_block_timeout(call, skip_args=0, what="get")


def _classify_queue_put(call: ast.Call):
    # put(item, block=True, timeout=None): the leading item shifts the
    # positional (block, timeout) pair by one vs get()
    return _classify_block_timeout(call, skip_args=1, what="put")


def _classify_block_timeout(call: ast.Call, skip_args: int, what: str):
    pos = call.args[skip_args:]
    block = pos[0] if pos else _has_timeout_kw(call, "block")
    if isinstance(block, ast.Constant) and block.value is False:
        return NONBLOCKING, f"{what}(block=False)"
    timeout = (
        pos[1] if len(pos) >= 2 else _has_timeout_kw(call, "timeout")
    )
    if timeout is not None and not _is_none(timeout):
        return BOUNDED, "timeout passed"
    return UNBOUNDED, "queue wait with no timeout"


def _classify_popen_wait(call: ast.Call):
    # wait(timeout=None): positional or keyword
    t = call.args[0] if call.args else _has_timeout_kw(call, "timeout")
    if t is not None and not _is_none(t):
        return BOUNDED, "timeout passed"
    return UNBOUNDED, "no timeout on a child-process wait"


def _classify_popen_communicate(call: ast.Call):
    # communicate(input=None, timeout=None): timeout is the SECOND
    # positional
    t = (
        call.args[1]
        if len(call.args) >= 2
        else _has_timeout_kw(call, "timeout")
    )
    if t is not None and not _is_none(t):
        return BOUNDED, "timeout passed"
    return UNBOUNDED, "no timeout on a child-process wait"


def _classify_join(call: ast.Call):
    arg = call.args[0] if call.args else _has_timeout_kw(call, "timeout")
    if arg is not None and not _is_none(arg):
        return BOUNDED, "timeout passed to join()"
    return UNBOUNDED, "join() with no timeout"


def _classify_socket_verb(call: ast.Call):
    # settimeout() state is invisible statically: classify unbounded
    # (documented over-approximation on the rare sync-socket path)
    return UNBOUNDED, "synchronous socket op (settimeout state unknowable)"


def _classify_flush(call: ast.Call):
    return BOUNDED, "buffered flush hands bytes to the page cache; the durability stall is fsync's"


def _classify_nonblocking(call: ast.Call):
    return NONBLOCKING, "explicitly non-blocking form"


# method name -> list of (receiver class names, ctor modules, classifier)
METHOD_PRIMITIVES: Dict[str, List[tuple]] = {
    "wait": [
        (_THREADING_WAITABLES, ("threading",), _classify_wait),
        (_POPEN_CLASSES, ("subprocess",), _classify_popen_wait),
    ],
    "acquire": [(_THREADING_LOCKS, ("threading",), _classify_acquire)],
    "get": [(_QUEUE_CLASSES, ("queue",), _classify_queue_get)],
    "put": [(_QUEUE_CLASSES, ("queue",), _classify_queue_put)],
    "get_nowait": [(_QUEUE_CLASSES, ("queue",), _classify_nonblocking)],
    "put_nowait": [(_QUEUE_CLASSES, ("queue",), _classify_nonblocking)],
    "join": [
        (_THREAD_CLASSES, ("threading",), _classify_join),
        (_QUEUE_CLASSES, ("queue",), _classify_join),
    ],
    "communicate": [
        (_POPEN_CLASSES, ("subprocess",), _classify_popen_communicate)
    ],
    "recv": [(_SOCKET_CLASSES, ("socket",), _classify_socket_verb)],
    "recv_into": [(_SOCKET_CLASSES, ("socket",), _classify_socket_verb)],
    "sendall": [(_SOCKET_CLASSES, ("socket",), _classify_socket_verb)],
    "accept": [(_SOCKET_CLASSES, ("socket",), _classify_socket_verb)],
    "connect": [(_SOCKET_CLASSES, ("socket",), _classify_socket_verb)],
    "flush": [(_FILE_CLASSES, ("", "io"), _classify_flush)],
    "block_until_ready": [
        # any receiver: the method name is jax-unique in this codebase
        (None, None, _classify_device_sync),
    ],
}

# annotation type names unambiguous enough to stand in for a birth site
# when no ctor is visible (Optional[subprocess.Popen] fields etc.)
_ANNOTATION_CLASSES = {
    "Popen": _POPEN_CLASSES,
    "Thread": _THREAD_CLASSES,
    "Timer": _THREAD_CLASSES,
    "BufferedWriter": _FILE_CLASSES,
    "BufferedReader": _FILE_CLASSES,
    "TextIOWrapper": _FILE_CLASSES,
}


# ---------------------------------------------------------------------------
# receiver birth resolution


def _ctor_class(mod: ModuleIndex, value: ast.AST) -> Optional[str]:
    """Canonical "<module>.<Class>" for a blocking-class constructor
    call, resolved through this module's import maps; None otherwise.
    `open(...)` births are returned as ".open"."""
    if not isinstance(value, ast.Call):
        return None
    d = _dotted(value.func)
    if not d:
        return None
    parts = d.split(".")
    if len(parts) == 1:
        name = parts[0]
        if name == "open":
            return ".open"
        entry = mod.from_imports.get(name)
        if entry is not None and entry[1] in (
            "threading", "queue", "socket", "subprocess"
        ):
            return f"{entry[1]}.{entry[2]}"
        return None
    head, cls = parts[0], parts[-1]
    target_mod = mod.import_alias.get(head)
    if target_mod in ("threading", "queue", "socket", "subprocess"):
        return f"{target_mod}.{cls}"
    return None


class _Births:
    """Where blocking-class instances are born: module globals,
    instance fields (per owning class, across the whole package so
    base-class fields resolve), and per-function locals."""

    def __init__(self, pkg: Package) -> None:
        self.pkg = pkg
        self.globals: Dict[Tuple[str, str], str] = {}
        self.fields: Dict[Tuple[str, str, str], str] = {}
        for mod in pkg.modules.values():
            for node in mod.tree.body:
                if isinstance(node, (ast.Assign, ast.AnnAssign)):
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    born = _ctor_class(mod, node.value) if node.value else None
                    if born:
                        for t in targets:
                            if isinstance(t, ast.Name):
                                self.globals[(mod.path, t.id)] = born
            for cname, rec in mod.classes.items():
                for m in rec["methods"].values():
                    for node in ast.walk(m):
                        if not isinstance(node, ast.Assign):
                            continue
                        born = _ctor_class(mod, node.value)
                        if not born:
                            continue
                        for t in node.targets:
                            if (
                                isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"
                            ):
                                self.fields[(mod.path, cname, t.attr)] = born
                # unambiguous annotations fill in for invisible births
                for attr, tname in rec["attrs"].items():
                    if attr.startswith("*"):
                        continue
                    classes = _ANNOTATION_CLASSES.get(tname)
                    if classes is None:
                        continue
                    key = (mod.path, cname, attr)
                    if key not in self.fields:
                        mod_name = (
                            "subprocess"
                            if tname == "Popen"
                            else "threading"
                            if tname in _THREAD_CLASSES
                            else ""
                        )
                        self.fields[key] = f"{mod_name}.{tname}" if mod_name else ".open"

    def local_births(self, mod: ModuleIndex, fn: ast.AST) -> Dict[str, str]:
        out: Dict[str, str] = {}
        for node in _body_walk(fn):
            if isinstance(node, ast.Assign):
                born = _ctor_class(mod, node.value)
                if born:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            out[t.id] = born
        return out

    def field_birth(
        self, mod: ModuleIndex, cname: str, attr: str, _depth: int = 0
    ) -> Optional[str]:
        """Birth class of self.<attr> for `cname`, walking bases."""
        if _depth > 4:
            return None
        found = self.pkg.find_class(mod, cname)
        if found is None:
            return self.fields.get((mod.path, cname, attr))
        owner, rec = found
        got = self.fields.get((owner.path, rec["node"].name, attr))
        if got is not None:
            return got
        for base in rec["bases"]:
            got = self.field_birth(
                owner, base.split(".")[-1], attr, _depth + 1
            )
            if got is not None:
                return got
        return None


# ---------------------------------------------------------------------------
# site discovery


def _match_method(
    births: _Births,
    mod: ModuleIndex,
    fi: FuncInfo,
    call: ast.Call,
    local_births: Dict[str, str],
) -> Optional[Tuple[str, tuple]]:
    """(canonical primitive name, classifier) for a method-shaped
    blocking call whose receiver birth resolves; None otherwise."""
    func = call.func
    if not isinstance(func, ast.Attribute):
        return None
    variants = METHOD_PRIMITIVES.get(func.attr)
    if variants is None:
        return None
    recv = func.value
    born: Optional[str] = None
    if isinstance(recv, ast.Name):
        born = local_births.get(recv.id) or births.globals.get(
            (mod.path, recv.id)
        )
    elif (
        isinstance(recv, ast.Attribute)
        and isinstance(recv.value, ast.Name)
        and recv.value.id == "self"
        and fi.class_name
    ):
        born = births.field_birth(mod, fi.class_name, recv.attr)
    for classes, modules, classifier in variants:
        if classes is None:  # receiver-free primitive (block_until_ready)
            return f"*.{func.attr}", classifier
        if born is None:
            continue
        bmod, _, bcls = born.rpartition(".")
        if bcls in classes and (bmod in modules or born == ".open"):
            return f"{born}.{func.attr}", classifier
    return None


def _awaited_positions(fn: ast.AST) -> Set[Tuple[int, int]]:
    """Positions of calls that construct/await coroutines: `await f()`,
    plus calls wrapped in ensure_future/create_task/wait_for (coroutine
    constructions handed to the loop, never executed synchronously)."""
    out: Set[Tuple[int, int]] = set()
    wrappers = {"ensure_future", "create_task", "wait_for", "gather", "shield"}
    for node in _body_walk(fn):
        inner = None
        if isinstance(node, ast.Await):
            inner = node.value
        elif isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d.split(".")[-1] in wrappers:
                for arg in node.args:
                    if isinstance(arg, ast.Call):
                        out.add((arg.lineno, arg.col_offset))
        if isinstance(inner, ast.Call):
            out.add((inner.lineno, inner.col_offset))
    return out


def collect_sites(pkg: Package) -> List[BlockSite]:
    """Every blocking-primitive call site in the package (harness
    prefixes included — rule evaluation filters them, stats keep
    them)."""
    births = _Births(pkg)
    sites: List[BlockSite] = []
    for fi in pkg.functions.values():
        mod = pkg.modules[fi.path]
        awaited = _awaited_positions(fi.node)
        local_births = births.local_births(mod, fi.node)
        ext_by_pos = {
            (c.lineno, c.col): c.external
            for c in fi.calls
            if c.external is not None
        }
        for node in _body_walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            pos = (node.lineno, node.col_offset)
            if pos in awaited:
                continue
            primitive = None
            classifier = None
            ext = ext_by_pos.get(pos)
            if ext is not None and ext in MODULE_PRIMITIVES:
                primitive, classifier = ext, MODULE_PRIMITIVES[ext]
            else:
                got = _match_method(births, mod, fi, node, local_births)
                if got is not None:
                    primitive, classifier = got
            if primitive is None:
                continue
            kind, detail = classifier(node)
            sites.append(
                BlockSite(
                    fi.key, fi.path, node.lineno, node.col_offset,
                    primitive, kind, detail,
                )
            )
    sites.sort(key=lambda s: (s.path, s.lineno, s.col))
    return sites
