"""Lock-discipline rules for the threaded device path.

The TPU crypto path grew real threads (breaker probe timers, gather
watchdog workers, sigcache rotation) on top of the single-writer
asyncio core. Two mechanical hazards follow:

- shared module-level state mutated without its lock is a data race
  the GIL only *mostly* hides (check-then-act sequences interleave);
- a non-daemon worker thread blocks process exit — a wedged gather
  watchdog would hang every node shutdown.

These rules make both visible at lint time; lockwatch (the runtime
half of this subsystem) covers what static analysis can't — actual
acquisition *order* across threads.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional

from .tmlint import Module, Rule, Violation, dotted_name, register

_MUTABLE_CTORS = {
    "list",
    "dict",
    "set",
    "collections.deque",
    "deque",
    "collections.defaultdict",
    "defaultdict",
    "collections.OrderedDict",
    "OrderedDict",
}

_MUTATING_METHODS = {
    "append",
    "extend",
    "insert",
    "remove",
    "pop",
    "popitem",
    "clear",
    "add",
    "discard",
    "update",
    "setdefault",
    "appendleft",
    "popleft",
    "sort",
    "reverse",
}

_LOCK_CTORS = {"threading.Lock", "threading.RLock", "Lock", "RLock"}


def _is_mutable_literal(node: ast.AST) -> bool:
    if isinstance(
        node, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)
    ):
        return True
    if isinstance(node, ast.Call):
        return dotted_name(node.func) in _MUTABLE_CTORS
    return False


def _thread_ctor(mod: Module, node: ast.Call) -> Optional[str]:
    """'Thread'/'Timer' when `node` constructs one, else None."""
    name = dotted_name(node.func)
    if name in ("threading.Thread", "threading.Timer"):
        return name.split(".")[1]
    if name in ("Thread", "Timer") and mod.from_imports.get(name) == "threading":
        return name
    return None


@register
class LockDaemonThread(Rule):
    id = "lock-daemon"
    title = "Thread/Timer without daemon=True"
    rationale = (
        "A non-daemon worker blocks interpreter exit: a breaker probe "
        "timer or gather watchdog parked on a hung device "
        "would hang node shutdown forever. Every background thread in "
        "this codebase must be a daemon (threading.Timer takes no "
        "daemon kwarg — assign `t.daemon = True` before start())."
    )

    def applies(self, mod: Module) -> bool:
        return mod.imports_threading

    def check(self, mod: Module) -> Iterator[Violation]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _thread_ctor(mod, node)
            if kind is None:
                continue
            if any(
                kw.arg == "daemon"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value is True
                for kw in node.keywords
            ):
                continue
            if self._daemon_assigned_later(mod, node):
                continue
            yield self.violation(
                mod,
                node,
                f"threading.{kind} constructed without daemon=True "
                "(and no `<var>.daemon = True` before start()); a "
                "non-daemon worker blocks process exit",
            )

    def _daemon_assigned_later(self, mod: Module, call: ast.Call) -> bool:
        """True when the construction is `t = threading.Timer(...)` (or
        `self.x = ...`) and the enclosing function later assigns
        `t.daemon = True` — the only way to daemonize a Timer."""
        parent = mod.parents.get(call)
        target_name: Optional[str] = None
        target_attr: Optional[str] = None
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
            tgt = parent.targets[0]
            if isinstance(tgt, ast.Name):
                target_name = tgt.id
            elif isinstance(tgt, ast.Attribute):
                target_attr = dotted_name(tgt)
        if target_name is None and target_attr is None:
            return False
        scope = mod.enclosing_function(call) or mod.tree
        for node in ast.walk(scope):
            if not isinstance(node, ast.Assign):
                continue
            if not (
                isinstance(node.value, ast.Constant)
                and node.value.value is True
            ):
                continue
            for tgt in node.targets:
                if not (
                    isinstance(tgt, ast.Attribute) and tgt.attr == "daemon"
                ):
                    continue
                if node.lineno < call.lineno:
                    continue
                base = tgt.value
                if target_name is not None and (
                    isinstance(base, ast.Name) and base.id == target_name
                ):
                    return True
                if target_attr is not None and (
                    dotted_name(base) == target_attr
                ):
                    return True
        return False


@register
class LockGlobalMutation(Rule):
    id = "lock-global-mutation"
    title = "module-level mutable state mutated outside a lock"
    rationale = (
        "In a module that imports threading, module-level "
        "dicts/lists/sets are shared across threads; mutating one "
        "outside a `with <lock>:` block is a data race — GIL "
        "atomicity does not cover check-then-act sequences, and the "
        "reference gates exactly this class of bug with `go test "
        "-race`. Mutations are exempt inside a with-block whose "
        "context mentions a lock, inside functions named `*_locked` "
        "(the held-lock calling convention used across crypto/), and "
        "at module import time (single-threaded)."
    )

    def applies(self, mod: Module) -> bool:
        return mod.imports_threading

    def _module_level_mutables(self, mod: Module) -> set:
        names = set()
        for node in mod.tree.body:
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
                value = node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
                value = node.value
            else:
                continue
            if not _is_mutable_literal(value):
                continue
            for tgt in targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
        return names

    def _guarded(self, mod: Module, node: ast.AST) -> bool:
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.With, ast.AsyncWith)):
                for item in cur.items:
                    ctx = dotted_name(item.context_expr)
                    if not ctx and isinstance(item.context_expr, ast.Call):
                        ctx = dotted_name(item.context_expr.func)
                    if "lock" in ctx.lower():
                        return True
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if cur.name.endswith("_locked"):
                    return True
            cur = mod.parents.get(cur)
        return False

    def check(self, mod: Module) -> Iterator[Violation]:
        shared = self._module_level_mutables(mod)
        if not shared:
            return
        for node in ast.walk(mod.tree):
            name: Optional[str] = None
            what = ""
            if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute
            ):
                if (
                    node.func.attr in _MUTATING_METHODS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in shared
                ):
                    name = node.func.value.id
                    what = f"`{name}.{node.func.attr}()`"
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for tgt in targets:
                    if (
                        isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id in shared
                    ):
                        name = tgt.value.id
                        what = f"`{name}[...] = ...`"
            elif isinstance(node, ast.Delete):
                for tgt in node.targets:
                    if (
                        isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id in shared
                    ):
                        name = tgt.value.id
                        what = f"`del {name}[...]`"
            if name is None:
                continue
            # import-time mutation (module or class body) is
            # single-threaded setup
            if mod.enclosing_function(node) is None:
                continue
            if self._guarded(mod, node):
                continue
            yield self.violation(
                mod,
                node,
                f"module-level mutable `{name}` mutated ({what}) outside "
                "a `with <lock>:` block in a threading module; "
                "check-then-act races are not GIL-atomic",
            )

        # rebinding a module global from a function body (global X; X = ...)
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fn.name.endswith("_locked"):
                continue
            declared = {
                n
                for stmt in ast.walk(fn)
                if isinstance(stmt, ast.Global)
                for n in stmt.names
                if n in shared
            }
            if not declared:
                continue
            for node in ast.walk(fn):
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for tgt in targets:
                        if (
                            isinstance(tgt, ast.Name)
                            and tgt.id in declared
                            and not self._guarded(mod, node)
                        ):
                            yield self.violation(
                                mod,
                                node,
                                f"module-level mutable `{tgt.id}` rebound "
                                "outside a `with <lock>:` block in a "
                                "threading module",
                            )
