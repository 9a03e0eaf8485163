"""Trace-stability dataflow: tracer leaks and dynamic shapes.

Three passes over the device scope, each a machine-checked version of
a rule that used to live in review comments (or, for the first two,
in tmlint's per-module scan — folded here so one site is never
reported twice):

1. **trace-tracer-leak** (interprocedural, the widening of tmlint's
   local dev-host-sync): starting from every jit target's array
   parameters, an ARRAY taint is propagated through local dataflow
   and resolved calls across the traced region. Python control flow
   (`if`/`while`/ternary/`assert`) on an ARRAY value, `bool()/int()/
   float()` conversions, `.item()`/`.tolist()`, and `np.asarray`/
   `np.array` on ARRAY values are trace-time errors (ConcretizationError
   or a silent constant-fold) that only detonate when the root is
   finally jitted on a device — exactly what the no-TPU gate
   exists to catch *before* a chip run. Shape reads (`.shape`, `.ndim`,
   `len()` of a traced array) are static during tracing and do not
   taint.

2. **dev-host-sync** (migrated from tmlint, scope unchanged:
   crypto/batch.py, crypto/tpu_verifier.py, parallel/): implicit
   device→host syncs in the *dispatch* layer — `.item()`, `float(x)`,
   np.asarray/np.array — where they serialize the async pipeline.
   The node engine is rules_device.DevHostSync, evaluated here so
   tmlint no longer registers (= never double-reports) it.

3. **dev-shape-leak** (migrated and widened: dispatch modules + ops/):
   jnp shaped constructors whose shape argument is not provably
   drawn from the pad-bucket configuration. The widening is a
   three-valued provenance dataflow (static / unknown / dynamic):
   constants, SCREAMING names, attributes, `.shape` reads and
   arithmetic over them are static; results of the bucketizer family
   (`bucket_for`, `*._bucket`) are static — that is
   the pad-bucket table laundering a dynamic `len(batch)` into a
   compiled shape; `len(...)` is dynamic; function parameters take
   the meet of every resolved call site's argument provenance
   (no resolved callers ⇒ static, under-approximate like the rest of
   the call graph — documented). Anything not provably static is
   flagged, preserving tmlint's strictness while the dataflow keeps
   the legitimate `zeros = padded_len - length - 1 - 8` sites green.

Suppressions: `# tmtrace: trace-ok — why` (same line or comment block
above), plus the legacy `# tmlint: disable=dev-host-sync/dev-shape-leak`
forms for the two migrated rules (existing justified sites keep
working).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..tmlint import Module as LintModule
from ..tmlint import Violation, dotted_name
from ..rules_device import _JNP_SHAPED_CTORS, _NP_TRANSFER, DevHostSync
from ..tmcheck.callgraph import FuncInfo, Package, _body_walk
from .jitroots import JitRoot, is_dispatch_scope

__all__ = [
    "tracer_leak_violations",
    "host_sync_violations",
    "shape_leak_violations",
    "LEGACY_DEVICE_FILES",
    "LEGACY_DEVICE_PREFIXES",
]

FuncKey = Tuple[str, str]

# dev-host-sync keeps tmlint's historical scope: the dispatch layer,
# where a sync is a throughput bug. Inside the traced region the same
# constructs are trace errors and trace-tracer-leak owns them.
LEGACY_DEVICE_FILES = {"crypto/batch.py", "crypto/tpu_verifier.py"}
LEGACY_DEVICE_PREFIXES = ("parallel/",)

_BUCKETIZERS = ("bucket_for",)

# attribute reads on an array that yield trace-static Python values
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "weak_type"}

_CONVERTERS = {"bool", "int", "float"}


def _line(pkg: Package, path: str, lineno: int) -> str:
    lines = pkg.modules[path].lines
    if 1 <= lineno <= len(lines):
        return lines[lineno - 1].strip()
    return ""


# ---------------------------------------------------------------------------
# pass 1: interprocedural ARRAY taint (trace-tracer-leak)


def _array_params(fi: FuncInfo, root: Optional[JitRoot]) -> Set[str]:
    """The parameters of a jit target that carry traced arrays: the
    ones without defaults, minus declared static args. Config flags
    (`with_t=True`) all carry defaults in this codebase —
    a default marks a trace-time constant."""
    args = fi.node.args
    names = [a.arg for a in args.args]
    n_defaults = len(args.defaults)
    positional = names[: len(names) - n_defaults] if n_defaults else names
    out = {n for n in positional if n not in ("self", "cls")}
    if root is not None:
        out -= set(root.static_argnames)
        for i in root.static_argnums:
            if 0 <= i < len(names):
                out.discard(names[i])
    return out


class _TaintPass:
    """One (function, tainted-param-mask) analysis context."""

    def __init__(self, pkg: Package, report: "_Findings") -> None:
        self.pkg = pkg
        self.report = report
        self.done: Set[Tuple[FuncKey, frozenset]] = set()
        self.queue: List[Tuple[FuncKey, frozenset]] = []
        self.parents: Dict[Tuple[FuncKey, frozenset], FuncKey] = {}

    def seed(self, key: FuncKey, params: Iterable[str]) -> None:
        item = (key, frozenset(params))
        if item not in self.done:
            self.done.add(item)
            self.queue.append(item)

    def run(self) -> None:
        while self.queue:
            key, mask = self.queue.pop()
            self._analyze(key, mask)

    # -- per-function analysis --

    def _analyze(self, key: FuncKey, mask: frozenset) -> None:
        fi = self.pkg.functions.get(key)
        if fi is None:
            return
        resolved = {
            (s.lineno, s.col): s.target
            for s in fi.calls
            if s.target is not None
        }
        env: Dict[str, bool] = {n: True for n in mask}

        def flag(node: ast.AST, what: str) -> None:
            self.report.add(
                "trace-tracer-leak",
                fi.path,
                node.lineno,
                f"{what} inside the traced region "
                f"({fi.qualname}, reached from a jax.jit root"
                f"{self._chain_note(key, mask)}) — a trace-time error "
                "on the device path; keep control flow and host "
                "conversions outside jitted bodies "
                "(jnp.where / lax.cond / shape reads are fine)",
                _line(self.pkg, fi.path, node.lineno),
            )

        def tainted(node: ast.AST) -> bool:
            # NO short-circuiting anywhere in here: evaluating a
            # sub-expression is what flags leaks and enqueues
            # interprocedural edges, so every operand must be visited
            # even once the result is known (`x + helper(y)` must
            # still analyze helper when x is already tainted)
            if isinstance(node, ast.Name):
                return env.get(node.id, False)
            if isinstance(node, ast.Constant):
                return False
            if isinstance(node, ast.Attribute):
                # evaluate the receiver FIRST even when the attribute
                # itself is static: `helper(x).shape[0]` must still
                # analyze helper (same no-short-circuit invariant as
                # the operand rules above)
                t = tainted(node.value)
                if node.attr in _STATIC_ATTRS:
                    return False
                return t
            if isinstance(node, ast.Subscript):
                # indexing BY a traced value yields a traced value too
                ts = [tainted(node.value), tainted(node.slice)]
                return any(ts)
            if isinstance(node, ast.BinOp):
                ts = [tainted(node.left), tainted(node.right)]
                return any(ts)
            if isinstance(node, ast.UnaryOp):
                return tainted(node.operand)
            if isinstance(node, ast.Compare):
                ts = [tainted(node.left)] + [
                    tainted(c) for c in node.comparators
                ]
                # identity checks (`x is None`, `prog is _JIT`) test
                # the Python binding, never the abstract value — the
                # `acc = s if acc is None else acc + s` accumulator
                # idiom is trace-safe
                if all(
                    isinstance(op, (ast.Is, ast.IsNot))
                    for op in node.ops
                ):
                    return False
                return any(ts)
            if isinstance(node, ast.BoolOp):
                return any([tainted(v) for v in node.values])
            if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
                return any([tainted(e) for e in node.elts])
            if isinstance(node, ast.Starred):
                return tainted(node.value)
            if isinstance(node, ast.Slice):
                for part in (node.lower, node.upper, node.step):
                    if part is not None:
                        tainted(part)
                return False
            if isinstance(node, ast.IfExp):
                # ternary on a traced value is itself a leak
                if tainted(node.test):
                    flag(node.test, "ternary on a traced value")
                ts = [tainted(node.body), tainted(node.orelse)]
                return any(ts)
            if isinstance(node, ast.Call):
                return self._call(node, tainted, key, mask, resolved)
            return False

        # program-order statement walk: the taint env is built as
        # control flow would (a stack-order ast.walk reads uses before
        # their defs and silently drops every interprocedural edge —
        # found by the propagation-depth test). Loop bodies get TWO
        # passes so loop-carried taint (`state = _compress(state, w)`)
        # converges; findings dedupe by (rule, path, line).
        def do_stmt(st: ast.stmt) -> None:
            if isinstance(
                st, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                return  # nested defs are their own (unreached) nodes
            if isinstance(st, ast.Assign):
                t = tainted(st.value)
                for tgt in st.targets:
                    self._bind(tgt, t, env)
            elif isinstance(st, ast.AugAssign):
                if isinstance(st.target, ast.Name):
                    env[st.target.id] = env.get(
                        st.target.id, False
                    ) or tainted(st.value)
                else:
                    tainted(st.value)
            elif isinstance(st, ast.AnnAssign):
                if st.value is not None:
                    self._bind(st.target, tainted(st.value), env)
            elif isinstance(st, ast.If):
                if tainted(st.test):
                    flag(st.test, "Python branch on a traced value")
                walk(st.body)
                walk(st.orelse)
            elif isinstance(st, ast.While):
                if tainted(st.test):
                    flag(st.test, "Python loop on a traced value")
                walk(st.body)
                walk(st.body)
                walk(st.orelse)
            elif isinstance(st, (ast.For, ast.AsyncFor)):
                self._bind(st.target, tainted(st.iter), env)
                walk(st.body)
                walk(st.body)
                walk(st.orelse)
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    tainted(item.context_expr)
                walk(st.body)
            elif isinstance(st, ast.Try):
                walk(st.body)
                for h in st.handlers:
                    walk(h.body)
                walk(st.orelse)
                walk(st.finalbody)
            elif isinstance(st, ast.Assert):
                if tainted(st.test):
                    flag(st.test, "assert on a traced value")
            elif isinstance(st, ast.Return):
                if st.value is not None:
                    tainted(st.value)
            elif isinstance(st, ast.Expr):
                tainted(st.value)
            elif isinstance(st, ast.Raise):
                if st.exc is not None:
                    tainted(st.exc)

        def walk(stmts) -> None:
            for st in stmts:
                do_stmt(st)

        walk(fi.node.body)

    def _bind(self, tgt: ast.AST, t: bool, env: Dict[str, bool]) -> None:
        if isinstance(tgt, ast.Name):
            env[tgt.id] = t
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for e in tgt.elts:
                self._bind(e, t, env)

    def _chain_note(self, key: FuncKey, mask: frozenset) -> str:
        chain = []
        cur = (key, mask)
        seen = set()
        while cur in self.parents and cur not in seen:
            seen.add(cur)
            parent = self.parents[cur]
            chain.append(parent[1])
            cur = None
            for item in self.done:
                if item[0] == parent:
                    cur = item
                    break
            if cur is None:
                break
        if not chain:
            return ""
        return " via " + " -> ".join(reversed(chain[:4]))

    def _call(
        self,
        node: ast.Call,
        tainted,
        key: FuncKey,
        mask: frozenset,
        resolved: Dict[Tuple[int, int], FuncKey],
    ) -> bool:
        name = dotted_name(node.func)
        arg_taints = [tainted(a) for a in node.args]
        kw_taints = {
            k.arg: tainted(k.value) for k in node.keywords if k.arg
        }
        any_tainted = any(arg_taints) or any(kw_taints.values())
        fi = self.pkg.functions[key]

        def leak(what: str) -> None:
            self.report.add(
                "trace-tracer-leak",
                fi.path,
                node.lineno,
                f"{what} on a traced value inside the traced region "
                f"({fi.qualname}) — concretizes an abstract value at "
                "trace time; gather results on the host side of the "
                "jit boundary instead",
                _line(self.pkg, fi.path, node.lineno),
            )

        if name in _CONVERTERS and any_tainted:
            leak(f"`{name}()`")
            return False
        if name == "len" and any_tainted:
            return False  # len of a traced array is its static dim
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in ("item", "tolist")
            and tainted(node.func.value)
        ):
            leak(f"`.{node.func.attr}()`")
            return False
        if name in _NP_TRANSFER and any_tainted:
            leak(f"`{name}(...)`")
            return False
        # interprocedural step through a resolved in-package call
        target = resolved.get((node.lineno, node.col_offset))
        if target is not None and any_tainted:
            callee = self.pkg.functions.get(target)
            if callee is not None:
                params = [a.arg for a in callee.node.args.args]
                skip_self = bool(params) and params[0] in ("self", "cls")
                if skip_self and isinstance(node.func, ast.Attribute):
                    params = params[1:]
                sub: Set[str] = set()
                for i, t in enumerate(arg_taints):
                    if t and i < len(params):
                        sub.add(params[i])
                for k, t in kw_taints.items():
                    if t and k in params:
                        sub.add(k)
                if sub:
                    item = (target, frozenset(sub))
                    if item not in self.done:
                        self.done.add(item)
                        self.parents[item] = key
                        self.queue.append(item)
        # a receiver-method call on a traced value stays traced
        if isinstance(node.func, ast.Attribute) and tainted(
            node.func.value
        ):
            return True
        return any_tainted


class _Findings:
    def __init__(self) -> None:
        self.seen: Set[Tuple[str, str, int]] = set()
        self.violations: List[Violation] = []

    def add(
        self, rule: str, path: str, lineno: int, message: str, source: str
    ) -> None:
        key = (rule, path, lineno)
        if key in self.seen:
            return
        self.seen.add(key)
        self.violations.append(
            Violation(
                rule=rule,
                path=path,
                line=lineno,
                col=0,
                message=message,
                source=source,
            )
        )


def tracer_leak_violations(
    pkg: Package, roots: List[JitRoot]
) -> List[Violation]:
    """Interprocedural tracer-leak findings over the traced region."""
    report = _Findings()
    tp = _TaintPass(pkg, report)
    for root in roots:
        if root.target_key is None:
            continue
        fi = pkg.functions.get(root.target_key)
        if fi is None:
            continue
        params = _array_params(fi, root)
        if params:
            tp.seed(root.target_key, params)
    tp.run()
    report.violations.sort(key=lambda v: (v.path, v.line))
    return report.violations


# ---------------------------------------------------------------------------
# pass 2: dev-host-sync (migrated from tmlint, legacy dispatch scope)


def host_sync_violations(pkg: Package) -> List[Violation]:
    rule = DevHostSync()
    out: List[Violation] = []
    for path in sorted(pkg.modules):
        if not (
            path in LEGACY_DEVICE_FILES
            or path.startswith(LEGACY_DEVICE_PREFIXES)
        ):
            continue
        mod = LintModule(path, pkg.modules[path].source)
        for v in rule.check(mod):
            if not mod.is_suppressed(v.rule, v.line):
                out.append(v)
    return out


# ---------------------------------------------------------------------------
# pass 3: dev-shape-leak, widened with bucket-provenance dataflow

S, U, D = "static", "unknown", "dynamic"


def _meet(*classes: str) -> str:
    if D in classes:
        return D
    if U in classes:
        return U
    return S


class _Provenance:
    """Three-valued shape provenance over the dispatch scope."""

    def __init__(self, pkg: Package) -> None:
        self.pkg = pkg
        # (path, qualname, param) -> class; top (static) until a
        # resolved call site lowers it
        self.params: Dict[Tuple[str, str, str], str] = {}

    def param_class(self, fi: FuncInfo, name: str) -> str:
        return self.params.get((fi.path, fi.qualname, name), S)

    def classify(
        self, node: ast.AST, ctx: Dict[str, str], fi: Optional[FuncInfo]
    ) -> str:
        if isinstance(node, ast.Constant):
            return S
        if isinstance(node, ast.Name):
            if node.id in ctx:
                return ctx[node.id]
            if fi is not None and node.id in {
                a.arg for a in fi.node.args.args
            }:
                return self.param_class(fi, node.id)
            if node.id == node.id.upper():
                return S
            return U
        if isinstance(node, ast.Attribute):
            return S  # self.BUCKET / cls.SIZE / F.NLIMBS: configuration
        if isinstance(node, ast.Subscript):
            if (
                isinstance(node.value, ast.Attribute)
                and node.value.attr == "shape"
            ):
                return S  # x.shape[i] is concrete during tracing
            return self.classify(node.value, ctx, fi)
        if isinstance(node, (ast.Tuple, ast.List)):
            return _meet(
                *(self.classify(e, ctx, fi) for e in node.elts)
            ) if node.elts else S
        if isinstance(node, ast.BinOp):
            return _meet(
                self.classify(node.left, ctx, fi),
                self.classify(node.right, ctx, fi),
            )
        if isinstance(node, ast.UnaryOp):
            return self.classify(node.operand, ctx, fi)
        if isinstance(node, ast.IfExp):
            return _meet(
                self.classify(node.body, ctx, fi),
                self.classify(node.orelse, ctx, fi),
            )
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            last = name.split(".")[-1] if name else ""
            if last in _BUCKETIZERS or last == "_bucket":
                return S  # the pad-bucket table: dynamic in, bucket out
            if last == "len":
                return D
            if last in ("min", "max", "abs", "sum"):
                return _meet(
                    *(self.classify(a, ctx, fi) for a in node.args)
                ) if node.args else U
            return U
        return U

    def build_ctx(
        self, body: Iterable[ast.stmt], fi: Optional[FuncInfo]
    ) -> Dict[str, str]:
        """One forward pass over a statement list (program order,
        loops not iterated — provenance only ever *lowers*, so a
        single pass is sound for flagging purposes)."""
        ctx: Dict[str, str] = {}

        def bind(tgt: ast.AST, cls: str, value: ast.AST = None) -> None:
            if isinstance(tgt, ast.Name):
                ctx[tgt.id] = cls
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                # `length, n = data.shape` unpacks static dims
                if (
                    value is not None
                    and isinstance(value, ast.Attribute)
                    and value.attr == "shape"
                ):
                    for e in tgt.elts:
                        bind(e, S)
                    return
                elts = (
                    value.elts
                    if isinstance(value, (ast.Tuple, ast.List))
                    and len(value.elts) == len(tgt.elts)
                    else None
                )
                for i, e in enumerate(tgt.elts):
                    if elts is not None:
                        bind(e, self.classify(elts[i], ctx, fi))
                    else:
                        bind(e, cls)

        def walk(stmts) -> None:
            for st in stmts:
                if isinstance(st, ast.Assign):
                    cls = self.classify(st.value, ctx, fi)
                    for tgt in st.targets:
                        bind(tgt, cls, st.value)
                elif isinstance(st, ast.AugAssign) and isinstance(
                    st.target, ast.Name
                ):
                    ctx[st.target.id] = _meet(
                        ctx.get(st.target.id, U),
                        self.classify(st.value, ctx, fi),
                    )
                elif isinstance(st, ast.AnnAssign) and st.value is not None:
                    bind(st.target, self.classify(st.value, ctx, fi))
                elif isinstance(st, (ast.For, ast.AsyncFor)):
                    bind(st.target, U)
                    walk(st.body)
                    walk(st.orelse)
                elif isinstance(st, (ast.If, ast.While)):
                    walk(st.body)
                    walk(st.orelse)
                elif isinstance(st, (ast.With, ast.AsyncWith)):
                    walk(st.body)
                elif isinstance(st, ast.Try):
                    walk(st.body)
                    for h in st.handlers:
                        walk(h.body)
                    walk(st.orelse)
                    walk(st.finalbody)

        walk(list(body))
        return ctx

    def solve_params(self, scope_paths: Set[str]) -> None:
        """Meet every scoped function's param provenance over its
        resolved call sites (3 rounds bound the descending chain
        static > unknown > dynamic)."""
        for _ in range(3):
            changed = False
            for fi in self.pkg.functions.values():
                mod = self.pkg.modules.get(fi.path)
                if mod is None:
                    continue
                resolved = {
                    (s.lineno, s.col): s.target
                    for s in fi.calls
                    if s.target is not None
                }
                ctx = self.build_ctx(fi.node.body, fi)
                for node in _body_walk(fi.node):
                    if not isinstance(node, ast.Call):
                        continue
                    target = resolved.get(
                        (node.lineno, node.col_offset)
                    )
                    if target is None or target[0] not in scope_paths:
                        continue
                    callee = self.pkg.functions.get(target)
                    if callee is None:
                        continue
                    params = [a.arg for a in callee.node.args.args]
                    if params and params[0] in ("self", "cls") and (
                        isinstance(node.func, ast.Attribute)
                    ):
                        params = params[1:]
                    for i, a in enumerate(node.args):
                        if i >= len(params):
                            break
                        cls = self.classify(a, ctx, fi)
                        k = (target[0], target[1], params[i])
                        old = self.params.get(k, S)
                        new = _meet(old, cls)
                        if new != old:
                            self.params[k] = new
                            changed = True
                    for kw in node.keywords:
                        if kw.arg and kw.arg in params:
                            cls = self.classify(kw.value, ctx, fi)
                            k = (target[0], target[1], kw.arg)
                            old = self.params.get(k, S)
                            new = _meet(old, cls)
                            if new != old:
                                self.params[k] = new
                                changed = True
            if not changed:
                break


def shape_leak_violations(pkg: Package) -> List[Violation]:
    """dev-shape-leak over the widened dispatch scope (ops/ included)
    with the bucket-provenance dataflow."""
    scope = {p for p in pkg.modules if is_dispatch_scope(p)}
    prov = _Provenance(pkg)
    prov.solve_params(scope)
    out: List[Violation] = []
    for path in sorted(scope):
        mod = pkg.modules[path]
        lint_mod = LintModule(path, mod.source)
        # per-function sweep (plus module top level via fi=None)
        fns = [
            fi for fi in pkg.functions.values() if fi.path == path
        ]
        units: List[Tuple[Optional[FuncInfo], Iterable[ast.stmt]]] = [
            (fi, fi.node.body) for fi in fns
        ]
        units.append((None, mod.tree.body))
        for fi, body in units:
            ctx = prov.build_ctx(body, fi)
            nodes = (
                _body_walk(fi.node) if fi is not None else _toplevel(mod.tree)
            )
            for node in nodes:
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                if name not in _JNP_SHAPED_CTORS or not node.args:
                    continue
                cls = prov.classify(node.args[0], ctx, fi)
                if cls == S:
                    continue
                if lint_mod.is_suppressed("dev-shape-leak", node.lineno):
                    continue
                out.append(
                    Violation(
                        rule="dev-shape-leak",
                        path=path,
                        line=node.lineno,
                        col=node.col_offset,
                        message=(
                            f"`{name}` called with a {cls}-provenance "
                            f"shape (`{ast.unparse(node.args[0])}`); "
                            "every distinct value compiles a new XLA "
                            "program — derive the shape from the "
                            "pad-bucket table (bucket_for / "
                            "*._bucket) or a "
                            "configured constant"
                        ),
                        source=_line(pkg, path, node.lineno),
                    )
                )
    out.sort(key=lambda v: (v.path, v.line))
    return out


def _toplevel(tree: ast.Module):
    """Module-level statements only (function bodies are their own
    units)."""
    stack: List[ast.AST] = list(tree.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            continue
        stack.extend(ast.iter_child_nodes(node))
