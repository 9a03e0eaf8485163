"""Static discovery of every `jax.jit` root in the package.

A *jit root* is a source site that hands a function to `jax.jit`
(directly, via `functools.partial(jax.jit, ...)` as a decorator, or
as a plain `@jax.jit` decorator). Everything tmtrace proves — trace
stability, the signature budget, the no-TPU compile gate — is
quantified over this set, so discovery must be a whole-package AST
scan, not a hand-kept list: a new `jax.jit` anywhere in the package
is discovered on the next run and, lacking a shapemodel entry, fails
the gate as `trace-unknown-root` until its bucket shapes are
declared.

Each root records the jit *target* (resolved to an in-package
function where the receiver is static; `per_chip`-style
dynamic targets keep their source text as identity), the declared
`static_argnames`/`static_argnums`, and any `donate_argnums`/
`donate_argnames` (consumed by shardcheck's donated-reuse rule).

The *traced region* — every in-package function reachable from a jit
target through the PR-5 call graph — is where a `.item()`, a
`float()`, or Python control flow on an abstract value is a trace
error rather than a slowdown; shapeflow runs its interprocedural
tracer-leak pass exactly there.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from ..tmcheck.callgraph import Package

__all__ = [
    "JitRoot",
    "DEVICE_MODULE_FILES",
    "DEVICE_MODULE_PREFIXES",
    "discover",
    "traced_region",
    "is_dispatch_scope",
]

FuncKey = Tuple[str, str]

# The dispatch half of the device scope: tmlint's historical device
# modules (crypto/batch.py, crypto/tpu_verifier.py, parallel/) plus
# ops/ — every module that either packs buckets for, or defines, a
# device program.
DEVICE_MODULE_FILES = {"crypto/batch.py", "crypto/tpu_verifier.py"}
DEVICE_MODULE_PREFIXES = ("parallel/", "ops/")


def is_dispatch_scope(path: str) -> bool:
    return path in DEVICE_MODULE_FILES or path.startswith(
        DEVICE_MODULE_PREFIXES
    )


class JitRoot:
    """One jax.jit site."""

    __slots__ = (
        "path",
        "lineno",
        "target_src",
        "target_key",
        "static_argnames",
        "static_argnums",
        "donate_argnums",
        "donate_argnames",
        "assigned_name",
    )

    def __init__(
        self,
        path: str,
        lineno: int,
        target_src: str,
        target_key: Optional[FuncKey],
        static_argnames: Tuple[str, ...] = (),
        static_argnums: Tuple[int, ...] = (),
        donate_argnums: Tuple[int, ...] = (),
        donate_argnames: Tuple[str, ...] = (),
        assigned_name: str = "",
    ) -> None:
        self.path = path
        self.lineno = lineno
        self.target_src = target_src
        self.target_key = target_key
        self.static_argnames = static_argnames
        self.static_argnums = static_argnums
        self.donate_argnums = donate_argnums
        self.donate_argnames = donate_argnames
        # local/module name the jitted callable is bound to at the
        # site (`fn = jax.jit(...)`) — shardcheck's donated-reuse
        # rule follows calls through it
        self.assigned_name = assigned_name

    @property
    def rid(self) -> str:
        """Stable identity: site module + the target expression's
        source text (line numbers deliberately do not participate)."""
        return f"{self.path}:{self.target_src}"

    def render(self) -> str:
        return f"{self.rid} (line {self.lineno})"


def _const_str_tuple(node: ast.AST) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
        return tuple(out)
    return ()


def _const_int_tuple(node: ast.AST) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return tuple(out)
    return ()


def _is_jax_jit(node: ast.AST, mod) -> bool:
    """`jax.jit` / `jit` (from-imported) / alias thereof."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        base = node.value
        if isinstance(base, ast.Name):
            alias = mod.import_alias.get(base.id, base.id)
            return alias in ("jax", "jax.numpy") or alias.startswith("jax")
        return False
    if isinstance(node, ast.Name):
        fi = mod.from_imports.get(node.id)
        return fi is not None and fi[1] == "jax" and fi[2] == "jit"
    return False


def _resolve_target(
    pkg: Package, mod, node: ast.AST
) -> Tuple[str, Optional[FuncKey]]:
    """(source text, in-package FuncInfo key or None) of a jit-target
    expression. Unwraps one functools.partial layer."""
    if isinstance(node, ast.Call):
        fname = ast.unparse(node.func)
        if fname.endswith("partial") and node.args:
            inner_src, inner_key = _resolve_target(pkg, mod, node.args[0])
            return ast.unparse(node), inner_key
        return ast.unparse(node), None
    src = ast.unparse(node)
    if isinstance(node, ast.Name):
        name = node.id
        if name in mod.functions:
            return src, (mod.path, name)
        fi = mod.from_imports.get(name)
        if fi is not None and fi[0] is not None:
            target = pkg.module_for_dotted(fi[0])
            if target is not None and fi[2] in target.functions:
                return src, (target.path, fi[2])
        return src, None
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
        # S.inner_hash_batch through a module alias / from-import
        head = node.value.id
        target = None
        alias = mod.import_alias.get(head)
        if alias is not None:
            prefix = pkg.pkg_name + "."
            if alias.startswith(prefix):
                target = pkg.module_for_dotted(alias[len(prefix):])
        else:
            fi = mod.from_imports.get(head)
            if fi is not None and fi[0] is not None:
                base = fi[0] + "." + fi[2] if fi[0] else fi[2]
                target = pkg.module_for_dotted(base)
        if target is not None and node.attr in target.functions:
            return src, (target.path, node.attr)
    return src, None


def _root_from_jit_call(
    pkg: Package, mod, call: ast.Call, assigned_name: str = ""
) -> Optional[JitRoot]:
    if not call.args:
        return None
    target_src, target_key = _resolve_target(pkg, mod, call.args[0])
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    return JitRoot(
        mod.path,
        call.lineno,
        target_src,
        target_key,
        static_argnames=_const_str_tuple(kw.get("static_argnames")),
        static_argnums=_const_int_tuple(kw.get("static_argnums")),
        donate_argnums=_const_int_tuple(kw.get("donate_argnums")),
        donate_argnames=_const_str_tuple(kw.get("donate_argnames")),
        assigned_name=assigned_name,
    )


def discover(pkg: Package) -> List[JitRoot]:
    """Every jax.jit site in the package, in (path, lineno) order."""
    roots: List[JitRoot] = []
    for path in sorted(pkg.modules):
        mod = pkg.modules[path]
        # decorators first: @jax.jit and
        # @functools.partial(jax.jit, static_argnames=...)
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_jax_jit(dec, mod):
                        roots.append(
                            JitRoot(
                                path,
                                dec.lineno,
                                node.name,
                                (path, node.name)
                                if (path, node.name) in pkg.functions
                                else None,
                            )
                        )
                    elif (
                        isinstance(dec, ast.Call)
                        and ast.unparse(dec.func).endswith("partial")
                        and dec.args
                        and _is_jax_jit(dec.args[0], mod)
                    ):
                        kw = {
                            k.arg: k.value for k in dec.keywords if k.arg
                        }
                        roots.append(
                            JitRoot(
                                path,
                                dec.lineno,
                                node.name,
                                (path, node.name)
                                if (path, node.name) in pkg.functions
                                else None,
                                static_argnames=_const_str_tuple(
                                    kw.get("static_argnames")
                                ),
                                static_argnums=_const_int_tuple(
                                    kw.get("static_argnums")
                                ),
                                donate_argnums=_const_int_tuple(
                                    kw.get("donate_argnums")
                                ),
                                donate_argnames=_const_str_tuple(
                                    kw.get("donate_argnames")
                                ),
                            )
                        )
            elif isinstance(node, ast.Call) and _is_jax_jit(
                node.func, mod
            ):
                assigned = ""
                # `X = jax.jit(...)`: remember the bound name so the
                # donated-reuse rule can follow calls through it
                parent_assign = None
                # cheap parent scan: jit calls are rare, so a local
                # walk per site beats building parent links
                for cand in ast.walk(mod.tree):
                    if (
                        isinstance(cand, ast.Assign)
                        and cand.value is node
                        and len(cand.targets) == 1
                        and isinstance(cand.targets[0], ast.Name)
                    ):
                        parent_assign = cand.targets[0].id
                        break
                if parent_assign:
                    assigned = parent_assign
                root = _root_from_jit_call(pkg, mod, node, assigned)
                if root is not None:
                    roots.append(root)
    roots.sort(key=lambda r: (r.path, r.lineno))
    return roots


def traced_region(
    pkg: Package, roots: List[JitRoot]
) -> Set[FuncKey]:
    """Every function reachable from a jit target through the call
    graph (witness chains for findings come from the taint pass's own
    parent links, which also carry the tainted-param mask)."""
    region: Set[FuncKey] = set()
    queue: List[FuncKey] = []
    for r in roots:
        if r.target_key is not None and r.target_key in pkg.functions:
            if r.target_key not in region:
                region.add(r.target_key)
                queue.append(r.target_key)
    while queue:
        key = queue.pop()
        for site in pkg.functions[key].calls:
            tgt = site.target
            if tgt is not None and tgt not in region:
                region.add(tgt)
                queue.append(tgt)
    return region
