"""Sharding-consistency checks: mesh axes, bucket divisibility,
donated buffers.

The sharded dispatch path (parallel/sharding.py) partitions the
bucketed device programs over a 1-D `sig` mesh. Three properties die
silently if an edit breaks them, and each only detonates on the
first multi-chip run — so they are gates here:

- **trace-mesh-axis** (static): every axis name appearing in a
  `PartitionSpec(...)` must be declared by some `Mesh(..., (<axes>,))`
  in the package. An undeclared axis raises at dispatch time on the
  first sharded call — i.e. on the chips. Axis names are resolved
  through module-level string constants (`SIG_AXIS = "sig"`, also
  where another module of the package from-imports it), the import
  aliases `P`/`PartitionSpec`, and constant tuples.

- **trace-bucket-indivisible** (live, run by tracegate): for every
  virtual mesh size 1..8, the *real* sharded verifier classes are
  instantiated against a duck-typed mesh and every bucket they would
  dispatch must divide by the mesh size, and a chip's share of it
  must be one tile of the fused window walk or whole tiles
  (`LANE_TILE`: the kernel's grid has no ragged last step, and the CPU
  suite, which traces the scan, would never see one) — the properties
  `BucketedVerifier.__init__`/`_bucket` (ops/verifier.py) guarantee, checked
  against the production rounding code rather than a re-derived
  formula, so a refactor that drops the round-up turns the gate red.

- **trace-donated-reuse** (static): a buffer donated to a jit program
  (`donate_argnums`/`donate_argnames`) is invalidated by dispatch;
  any later read of the same name in the enclosing scope is a
  use-after-donate that XLA only reports (as a cryptic
  "buffer donated" error) on the device. No in-tree site donates
  today; the rule exists so the first one that does is born checked
  (seeded fixture in tests/data/trace/).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..tmlint import Violation, dotted_name
from ..tmcheck.callgraph import Package
from .jitroots import JitRoot

__all__ = [
    "mesh_axis_violations",
    "donated_reuse_violations",
    "divisibility_violations",
    "MESH_SIZES",
]

# virtual mesh widths the divisibility gate proves (SHARD_SCALING.json
# measured divide-by-n to 8 virtual devices; 3 catches non-power-of-2)
MESH_SIZES = (1, 2, 3, 4, 8)


def _str_const(
    node: ast.AST, consts: Dict[str, str]
) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _module_str_consts(tree: ast.Module) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
        ):
            out[node.targets[0].id] = node.value.value
    return out


def _pspec_names(mod) -> Set[str]:
    """Local names bound to jax.sharding.PartitionSpec (incl. the
    conventional `as P`)."""
    names = set()
    for local, (tgt, ext, orig) in mod.from_imports.items():
        if ext is not None and "sharding" in ext and orig == "PartitionSpec":
            names.add(local)
    return names


def mesh_axis_violations(pkg: Package) -> List[Violation]:
    """Every PartitionSpec axis must exist in a declared Mesh."""
    declared: Set[str] = set()
    uses: List[Tuple[str, int, str]] = []  # (path, lineno, axis)
    own = {p: _module_str_consts(m.tree) for p, m in pkg.modules.items()}
    for path in sorted(pkg.modules):
        mod = pkg.modules[path]
        # the module's own constants, and those it from-imports from
        # another module of the package (`from ..ops.verifier import
        # SIG_AXIS`)
        consts = dict(own[path])
        for local, (tgt, _ext, orig) in mod.from_imports.items():
            src = pkg.module_for_dotted(tgt) if tgt is not None else None
            if src is not None and orig in own[src.path]:
                consts[local] = own[src.path][orig]
        pspec_locals = _pspec_names(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            last = name.split(".")[-1] if name else ""
            if last == "Mesh":
                axes_node = None
                if len(node.args) >= 2:
                    axes_node = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        axes_node = kw.value
                if axes_node is not None:
                    if isinstance(axes_node, (ast.Tuple, ast.List)):
                        for e in axes_node.elts:
                            s = _str_const(e, consts)
                            if s:
                                declared.add(s)
                    else:
                        s = _str_const(axes_node, consts)
                        if s:
                            declared.add(s)
            elif (
                (isinstance(node.func, ast.Name) and last in pspec_locals)
                or name
                in ("jax.sharding.PartitionSpec", "sharding.PartitionSpec")
            ):
                for e in node.args:
                    s = _str_const(e, consts)
                    if s is not None:
                        uses.append((path, node.lineno, s))
    out: List[Violation] = []
    for path, lineno, axis in uses:
        if axis in declared:
            continue
        lines = pkg.modules[path].lines
        src = lines[lineno - 1].strip() if lineno <= len(lines) else ""
        out.append(
            Violation(
                rule="trace-mesh-axis",
                path=path,
                line=lineno,
                col=0,
                message=(
                    f"PartitionSpec axis '{axis}' is not declared by "
                    f"any Mesh in the package (declared: "
                    f"{sorted(declared) or 'none'}) — dispatch would "
                    "raise on the first sharded call, i.e. on the chips"
                ),
                source=src,
            )
        )
    return out


def donated_reuse_violations(
    pkg: Package, roots: List[JitRoot]
) -> List[Violation]:
    """Reads of a donated buffer after the dispatch that consumed it."""
    out: List[Violation] = []
    donating = {
        (r.path, r.assigned_name): r
        for r in roots
        if r.assigned_name and (r.donate_argnums or r.donate_argnames)
    }
    if not donating:
        return out
    for fi in pkg.functions.values():
        root_names = {
            name: r
            for (p, name), r in donating.items()
            if p == fi.path
        }
        if not root_names:
            continue
        # find calls through the donating jitted name; map donated
        # positions/names to plain-Name args; flag later loads
        donated: List[Tuple[str, int, JitRoot]] = []  # (var, call line)
        for node in ast.walk(fi.node):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in root_names
            ):
                r = root_names[node.func.id]
                for i in r.donate_argnums:
                    if i < len(node.args) and isinstance(
                        node.args[i], ast.Name
                    ):
                        donated.append(
                            (node.args[i].id, node.lineno, r)
                        )
                for kw in node.keywords:
                    if (
                        kw.arg in r.donate_argnames
                        and isinstance(kw.value, ast.Name)
                    ):
                        donated.append((kw.value.id, node.lineno, r))
        for var, call_line, r in donated:
            for node in ast.walk(fi.node):
                if (
                    isinstance(node, ast.Name)
                    and node.id == var
                    and isinstance(node.ctx, ast.Load)
                    and node.lineno > call_line
                ):
                    lines = pkg.modules[fi.path].lines
                    src = (
                        lines[node.lineno - 1].strip()
                        if node.lineno <= len(lines)
                        else ""
                    )
                    out.append(
                        Violation(
                            rule="trace-donated-reuse",
                            path=fi.path,
                            line=node.lineno,
                            col=node.col_offset,
                            message=(
                                f"`{var}` was donated to "
                                f"`{r.assigned_name}` (line {call_line}, "
                                f"{r.rid}) and its buffer is invalid "
                                "after dispatch; copy before donating "
                                "or drop the donation"
                            ),
                            source=src,
                        )
                    )
                    break
    out.sort(key=lambda v: (v.path, v.line))
    return out


def divisibility_violations(
    sharded_classes: Optional[Sequence] = None,
    mesh_sizes: Sequence[int] = MESH_SIZES,
    probe_sizes: Sequence[int] = (1, 5, 100, 9000, 20000),
) -> List[Violation]:
    """Instantiate each sharded verifier against duck meshes of every
    virtual width and prove every bucket it would dispatch divides by
    the mesh, into shares the fused walk tiles — exercising the REAL
    ops/verifier.py rounding code, not a re-derivation of it. Needs jax
    importable (tracegate runs it)."""
    import numpy as np

    from ...ops.verifier import LANE_TILE

    if sharded_classes is None:
        from ...parallel import sharding as sh

        sharded_classes = (
            sh.ShardedEd25519Verifier,
            sh.ShardedSr25519Verifier,
        )

    class _DuckMesh:
        def __init__(self, n: int) -> None:
            self.devices = np.empty((n,), dtype=object)

    out: List[Violation] = []
    for cls in sharded_classes:
        for n in mesh_sizes:
            try:
                v = cls(_DuckMesh(n))
            except Exception as e:
                out.append(
                    Violation(
                        rule="trace-bucket-indivisible",
                        path="parallel/sharding.py",
                        line=1,
                        col=0,
                        message=(
                            f"{cls.__name__} failed to instantiate "
                            f"against a {n}-device mesh: {e!r}"
                        ),
                        source="",
                    )
                )
                continue
            buckets = set(v.bucket_sizes) | {
                v._bucket(m) for m in probe_sizes
            }
            bad = [b for b in buckets if b % n]
            ragged = [
                b
                for b in buckets
                if not b % n and b // n > LANE_TILE and b // n % LANE_TILE
            ]
            if ragged:
                out.append(
                    Violation(
                        rule="trace-bucket-indivisible",
                        path="parallel/sharding.py",
                        line=1,
                        col=0,
                        message=(
                            f"{cls.__name__} on a {n}-device mesh "
                            f"produces bucket(s) {sorted(ragged)} whose "
                            f"share a chip is above {LANE_TILE} lanes "
                            f"and no multiple of it — a TPU's fused "
                            "walk (ops/fused_walk.py) refuses the width "
                            "when the program is traced"
                        ),
                        source="",
                    )
                )
            if bad:
                out.append(
                    Violation(
                        rule="trace-bucket-indivisible",
                        path="parallel/sharding.py",
                        line=1,
                        col=0,
                        message=(
                            f"{cls.__name__} on a {n}-device mesh "
                            f"produces bucket(s) {sorted(set(bad))} "
                            f"not divisible by {n} — XLA would pad "
                            "unevenly or reject the sharding at "
                            "dispatch time"
                        ),
                        source="",
                    )
                )
    return out
