"""Memo-soundness audit — the machine-checked argument behind every
cache in the hot path.

The warm-commit work (PERF.md "Warm path") rests on a family of memos:
commit-scoped sign-bytes rows, BlockIDFlag arrays, validator-set pubkey
bytes, proto wire bytes, merkle roots, and the commit-level
verification memo in crypto/sigcache. Each is sound only if the
memoized function is a PURE function of its inputs — no wall clock, no
RNG, no float arithmetic, no hash-order iteration can reach its body or
anything it calls. That is exactly the taint property tmcheck already
proves for the sign-bytes region; this module re-runs the same
interprocedural source scan with every MEMOIZED function as a root, so
"the memo is sound by construction" is a gate, not a comment.

Two checks:

1. **Catalog completeness** (`memo-uncataloged`): every function that
   both LOADS and STORES a memo-named attribute on the same receiver
   (`self._x_memo`, `self._hash`, `self.__dict__["_sb_memo"]`,
   `getattr(self, "_proto_memo", ...)` and friends) must appear in
   CATALOG below. A new memo cannot ship without declaring its
   soundness class.
2. **Taint cleanliness** (`memo-taint`): every catalog entry of kind
   "consensus" is used as a taint sink root — any nondeterminism
   source reachable from it (same catalogs, suppressions, and witness
   chains as the sign-bytes taint pass) is a violation. Entries of
   kind "identity" produce content-free identity tokens (their only
   output is a fresh `object()`), audited for catalog presence but
   exempt from the float/clock scan by declared justification.

`scripts/lint.py --memo-audit` prints the full listing (function,
memo attributes, declared inputs, taint status) and the full gate runs
both checks on every invocation. docs/static_analysis.md ("Memo
soundness") has the prose argument this module enforces.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..tmlint import Violation
from .callgraph import FuncInfo, Package, _body_walk, build_package
from .taint import _suppressed_lines, function_sources

__all__ = [
    "CATALOG",
    "MemoEntry",
    "audit",
    "discover_memoizers",
    "memo_audit_violations",
    "render_report",
]

# attribute names that hold memoized state but don't contain "memo"
_EXTRA_MEMO_ATTRS = {
    "_hash",
    "_sign_templates",
    "_sb_rows",
    "_sb_complete",
    "_fp_token",
    "_memo_epoch",
}


def _is_memo_attr(name: str) -> bool:
    # private-by-convention only: public attrs named e.g. `memory` are
    # state, not memos — every in-tree memo is underscore-prefixed
    return name.startswith("_") and (
        "memo" in name or name in _EXTRA_MEMO_ATTRS
    )


class MemoEntry:
    """One cataloged memoized function: where it lives, what makes its
    memo sound, and which audit it gets."""

    __slots__ = ("path", "qualname", "kind", "why")

    def __init__(self, path: str, qualname: str, kind: str, why: str):
        assert kind in ("consensus", "identity")
        self.path = path
        self.qualname = qualname
        self.kind = kind
        self.why = why


# The declared memo surface. "consensus": the memoized value feeds
# consensus-critical bytes or accept/reject decisions — must be
# taint-clean transitively. "identity": the function only mints or
# validates identity tokens (fresh object() / epoch pins) whose VALUE
# carries no data; catalog presence is still enforced so the
# invalidation protocol stays reviewed.
CATALOG: List[MemoEntry] = [
    MemoEntry(
        "types/commit.py", "Commit.vote_sign_bytes", "consensus",
        "sign-bytes row per (chain_id, index); inputs frozen after "
        "construction, dropped by the _MUT_EPOCH hook on any mutation",
    ),
    MemoEntry(
        "types/commit.py", "Commit.sign_bytes_batch", "consensus",
        "all sign-bytes rows per chain_id; same epoch invalidation",
    ),
    MemoEntry(
        "types/commit.py", "Commit._rows_for", "consensus",
        "allocator for the shared sign-bytes row lists",
    ),
    MemoEntry(
        "types/commit.py", "Commit._sign_template", "consensus",
        "splice template per (chain_id, for_block)",
    ),
    MemoEntry(
        "types/commit.py", "Commit.block_id_flags_array", "consensus",
        "uint8 BlockIDFlags; drives the vectorized tally masks",
    ),
    MemoEntry(
        "types/commit.py", "Commit.hash", "consensus",
        "merkle root over marshalled CommitSigs",
    ),
    MemoEntry(
        "types/commit.py", "Commit.fingerprint_token", "identity",
        "content-identity object for the commit-level sigcache memo; "
        "the token VALUE is meaningless — only replaced-on-mutation "
        "identity matters",
    ),
    MemoEntry(
        "types/commit.py", "Commit._memos_fresh", "identity",
        "epoch pin/clear checkpoint for every Commit memo",
    ),
    MemoEntry(
        "types/vote.py", "Vote.sign_bytes", "consensus",
        "canonical vote sign-bytes per chain_id; __setattr__ drops the "
        "memo on any encoded-field write",
    ),
    MemoEntry(
        "types/header.py", "Header.hash", "consensus",
        "field-merkle root; every Header field feeds the tree, so "
        "__setattr__ drops the memo on ANY attribute write (the "
        "dataclass __init__ included) — same discipline as "
        "Vote._SB_FIELDS",
    ),
    MemoEntry(
        "types/validator.py", "ValidatorSet.hash", "consensus",
        "merkle root over SimpleValidator leaves; cleared by _reindex",
    ),
    MemoEntry(
        "types/validator.py", "ValidatorSet.to_proto", "consensus",
        "wire bytes validated per call against a full fingerprint of "
        "the mutable inputs (ADVICE r5)",
    ),
    MemoEntry(
        "types/validator.py", "ValidatorSet.pubkeys_bytes", "consensus",
        "raw pubkey encodings for warm cache-key builds; cleared by "
        "_reindex and by the _VAL_MUT_EPOCH hook on in-place pub_key "
        "re-assignment",
    ),
    MemoEntry(
        "types/validator.py", "ValidatorSet.key_classes", "consensus",
        "each validator's key type as a code, and the PubKey objects, "
        "for routing a commit's cache misses to their batch verifiers; "
        "cleared by _reindex and by the _VAL_MUT_EPOCH hook on in-place "
        "pub_key re-assignment, beside pubkeys_bytes",
    ),
    MemoEntry(
        "types/validator.py", "ValidatorSet.powers_array", "consensus",
        "voting powers for the vectorized tallies; cleared by _reindex "
        "and by the _VAL_MUT_EPOCH hook on in-place voting_power "
        "re-assignment, so it can never diverge from the scalar "
        "paths' live reads (ADVICE r5)",
    ),
    MemoEntry(
        "types/validator.py", "ValidatorSet.total_voting_power",
        "consensus",
        "threshold input; recomputed through _update_total_voting_power "
        "on every membership change",
    ),
    MemoEntry(
        "types/validator.py", "ValidatorSet.fingerprint_token",
        "identity",
        "membership-identity object for the commit-level sigcache memo; "
        "powers are fingerprinted separately with live bytes",
    ),
]


def discover_memoizers(
    pkg: Package,
) -> Dict[Tuple[str, str], Set[str]]:
    """(path, qualname) -> memo attribute names, for every function
    that both loads and stores a memo-named attribute on the same
    receiver. Recognized forms per receiver name R (usually `self`):

      store:  R.attr = ... | R.__dict__["attr"] = ...
      load:   R.attr | getattr(R, "attr", ...) | R.__dict__["attr"]
              | R.__dict__.get("attr", ...)

    Store-only functions (invalidators like _reindex, copiers writing a
    DIFFERENT receiver) are deliberately not memoizers."""
    out: Dict[Tuple[str, str], Set[str]] = {}
    for key, fi in pkg.functions.items():
        loads: Set[Tuple[str, str]] = set()
        stores: Set[Tuple[str, str]] = set()
        for node in _body_walk(fi.node):
            recv_attr = _attr_access(node)
            if recv_attr is None:
                continue
            recv, attr, is_store = recv_attr
            if not _is_memo_attr(attr):
                continue
            (stores if is_store else loads).add((recv, attr))
        both = {attr for (recv, attr) in loads if (recv, attr) in stores}
        if both:
            out[key] = both
    return out


def _attr_access(node: ast.AST) -> Optional[Tuple[str, str, bool]]:
    """(receiver name, attribute, is_store) when `node` is one of the
    recognized memo-attribute access forms, else None."""
    # R.attr (plain attribute load/store)
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
        return (
            node.value.id, node.attr, isinstance(node.ctx, ast.Store)
        )
    # R.__dict__["attr"] load/store
    if (
        isinstance(node, ast.Subscript)
        and isinstance(node.value, ast.Attribute)
        and node.value.attr == "__dict__"
        and isinstance(node.value.value, ast.Name)
        and isinstance(node.slice, ast.Constant)
        and isinstance(node.slice.value, str)
    ):
        return (
            node.value.value.id,
            node.slice.value,
            isinstance(node.ctx, ast.Store),
        )
    if isinstance(node, ast.Call):
        # getattr(R, "attr"[, default]) — load
        if (
            isinstance(node.func, ast.Name)
            and node.func.id == "getattr"
            and len(node.args) >= 2
            and isinstance(node.args[0], ast.Name)
            and isinstance(node.args[1], ast.Constant)
            and isinstance(node.args[1].value, str)
        ):
            return (node.args[0].id, node.args[1].value, False)
        # R.__dict__.get("attr"[, default]) — load
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr == "get"
            and isinstance(f.value, ast.Attribute)
            and f.value.attr == "__dict__"
            and isinstance(f.value.value, ast.Name)
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            return (f.value.value.id, node.args[0].value, False)
    return None


class MemoFinding:
    __slots__ = ("rule", "path", "qualname", "lineno", "message", "source")

    def __init__(self, rule, path, qualname, lineno, message, source=""):
        self.rule = rule
        self.path = path
        self.qualname = qualname
        self.lineno = lineno
        self.message = message
        self.source = source


def audit(pkg: Optional[Package] = None):
    """Run both checks. Returns (entries_report, findings) where
    entries_report is a list of dicts (one per catalog entry, with its
    discovered memo attrs, declared inputs, and taint status) for
    --memo-audit's listing, and findings is the violation list."""
    pkg = pkg or build_package()
    findings: List[MemoFinding] = []
    discovered = discover_memoizers(pkg)
    by_name = {(e.path, e.qualname): e for e in CATALOG}

    # 1. completeness: every discovered memoizer is cataloged
    for (path, qualname), attrs in sorted(discovered.items()):
        if (path, qualname) in by_name:
            continue
        fi = pkg.functions[(path, qualname)]
        findings.append(
            MemoFinding(
                "memo-uncataloged", path, qualname, fi.lineno,
                f"{qualname} memoizes {sorted(attrs)} but is not in "
                "tmcheck.memoaudit.CATALOG — declare its soundness "
                "class (consensus/identity) and justification",
            )
        )

    # ... and every cataloged function still exists (renames must not
    # silently drop a function out of the audit)
    report: List[dict] = []
    ok_lines = {
        path: _suppressed_lines(mod.lines, "taint-ok")
        for path, mod in pkg.modules.items()
    }
    break_lines = {
        path: _suppressed_lines(mod.lines, "taint-break")
        for path, mod in pkg.modules.items()
    }
    for entry in CATALOG:
        key = (entry.path, entry.qualname)
        fi = pkg.functions.get(key)
        row = {
            "function": f"{entry.path}:{entry.qualname}",
            "kind": entry.kind,
            "why": entry.why,
            "memo_attrs": sorted(discovered.get(key, ())),
            "inputs": _declared_inputs(fi) if fi is not None else [],
            "taint": "-",
        }
        if fi is None:
            findings.append(
                MemoFinding(
                    "memo-uncataloged", entry.path, entry.qualname, 0,
                    f"cataloged memoized function {entry.qualname} not "
                    f"found in {entry.path} — update the CATALOG after "
                    "renames/moves",
                )
            )
            row["taint"] = "MISSING"
            report.append(row)
            continue
        if entry.kind == "consensus":
            hits = _taint_from(pkg, key, ok_lines, break_lines)
            row["taint"] = "clean" if not hits else "TAINTED"
            for func, hit, chain in hits:
                findings.append(
                    MemoFinding(
                        "memo-taint", func.path, func.qualname,
                        hit.lineno,
                        f"{hit.detail} is reachable from memoized "
                        f"{entry.qualname} via: "
                        + " -> ".join(f.render() for f in chain),
                        _line_at(pkg, func.path, hit.lineno),
                    )
                )
        else:
            row["taint"] = f"exempt ({entry.kind})"
        report.append(row)
    return report, findings


def _declared_inputs(fi: FuncInfo) -> List[str]:
    args = fi.node.args
    names = [a.arg for a in args.posonlyargs + args.args]
    if args.vararg:
        names.append("*" + args.vararg.arg)
    names.extend(a.arg for a in args.kwonlyargs)
    if args.kwarg:
        names.append("**" + args.kwarg.arg)
    return names


def _line_at(pkg: Package, path: str, lineno: int) -> str:
    lines = pkg.modules[path].lines if path in pkg.modules else []
    return lines[lineno - 1].strip() if 1 <= lineno <= len(lines) else ""


def _taint_from(
    pkg: Package,
    root: Tuple[str, str],
    ok_lines: Dict[str, Set[int]],
    break_lines: Dict[str, Set[int]],
):
    """BFS from one memoized root over the call graph (same edge
    semantics and suppressions as taint.analyze), returning
    (function, SourceHit, witness chain) triples."""
    from collections import deque

    parents: Dict[Tuple[str, str], Optional[Tuple[str, str]]] = {root: None}
    queue = deque([root])
    while queue:
        key = queue.popleft()
        fi = pkg.functions[key]
        for site in fi.calls:
            if site.target is None or site.target not in pkg.functions:
                continue
            if site.lineno in break_lines.get(fi.path, ()):
                continue
            if site.target not in parents:
                parents[site.target] = key
                queue.append(site.target)
    out = []
    for key in parents:
        fi = pkg.functions[key]
        hits = function_sources(fi, pkg.modules[fi.path].lines)
        if not hits:
            continue
        chain: List[FuncInfo] = []
        cur: Optional[Tuple[str, str]] = key
        while cur is not None:
            chain.append(pkg.functions[cur])
            cur = parents[cur]
        chain.reverse()
        for hit in hits:
            if hit.lineno in ok_lines.get(fi.path, ()):
                continue
            out.append((fi, hit, chain))
    out.sort(key=lambda t: (t[0].path, t[1].lineno, t[1].rule))
    return out


def findings_to_violations(findings: List[MemoFinding]) -> List[Violation]:
    return [
        Violation(
            rule=f.rule,
            path=f.path,
            line=f.lineno,
            col=0,
            message=f.message,
            source=f.source,
        )
        for f in findings
    ]


def memo_audit_violations(pkg: Optional[Package] = None) -> List[Violation]:
    """Findings as tmlint Violations (fingerprint/baseline machinery
    compatible, though the memo audit ships with ZERO accepted debt —
    there is no baseline file; every finding fails the gate)."""
    pkg = pkg or build_package()
    _report, findings = audit(pkg)
    return findings_to_violations(findings)


def render_report(report: List[dict]) -> str:
    """The --memo-audit listing: every memoized function, its inputs,
    and its audit outcome."""
    lines = ["memoized-function audit (tmcheck.memoaudit.CATALOG):"]
    for row in report:
        lines.append(
            f"  {row['function']}  [{row['kind']}]  taint={row['taint']}"
        )
        if row["memo_attrs"]:
            lines.append(f"      memo attrs: {', '.join(row['memo_attrs'])}")
        if row["inputs"]:
            lines.append(f"      inputs: {', '.join(row['inputs'])}")
        lines.append(f"      why sound: {row['why']}")
    return "\n".join(lines)
