"""tmtrace — whole-program device-dispatch proof.

Chip time is scarce; the dispatch layer is the
code that executes *least* yet carries the north-star number, so a
trace error or recompilation storm discovered on the chip burns the
budget it was meant to measure with. PRs 4-6 machine-proved the consensus side (sign-bytes
taint, wire schemas, races); tmtrace is the same move applied to the
JAX side, on the same substrate (the PR-5 call graph):

1. **Jit-root discovery** (`jitroots.py`): every `jax.jit` site in
   the package, with resolved targets, static args, donations, and
   the *traced region* (functions reachable from jit targets).
2. **Trace-stability dataflow** (`shapeflow.py`): interprocedural
   ARRAY taint flags Python control flow / host conversions on
   abstract values anywhere in the traced region
   (`trace-tracer-leak`, the widening of tmlint's local
   dev-host-sync); the migrated `dev-host-sync` keeps its dispatch
   scope; `dev-shape-leak` is widened to ops/ with a three-valued
   bucket-provenance dataflow so only shapes PROVABLY drawn from the
   pad-bucket table pass.
3. **Recompile-budget gate** (`shapemodel.py`): every root's
   (bucket shape, dtype, static-arg) signature set is enumerated
   from the live config into the golden `jit_signatures.json`;
   drift — a new root, a new bucket, a changed static arg — fails
   tier-1 (`trace-signature-drift` / `trace-unknown-root`).
4. **Sharding consistency** (`shardcheck.py`): PartitionSpec axes
   must exist in a declared Mesh (`trace-mesh-axis`), every bucket
   must divide by every virtual mesh width through the REAL rounding
   code (`trace-bucket-indivisible`), donated buffers must not be
   read after dispatch (`trace-donated-reuse`).
5. **No-TPU compile gate** (`tracegate.py`): `jax.eval_shape` over
   declared root × bucket cases on CPU (`trace-compile-fail`) — the
   fast family in tier-1, the full sweep as the device-campaign
   pre-flight (`scripts/lint.py --trace-full`; its cost is bench.py's
   `trace_all_buckets` row).

Run via `scripts/lint.py --trace` (or the default full gate);
`--signatures-update` regenerates the golden table; suppressions are
`# tmtrace: trace-ok[=rule,...] — why` plus the legacy
`# tmlint: disable=dev-host-sync/dev-shape-leak` forms for the two
migrated rules. tests/test_tmtrace.py holds the tier-1 gates and
seeded-violation fixtures (tests/data/trace/);
docs/static_analysis.md has the catalog and workflow.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Set

from ..tmlint import (
    Violation,
    load_baseline,
    new_violations,
    save_baseline,
)
from ..tmcheck.callgraph import Package, build_package
from . import jitroots, shapeflow, shapemodel, shardcheck, tracegate
from .jitroots import JitRoot, discover
from .shapemodel import GOLDEN_PATH, load_golden, save_golden

__all__ = [
    "RULES",
    "NON_BASELINE_RULES",
    "TRACE_BASELINE_PATH",
    "TRACE_BASELINE_NOTE",
    "GOLDEN_PATH",
    "TraceReport",
    "analyze",
    "trace_violations",
    "new_trace_violations",
    "update_trace_baseline",
    "update_signatures_golden",
]

TRACE_BASELINE_PATH = os.path.join(
    os.path.dirname(__file__), "trace_baseline.json"
)

TRACE_BASELINE_NOTE = (
    "Accepted pre-existing tmtrace findings, fingerprinted by "
    "rule:path:sha1(source_line)[:12]. New findings are anything over "
    "these counts. Do not hand-edit counts to sneak a new finding in "
    "— fix it, or suppress it with a justified '# tmtrace: "
    "trace-ok[=rule] — why' (the migrated dev-host-sync/dev-shape-leak "
    "rules also honor their legacy '# tmlint: disable=<rule>' form). "
    "Signature drift has no baseline: the golden jit_signatures.json "
    "IS the accepted state (scripts/lint.py --signatures-update)."
)

# the tmtrace rule catalog (mirrored by --list-rules and the docs)
RULES = [
    (
        "trace-tracer-leak",
        "Python control flow or host conversion on a traced value "
        "inside the jit-reachable region (interprocedural)",
    ),
    (
        "dev-host-sync",
        "implicit device→host sync in the dispatch layer (migrated "
        "from tmlint, scope unchanged)",
    ),
    (
        "dev-shape-leak",
        "jnp shaped constructor whose shape is not provably drawn "
        "from the pad-bucket table (migrated from tmlint, widened to "
        "ops/ with bucket-provenance dataflow)",
    ),
    (
        "trace-unknown-root",
        "jax.jit root with no declared shape family in the shapemodel",
    ),
    (
        "trace-signature-drift",
        "enumerated (root, bucket shape, dtype, static-arg) signature "
        "set differs from the golden jit_signatures.json",
    ),
    (
        "trace-mesh-axis",
        "PartitionSpec axis name not declared by any Mesh",
    ),
    (
        "trace-bucket-indivisible",
        "a sharded verifier bucket does not divide by a virtual mesh "
        "width (proven against the real rounding code)",
    ),
    (
        "trace-donated-reuse",
        "buffer read after being donated to a jit program",
    ),
    (
        "trace-compile-fail",
        "a declared jit root × bucket fails jax.eval_shape on CPU",
    ),
]

# Rules whose accepted state is the golden jit_signatures.json (or a
# fixed trace), NOT the counted baseline: letting a routine
# --baseline-update fingerprint these would silently accept a
# recompile-budget change or an untraceable root without the reviewed
# --signatures-update path ever running — the same laundering class
# the PR-5 "--schema --baseline-update refused" fix closed.
NON_BASELINE_RULES = frozenset(
    {"trace-signature-drift", "trace-unknown-root", "trace-compile-fail"}
)


def split_baselineable(violations):
    """(baselineable, golden_gated): the second list can never be
    absorbed by a counted baseline."""
    base = [v for v in violations if v.rule not in NON_BASELINE_RULES]
    gated = [v for v in violations if v.rule in NON_BASELINE_RULES]
    return base, gated


_TRACE_OK_RE = re.compile(
    r"#\s*tmtrace:\s*trace-ok(?:=([A-Za-z0-9_\-, ]+))?"
)


def suppression_map(lines: List[str]) -> Dict[int, Set[str]]:
    """lineno -> suppressed rule ids ({'all'} for a bare trace-ok).
    Same two forms as tmlint: on the offending line, or in a comment
    block directly above it."""
    out: Dict[int, Set[str]] = {}
    for i, text in enumerate(lines, start=1):
        m = _TRACE_OK_RE.search(text)
        if not m:
            continue
        rules = (
            {r.strip() for r in m.group(1).split(",") if r.strip()}
            if m.group(1)
            else {"all"}
        )
        out.setdefault(i, set()).update(rules)
        if text.lstrip().startswith("#"):
            j = i + 1
            while j <= len(lines) and (
                not lines[j - 1].strip()
                or lines[j - 1].lstrip().startswith("#")
            ):
                j += 1
            if j <= len(lines):
                out.setdefault(j, set()).update(rules)
    return out


class TraceReport:
    def __init__(self) -> None:
        self.roots: List[JitRoot] = []
        self.traced_region: Set = set()
        self.stats: dict = {}
        self.violations: List[Violation] = []


def analyze(
    pkg: Optional[Package] = None,
    golden_path: Optional[str] = None,
    signatures: bool = True,
    live: bool = True,
    full: bool = False,
    live_budget_s: Optional[float] = None,
) -> TraceReport:
    pkg = pkg or build_package()
    report = TraceReport()
    roots = discover(pkg)
    report.roots = roots
    report.traced_region = jitroots.traced_region(pkg, roots)

    violations: List[Violation] = []
    violations.extend(shapeflow.tracer_leak_violations(pkg, roots))
    violations.extend(shapeflow.host_sync_violations(pkg))
    violations.extend(shapeflow.shape_leak_violations(pkg))
    violations.extend(shardcheck.mesh_axis_violations(pkg))
    violations.extend(shardcheck.donated_reuse_violations(pkg, roots))
    # the signature enumeration and the live tier need jax importable
    # (bucket tables come from the live config);
    # on a jax-less box the nine static passes above still gate —
    # degrade these two to a RECORDED skip, never an exit-2 crash
    if signatures:
        try:
            violations.extend(
                shapemodel.drift_violations(
                    roots, load_golden(golden_path), pkg
                )
            )
        except ImportError as e:
            report.stats["signatures"] = f"skipped: {e}"
    if live:
        try:
            live_v, stats = tracegate.run(
                roots, full=full, budget_s=live_budget_s
            )
        except ImportError as e:
            report.stats["live_tier"] = f"skipped: {e}"
        else:
            violations.extend(live_v)
            report.stats.update(stats)

    # -- suppressions: # tmtrace: trace-ok[=rule] (any rule) --
    maps: Dict[str, Dict[int, Set[str]]] = {}
    kept: List[Violation] = []
    for v in violations:
        mod = pkg.modules.get(v.path)
        if mod is not None:
            if v.path not in maps:
                maps[v.path] = suppression_map(mod.lines)
            rules = maps[v.path].get(v.line)
            if rules and ("all" in rules or v.rule in rules):
                continue
        kept.append(v)
    kept.sort(key=lambda v: (v.path, v.line, v.rule))
    report.violations = kept
    return report


def trace_violations(
    pkg: Optional[Package] = None, **kwargs
) -> List[Violation]:
    return analyze(pkg, **kwargs).violations


def new_trace_violations(
    pkg: Optional[Package] = None,
    baseline_path: Optional[str] = None,
    **kwargs,
) -> List[Violation]:
    """tmtrace findings beyond the checked-in baseline (same counted
    fingerprint semantics as tmlint/tmcheck/tmrace). Golden-gated
    rules (NON_BASELINE_RULES) are ALWAYS new — their accepted state
    lives in jit_signatures.json, not the baseline."""
    violations = trace_violations(pkg, **kwargs)
    base, gated = split_baselineable(violations)
    baseline = load_baseline(baseline_path or TRACE_BASELINE_PATH)
    out = new_violations(base, baseline) + gated
    out.sort(key=lambda v: (v.path, v.line, v.rule))
    return out


def update_trace_baseline(
    pkg: Optional[Package] = None,
    baseline_path: Optional[str] = None,
    **kwargs,
) -> Dict[str, int]:
    """Accept the current DATAFLOW findings; golden-gated rules are
    never written (use --signatures-update for those)."""
    base, _gated = split_baselineable(trace_violations(pkg, **kwargs))
    return save_baseline(
        base,
        baseline_path or TRACE_BASELINE_PATH,
        note=TRACE_BASELINE_NOTE,
    )


def update_signatures_golden(
    pkg: Optional[Package] = None, path: Optional[str] = None
) -> dict:
    pkg = pkg or build_package()
    return save_golden(discover(pkg), path)
