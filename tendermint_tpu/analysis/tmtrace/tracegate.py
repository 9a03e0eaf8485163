"""The no-TPU trace-compilation gate.

Every device program must *trace* (build a jaxpr through abstract
evaluation) before it can compile, and every trace failure a device
run would hit is reproducible on CPU with `jax.eval_shape` — no
backend, no chip time spent. This module drives eval_shape over
the shapemodel's concrete (root × bucket) cases and converts
exceptions into `trace-compile-fail` violations, plus the live
bucket-divisibility check (shardcheck) that needs the real sharded
classes importable.

Two tiers (rationale in shapemodel.py):

- default (tier-1, part of the <10 s budget): the fast family —
  sha512 at the min/max buckets, sr25519's merlin challenge at the
  narrowest bucket it runs at and the widest, the merkle inner-hash
  and proof programs — everything that traces in under half a
  second. The heavy crypto tiles are skipped *with their names recorded in
  stats["skipped_heavy"]*, never silently; tier-1's differential
  tests trace them at small shapes anyway.

- full (`scripts/lint.py --trace-full`, bench.py `trace_all_buckets`):
  every declared root × bucket — ~6-8 s of pure tracing per crypto
  tile per bucket, minutes total. This IS the pre-flight of a chip
  run: run it (or read its freshest bench row) first, so chip time
  starts at compilation, not at the first trace error. An optional budget stops the sweep late rather than
  hanging a bench run; whatever was skipped is listed in
  stats["skipped_budget"].

Stats also record jit-cache sizes for the long-lived jitted wrappers
(the per-instance compiled-program dicts plus `_cache_size()` where
the jax version exposes it) — the recompile budget's runtime
counterpart.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..tmlint import Violation
from . import shapemodel, shardcheck

__all__ = ["run", "run_cases", "jit_cache_stats"]


def run_cases(
    cases: Sequence[shapemodel.TraceCase],
    anchors: Optional[Dict[str, Tuple[str, int]]] = None,
    budget_s: Optional[float] = None,
) -> Tuple[List[Violation], dict]:
    """eval_shape every case; exceptions become trace-compile-fail.
    `anchors` maps rid -> (path, lineno) for violation placement."""
    import jax

    anchors = anchors or {}
    violations: List[Violation] = []
    per_case_ms: Dict[str, float] = {}
    skipped_budget: List[str] = []
    t0 = time.monotonic()
    for case in cases:
        if budget_s is not None and time.monotonic() - t0 > budget_s:
            skipped_budget.append(case.label)
            continue
        t1 = time.monotonic()
        try:
            fn, avals = case.build()
            jax.eval_shape(fn, *avals)
        except Exception as e:  # noqa: BLE001 — ANY trace failure is the finding
            path, lineno = anchors.get(case.rid, (case.rid.split(":", 1)[0], 1))
            msg = repr(e)
            if len(msg) > 300:
                msg = msg[:300] + "…"
            violations.append(
                Violation(
                    rule="trace-compile-fail",
                    path=path,
                    line=lineno,
                    col=0,
                    message=(
                        f"jit root `{case.rid}` fails to trace at "
                        f"{case.label}: {msg} — this is the error a "
                        "chip run would hit at its first dispatch; "
                        "fix it on CPU first"
                    ),
                    source="",
                )
            )
        per_case_ms[case.label] = round(
            (time.monotonic() - t1) * 1e3, 1
        )
    stats = {
        "traced": len(per_case_ms),
        "per_case_ms": per_case_ms,
        "skipped_budget": skipped_budget,
        "total_s": round(time.monotonic() - t0, 3),
    }
    return violations, stats


def jit_cache_stats() -> dict:
    """Sizes of the process's long-lived compiled-program caches: the
    modules' shared jitted programs (where this jax exposes
    `_cache_size`). Read-only: constructs no verifier."""
    out: dict = {}
    try:
        from ...ops import ed25519_kernel as K
        from ...ops import sr25519_kernel as SR

        for name, fn in (
            ("ed25519_tile_cache", K.Ed25519Verifier._TILE),
            ("ed25519_sha512_cache", K._SHA512),
            ("sr25519_tile_cache", SR.Sr25519Verifier._TILE),
            ("sr25519_merlin_cache", SR._MERLIN),
        ):
            if hasattr(fn, "_cache_size"):
                out[name] = fn._cache_size()
    except Exception:
        pass
    try:
        from ...ops import merkle_kernel as MK

        if hasattr(MK._inner_jit, "_cache_size"):
            out["merkle_inner_cache"] = MK._inner_jit._cache_size()
        if hasattr(MK._verify_program, "_cache_size"):
            out["merkle_proofs_cache"] = MK._verify_program._cache_size()
    except Exception:
        pass
    return out


def run(
    roots=None,
    full: bool = False,
    budget_s: Optional[float] = None,
    divisibility: bool = True,
) -> Tuple[List[Violation], dict]:
    """The live half of the tmtrace gate: eval_shape cases (fast tier
    or the full root × bucket sweep) + the real-class bucket
    divisibility proof. Returns (violations, stats)."""
    anchors = {}
    for r in roots or ():
        anchors.setdefault(r.rid, (r.path, r.lineno))
    cases = shapemodel.trace_cases(full)
    violations, stats = run_cases(cases, anchors, budget_s)
    stats["tier"] = "full" if full else "fast"
    stats["skipped_heavy"] = (
        []
        if full
        else sorted(
            {
                m.rid
                for m in shapemodel.MODEL.values()
                if m.cost == "heavy" and not m.cases_fn(False)
            }
        )
    )
    if divisibility:
        violations.extend(shardcheck.divisibility_violations())
    stats["jit_cache"] = jit_cache_stats()
    return violations, stats
