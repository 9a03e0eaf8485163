"""The declared shape table: every jit root's bucket signatures.

The recompile budget is a *number*: each (jit root, shape, dtype,
static-args) signature XLA has to compile exactly once, and an
accidental new signature is a silent mid-round recompilation storm on
the hot path (dev-shape-leak's rationale, made whole-program). This
module declares, for every discovered jit root, how its input shapes
are generated from the pad-bucket configuration — and enumerates the
resulting signature set from the LIVE config
(`config.DEFAULT_BUCKET_SIZES`), so editing it regenerates a different
set and fails the drift gate until `scripts/lint.py
--signatures-update` re-accepts it.

Three signature families:

- *bucketed*: concrete per-bucket avals (the ed25519/sr25519 tiles,
  sha512 and sr25519's merlin challenge with a symbolic message-length
  dimension `M`),
- *power-of-two*: merkle's `_bucket` (next pow2 ≥ n, min 8) yields an
  unbounded but structured family, recorded symbolically,
- *mesh-sharded*: ops/verifier.py's per-mesh programs (one jit site
  maps whichever shared program it is handed over the chips),
  recorded as the
  round-up formula over the base bucket table (the live divisibility
  gate proves the formula; the underlying body signatures are the
  ed25519/sr25519/sha512 entries).

A discovered root with no entry here is `trace-unknown-root` — the
author of a new `jax.jit` must declare its shape family before the
gate passes, which is exactly the review conversation the rule
exists to force.

Trace cases: each entry also says how to build concrete
(fn, avals) pairs for the no-TPU compile gate. `cost="fast"` cases
(sha256/sha512/merlin/merkle — <0.5 s each) run in the default tier-1 gate;
`cost="heavy"` cases (the crypto tiles, ~6-8 s of tracing EACH) run
only in the full sweep
(`scripts/lint.py --trace-full`, timed by bench.py's
`trace_all_buckets` row as the device-campaign pre-flight cost).
The heavy tiles are still traced on every tier-1 run — by the
differential tests (tests/test_ops_ed25519.py, test_ops_sr25519.py),
which execute them at small shapes — so the default gate skipping
them costs no coverage, only the per-bucket enumeration.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..tmlint import Violation

__all__ = [
    "GOLDEN_PATH",
    "MODEL",
    "REP_MSG_LEN",
    "TraceCase",
    "model_signatures",
    "current_table",
    "drift_violations",
    "load_golden",
    "save_golden",
    "trace_cases",
]

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "jit_signatures.json")

# representative sign-bytes length for sha512's symbolic M dimension:
# a canonical vote for a ~12-byte chain id (the shape the commit path
# hashes all day). Concrete only for the live trace; the golden
# signature keeps M symbolic so chain-id length never fails the gate.
REP_MSG_LEN = 110


def _buckets() -> Tuple[int, ...]:
    from ...config import DEFAULT_BUCKET_SIZES

    return tuple(DEFAULT_BUCKET_SIZES)


def _merlin_buckets() -> Tuple[int, ...]:
    """The buckets the sr25519 verifier launches its merlin program at."""
    from ...config import MERLIN_DEVICE_LANES

    return tuple(b for b in _buckets() if b >= MERLIN_DEVICE_LANES)


class TraceCase:
    """One concrete eval_shape case for the compile gate."""

    __slots__ = ("rid", "label", "cost", "build")

    def __init__(
        self, rid: str, label: str, cost: str, build: Callable
    ) -> None:
        self.rid = rid
        self.label = label
        self.cost = cost
        self.build = build  # () -> (fn, avals tuple)


class RootModel:
    __slots__ = ("rid", "cost", "signatures_fn", "cases_fn")

    def __init__(
        self,
        rid: str,
        cost: str,
        signatures_fn: Callable[[], List[str]],
        cases_fn: Callable[[bool], List[TraceCase]],
    ) -> None:
        self.rid = rid
        self.cost = cost
        self.signatures_fn = signatures_fn
        self.cases_fn = cases_fn


def _avals(*specs):
    import jax
    import jax.numpy as jnp

    dt = {"i32": jnp.int32, "u8": jnp.uint8}
    return tuple(
        jax.ShapeDtypeStruct(shape, dt[d]) for shape, d in specs
    )


# -- per-root case builders (lazy imports keep the static passes
# jax-free until a live trace is actually requested) --


def _ed_tile_case(b: int) -> TraceCase:
    def build():
        from ...ops.ed25519_kernel import _verify_tile

        return _verify_tile, _avals(
            ((32, b), "i32"), ((64, b), "i32"), ((64, b), "i32")
        )

    return TraceCase(
        "ops/ed25519_kernel.py:_verify_tile",
        f"ed25519_tile@{b}",
        "heavy",
        build,
    )


def _sr_tile_case(b: int) -> TraceCase:
    def build():
        from ...ops.sr25519_kernel import _verify_tile_sr

        return _verify_tile_sr, _avals(
            ((32, b), "i32"), ((64, b), "i32"), ((64, b), "i32")
        )

    return TraceCase(
        "ops/sr25519_kernel.py:_verify_tile_sr",
        f"sr25519_tile@{b}",
        "heavy",
        build,
    )


def _sha512_case(b: int, mlen: int) -> TraceCase:
    def build():
        from ...ops.sha512_kernel import sha512_fixed

        return sha512_fixed, _avals(((64 + mlen, b), "u8"))

    return TraceCase(
        "ops/ed25519_kernel.py:sha512_fixed",
        f"sha512@M{mlen}x{b}",
        "fast",
        build,
    )


def _merlin_case(b: int, mlen: int) -> TraceCase:
    def build():
        from ...ops.merlin_kernel import merlin_challenge

        return merlin_challenge, _avals(((mlen + 64, b), "u8"))

    return TraceCase(
        "ops/sr25519_kernel.py:merlin_challenge",
        f"merlin@M{mlen}x{b}",
        "fast",
        build,
    )


def _inner_hash_case(b: int) -> TraceCase:
    def build():
        from ...ops.sha256_kernel import inner_hash_batch

        return inner_hash_batch, _avals(
            ((32, b), "u8"), ((32, b), "u8")
        )

    return TraceCase(
        "ops/merkle_kernel.py:S.inner_hash_batch",
        f"merkle_inner@{b}",
        "fast",
        build,
    )


def _merkle_proof_case(k: int, d: int) -> TraceCase:
    def build():
        from ...ops.merkle_kernel import _verify_program

        return _verify_program, _avals(
            ((32, k), "u8"), ((d, 32, k), "u8"), ((d, k), "i32")
        )

    return TraceCase(
        "ops/merkle_kernel.py:_verify_program",
        f"merkle_proofs@k{k}d{d}",
        "fast",
        build,
    )


def _sig(shapes_dtypes: Sequence[Tuple[str, str]]) -> str:
    return ",".join(f"{d}[{s}]" for s, d in shapes_dtypes)


def _build_model() -> Dict[str, RootModel]:
    model: Dict[str, RootModel] = {}

    def add(rid, cost, sigs, cases):
        model[rid] = RootModel(rid, cost, sigs, cases)

    add(
        "ops/ed25519_kernel.py:_verify_tile",
        "heavy",
        lambda: [
            _sig([(f"32,{b}", "i32"), (f"64,{b}", "i32"), (f"64,{b}", "i32")])
            for b in _buckets()
        ],
        lambda full: [_ed_tile_case(b) for b in _buckets()]
        if full
        else [],
    )
    add(
        "ops/ed25519_kernel.py:sha512_fixed",
        "fast",
        lambda: [
            _sig([(f"64+M,{b}", "u8")]) + " M∈msg-len"
            for b in _buckets()
        ],
        lambda full: [
            _sha512_case(b, REP_MSG_LEN)
            for b in (
                _buckets()
                if full
                else (min(_buckets()), max(_buckets()))
            )
        ],
    )
    add(
        "ops/sr25519_kernel.py:_verify_tile_sr",
        "heavy",
        lambda: [
            _sig([(f"32,{b}", "i32"), (f"64,{b}", "i32"), (f"64,{b}", "i32")])
            for b in _buckets()
        ],
        lambda full: [
            _sr_tile_case(b) for b in _buckets()
        ]
        if full
        else [],
    )
    add(
        "ops/sr25519_kernel.py:merlin_challenge",
        "fast",
        lambda: [
            _sig([(f"M+64,{b}", "u8")]) + " M∈msg-len"
            for b in _merlin_buckets()
        ],
        lambda full: [
            _merlin_case(b, REP_MSG_LEN)
            for b in (
                _merlin_buckets()
                if full
                else (min(_merlin_buckets()), max(_buckets()))
            )
        ],
    )
    add(
        "ops/merkle_kernel.py:S.inner_hash_batch",
        "fast",
        lambda: ["u8[32,2^k],u8[32,2^k] k>=3 (pow2 buckets, min 8)"],
        lambda full: [
            _inner_hash_case(b) for b in ((8, 1024) if full else (8,))
        ],
    )
    add(
        "ops/merkle_kernel.py:_verify_program",
        "fast",
        lambda: [
            "u8[32,2^k],u8[2^d,32,2^k],i32[2^d,2^k] "
            "(pow2 batch and proof depth, min 8)"
        ],
        lambda full: [
            _merkle_proof_case(k, d)
            for k, d in (((8, 8), (64, 16)) if full else ((8, 8),))
        ],
    )
    add(
        "ops/verifier.py:per_chip",
        "heavy",
        lambda: [
            f"sharded(sig axis): base bucket {b} -> "
            "roundup(b, mesh) per mesh size"
            for b in _buckets()
        ]
        + [
            f"sharded(sig axis): 64+M x base bucket {b} -> "
            "roundup(b, mesh) per mesh size M∈msg-len"
            for b in _buckets()
        ],
        # no direct trace: the bodies are the ed25519/sr25519 tile,
        # sha512 and merlin entries (the last two the "64+M" rows),
        # each chip running one on its own shard
        # (shard_map: the fused walk a TPU's tile holds, ops/
        # fused_walk.py, is a kernel whose operands are the tile's
        # lanes and that the compiler cannot partition); mesh
        # placement is proven by the divisibility gate
        lambda full: [],
    )
    return model


MODEL: Dict[str, RootModel] = _build_model()


def model_signatures() -> Dict[str, List[str]]:
    return {rid: m.signatures_fn() for rid, m in MODEL.items()}


def trace_cases(full: bool) -> List[TraceCase]:
    out: List[TraceCase] = []
    for m in MODEL.values():
        out.extend(m.cases_fn(full))
    return out


# ---------------------------------------------------------------------------
# golden table


def load_golden(path: Optional[str] = None) -> Optional[dict]:
    path = path or GOLDEN_PATH
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def current_table(roots) -> dict:
    """The live (root -> signature record) table: discovery provides
    the root set and static/donate declarations, the model provides
    the enumerated signatures."""
    sigs = model_signatures()
    table: Dict[str, dict] = {}
    for r in roots:
        rec = {
            "signatures": sigs.get(r.rid, []),
            "static_argnames": sorted(r.static_argnames),
            "static_argnums": sorted(r.static_argnums),
            "donates": bool(r.donate_argnums or r.donate_argnames),
        }
        table[r.rid] = rec
    return table


def save_golden(roots, path: Optional[str] = None) -> dict:
    path = path or GOLDEN_PATH
    data = {
        "version": 1,
        "generated_by": "scripts/lint.py --signatures-update",
        "note": (
            "Golden jit-signature table: every jax.jit root in the "
            "package and the full (bucket shape, dtype, static-arg) "
            "signature set its pad-bucket family compiles, enumerated "
            "from the live config by analysis/tmtrace/shapemodel.py. "
            "Any drift — a new root, a removed root, a new bucket, a "
            "changed static arg — fails tier-1 until reviewed and "
            "re-accepted with scripts/lint.py --signatures-update. "
            "Do not hand-edit."
        ),
        "roots": {
            rid: rec for rid, rec in sorted(current_table(roots).items())
        },
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=False)
        f.write("\n")
    return data


def drift_violations(
    roots, golden: Optional[dict], pkg=None
) -> List[Violation]:
    """trace-unknown-root (no model entry) + trace-signature-drift
    (current enumeration vs golden)."""
    out: List[Violation] = []
    by_rid = {r.rid: r for r in roots}

    def src_line(r):
        if pkg is None:
            return ""
        lines = pkg.modules[r.path].lines
        return (
            lines[r.lineno - 1].strip() if r.lineno <= len(lines) else ""
        )

    for r in roots:
        if r.rid not in MODEL:
            out.append(
                Violation(
                    rule="trace-unknown-root",
                    path=r.path,
                    line=r.lineno,
                    col=0,
                    message=(
                        f"jax.jit root `{r.target_src}` has no entry "
                        "in analysis/tmtrace/shapemodel.py — declare "
                        "its bucket-shape family (and re-run "
                        "scripts/lint.py --signatures-update) so the "
                        "recompile budget stays enumerable"
                    ),
                    source=src_line(r),
                )
            )
    current = current_table(roots)
    gold_roots = (golden or {}).get("roots", {})
    for rid, rec in sorted(current.items()):
        if rid not in MODEL:
            continue  # already reported as trace-unknown-root
        if rid not in gold_roots:
            r = by_rid[rid]
            out.append(
                Violation(
                    rule="trace-signature-drift",
                    path=r.path,
                    line=r.lineno,
                    col=0,
                    message=(
                        f"jit root `{rid}` is not in the golden "
                        "jit_signatures.json — a new signature family "
                        "(= new compilations on the hot path); review "
                        "and accept with scripts/lint.py "
                        "--signatures-update"
                    ),
                    source=src_line(r),
                )
            )
            continue
        g = gold_roots[rid]
        for field in (
            "signatures",
            "static_argnames",
            "static_argnums",
            "donates",
        ):
            if rec.get(field) != g.get(field):
                r = by_rid[rid]
                out.append(
                    Violation(
                        rule="trace-signature-drift",
                        path=r.path,
                        line=r.lineno,
                        col=0,
                        message=(
                            f"jit root `{rid}`: {field} drifted from "
                            f"the golden table (now {rec.get(field)!r}, "
                            f"golden {g.get(field)!r}) — an accidental "
                            "new bucket/static-arg is a silent "
                            "recompilation on the hot path; review "
                            "and re-accept with scripts/lint.py "
                            "--signatures-update"
                        ),
                        source=src_line(r),
                    )
                )
                break
    for rid in sorted(gold_roots):
        if rid not in current:
            path = rid.split(":", 1)[0]
            out.append(
                Violation(
                    rule="trace-signature-drift",
                    path=path,
                    line=1,
                    col=0,
                    message=(
                        f"golden jit root `{rid}` no longer exists in "
                        "the package — if the program was deliberately "
                        "removed, re-accept with scripts/lint.py "
                        "--signatures-update"
                    ),
                    source="",
                )
            )
    return out
