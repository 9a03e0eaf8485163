"""Toolchain capability probe for the Mosaic/XLA lowering contracts.

The kernels in this package keep their jaxprs free of primitives
Mosaic cannot lower (scatter, gather, dynamic_slice, rev, rank-1
iota — each found the hard way on hardware). That contract is
enforced by tests/test_ops_pallas.py::test_mosaic_jaxpr_clean, but
the *jaxpr a given jax produces for the same source* is a property of
the tracer, not of this code: a tracer may spell a static slice
written with a zero-width ellipsis (`x[..., :-1, :]` on a rank-2
array — the field25519 carry-pass idiom) as `gather` where another
emits `slice`. The cleanliness check cannot then tell "our code
regressed" from "the tracer spells static slices differently", so it
asks this probe first and records the answer.

`mosaic_probe()` traces a catalog of known-clean constructs (each one
an idiom the kernels actually use, none of which *semantically*
needs a banned primitive) and reports which banned primitives the
installed toolchain introduces for them. The one supported
installation is JAX 0.9.0 with libtpu 0.0.34, where the probe is
clean; a non-empty `introduced` map would mean jaxpr-level
cleanliness checks are meaningless here, and the chip's own compiler
(tests/test_chip_compile.py, scripts/aot_check.py) is the ground
truth either way.

The probe is cheap (<100 ms after jax import), touches no backend
(pure abstract tracing of constant-free functions), and its result
rides in the bench JSON (`mosaic_probe` key).
"""

from __future__ import annotations

from typing import Dict, List

BANNED = (
    "scatter",
    "scatter-add",
    "gather",
    "dynamic_slice",
    "dynamic_update_slice",
    "rev",
)

__all__ = ["BANNED", "banned_prims_of", "mosaic_probe"]


def banned_prims_of(fn, *avals) -> set:
    """The banned-primitive names appearing anywhere in fn's jaxpr
    (sub-jaxprs included), plus rank-1 iota reported as
    'iota(rank-1)'. Shared by the mosaic cleanliness test and the
    probe so both walk the exact same definition of 'clean'."""
    import jax

    seen: set = set()

    def walk(jaxpr):
        for eq in jaxpr.eqns:
            name = eq.primitive.name
            if name in BANNED:
                seen.add(name)
            if name == "iota" and len(eq.outvars[0].aval.shape) == 1:
                seen.add("iota(rank-1)")
            for p in eq.params.values():
                if hasattr(p, "jaxpr"):
                    walk(p.jaxpr)
                elif isinstance(p, (list, tuple)):
                    for q in p:
                        if hasattr(q, "jaxpr"):
                            walk(q.jaxpr)

    walk(jax.make_jaxpr(fn)(*avals).jaxpr)
    return seen


def _clean_constructs():
    """Constructs the kernels rely on that have a banned-free lowering
    (newer jax emits slice/broadcast for every one). Keyed by the
    idiom's name; each value is (fn, avals)."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    r2 = jax.ShapeDtypeStruct((20, 8), i32)
    r3 = jax.ShapeDtypeStruct((4, 20, 8), i32)
    return {
        # field25519._pass: carry fold, ellipsis consumes zero dims
        "ellipsis-static-slice-rank2": (
            lambda x: jnp.concatenate(
                [x[..., -1:, :], x[..., :-1, :]], axis=-2
            ),
            (r2,),
        ),
        # the same slices on a rank-3 stack (edwards point coords)
        "ellipsis-static-slice-rank3": (
            lambda x: jnp.concatenate(
                [x[..., -1:, :], x[..., :-1, :]], axis=-2
            ),
            (r3,),
        ),
        # _onehot_select: broadcasted-iota masked accumulate
        "onehot-masked-select": (
            lambda t, i: jnp.sum(
                t
                * (
                    i[None, :]
                    == jax.lax.broadcasted_iota(i32, (4, 8), 0)
                ).astype(i32)[:, None, :],
                axis=0,
            ),
            (r3, jax.ShapeDtypeStruct((8,), i32)),
        ),
    }


def mosaic_probe() -> Dict[str, object]:
    """Probe the installed toolchain: does tracing known-clean
    constructs introduce Mosaic-banned primitives? Returns
    {"clean": bool, "introduced": {construct: [prims]},
    "jax_version": str}. clean=False means jaxpr-level banned-prim
    checks cannot run on this toolchain (skip, don't fail)."""
    import jax

    introduced: Dict[str, List[str]] = {}
    for name, (fn, avals) in _clean_constructs().items():
        bad = banned_prims_of(fn, *avals)
        if bad:
            introduced[name] = sorted(bad)
    return {
        "clean": not introduced,
        "introduced": introduced,
        "jax_version": jax.__version__,
    }
