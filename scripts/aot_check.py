"""Ask the TPU's compiler about the opt-in Pallas programs — no chip.

libtpu ships in this image, so the real TPU compiler (including
Mosaic's jaxpr->vreg pipeline) runs here against a compile-only v5e
topology. This is how the 'Invalid vector register cast' in the bool
Kogge-Stone recode was found and fixed.

Run on CPU only: JAX_PLATFORMS=cpu python scripts/aot_check.py

Checks, each compiled under shard_map over a 4-chip v5e:2x2 mesh
(batch axis sharded — the production layout of parallel/sharding.py):

  hybrid      — verify_hybrid (Pallas dual-mult segment + XLA around)
  sr-hybrid   — _verify_tile_sr with the same Pallas dual-mult
  monolithic  — verify_pallas (whole tile in one kernel)

All three compile on the installed JAX 0.9.0 / libtpu 0.0.34 (about
half a minute each here). They are too slow for tier-1; the default XLA programs at
real widths are compiled by tests/test_chip_compile.py instead. A
compile that passes is not a chip run: none of the three has executed
on hardware.
"""

from __future__ import annotations

import functools
import os
import sys
import time
import traceback

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tendermint_tpu.ops import sr25519_kernel as S
    from tendermint_tpu.ops.ed25519_pallas import (
        dual_mult_pallas,
        verify_hybrid,
        verify_pallas,
    )

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    mesh = topologies.make_mesh(topo, (4,), ("x",))

    failures = 0

    def aot(inner, name, rows):
        nonlocal failures
        fn = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(None, "x"),) * 3,
            out_specs=P("x"),
            check_vma=False,
        )
        args = [
            jax.ShapeDtypeStruct(
                (r, 512), jnp.int32, sharding=NamedSharding(mesh, P(None, "x"))
            )
            for r in rows
        ]
        t0 = time.perf_counter()
        try:
            jax.jit(fn).lower(*args).compile()
            print(f"{name}: OK in {time.perf_counter() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(
                f"{name}: FAILED after {time.perf_counter() - t0:.1f}s",
                flush=True,
            )
            traceback.print_exc(limit=3)

    aot(verify_hybrid, "hybrid", (32, 64, 64))
    aot(
        functools.partial(S._verify_tile_sr, dual_fn=dual_mult_pallas),
        "sr-hybrid",
        (32, 64, 32),
    )
    aot(verify_pallas, "monolithic", (32, 64, 64))
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
