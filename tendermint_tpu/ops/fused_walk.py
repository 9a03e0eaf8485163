"""The dual scalar multiplication `[S]B - [k]A` as one Pallas TPU kernel.

The scan program of ops/ed25519_kernel.py expresses a window of the
walk as thousands of separate HLO ops: XLA fuses the elementwise chains,
but every pad, concatenate and reduce materializes an intermediate, and
the scan body goes to HBM and back many times a window. Here the *same*
arithmetic (`ed25519_kernel.dual_mult_rows`: shared code, not a copy)
runs inside one `pl.pallas_call`, tiled 128 lanes a grid step along the
batch axis. The table of -A, the recoded digits and the accumulator
stay in VMEM, the grid pipelines the next tile's rows against the
compute, and the HBM traffic is the point and the digit rows in and the
T-less 3-stack out. Both tile programs (`_verify_tile`,
`_verify_tile_sr`) reach it through `dual_mult_sb_minus_ka`, which takes
it on a TPU; decompression, ristretto decode, mod-L prep and the final
compare stay XLA ops around it.

A Pallas kernel cannot close over array constants, and the field and
curve layers materialize their limb constants (2p, the fixed-base niels
table, ...) at trace time. `_closed()` lifts them off the traced jaxpr
once, dedupes identical arrays (the 2p bias alone appears dozens of
times), and the kernel takes them as inputs whose one block every grid
step maps.

The kernel is lane-local, so over a mesh each chip runs it on its own
shard (ops/verifier.py wraps a mesh's programs in `shard_map`: GSPMD
cannot partition a custom call).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ed25519_kernel as K
from . import field25519 as F
from .verifier import LANE_TILE

__all__ = ["TILE", "fused_walk"]

TILE = LANE_TILE  # lanes a grid step: one full VPU lane tile


@functools.lru_cache(maxsize=None)
def _closed(tile: int):
    """(closed_fn, unique_consts, index): `dual_mult_rows` at one tile
    with every trace-time array constant hoisted to an argument.
    jax.closure_convert hoists only captured jax arrays; these
    materialize during tracing (numpy -> jaxpr consts), so they are
    lifted straight off the jaxpr."""
    avals = tuple(
        jax.ShapeDtypeStruct(s, jnp.int32)
        for s in ((4, F.NLIMBS, tile), (64, tile), (64, tile))
    )
    cj = jax.make_jaxpr(K.dual_mult_rows)(*avals)

    def closed(A, dS, dk, *hoisted):
        (out,) = jax.core.eval_jaxpr(cj.jaxpr, list(hoisted), A, dS, dk)
        return out

    uniq: list = []
    index: list = []
    seen: dict = {}
    for c in cj.consts:
        arr = np.asarray(c)
        key = (arr.shape, arr.dtype.str, arr.tobytes())
        if key not in seen:
            seen[key] = len(uniq)
            uniq.append(arr)
        index.append(seen[key])
    return closed, uniq, index


def _const_spec(arr: np.ndarray) -> pl.BlockSpec:
    """The whole constant, the same block at every grid step."""
    nd = arr.ndim
    return pl.BlockSpec(
        arr.shape, lambda i: (0,) * nd, memory_space=pltpu.VMEM
    )


def _batch_spec(*leading: int, tile: int) -> pl.BlockSpec:
    """A block over the trailing batch axis; the leading axes whole."""
    nd = len(leading)
    return pl.BlockSpec(
        (*leading, tile), lambda i: (0,) * nd + (i,), memory_space=pltpu.VMEM
    )


def fused_walk(A, dS, dk, interpret: bool = False):
    """A (4, L, N) extended point, dS/dk (64, N) int32 radix-16 digits
    in [0, 15] -> the (3, L, N) T-less stack of [S]B - [k]A:
    dual_mult_sb_minus_ka's contract. N is a multiple of TILE, or
    narrower and one tile of its own width (the smallest buckets):
    what a verifier's buckets are rounded to (ops/verifier.py
    `_round`). Any other width is refused: a floored grid would leave
    the last lanes of the stack unwritten.
    `interpret` runs the kernel in Pallas's interpreter, which is how
    the CPU suite compares it with the scan program."""
    n = A.shape[-1]
    tile = min(TILE, n)
    if n % tile:
        raise ValueError(
            f"fused walk over {n} lanes: above {TILE} the width must "
            f"be a multiple of {TILE} (BucketedVerifier._round)"
        )
    closed, uniq, index = _closed(tile)

    def kernel(a_ref, ds_ref, dk_ref, *refs):
        *const_refs, out_ref = refs
        consts = [const_refs[j][...] for j in index]
        out_ref[...] = closed(a_ref[...], ds_ref[...], dk_ref[...], *consts)

    return pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[
            _batch_spec(4, F.NLIMBS, tile=tile),
            _batch_spec(64, tile=tile),
            _batch_spec(64, tile=tile),
            *map(_const_spec, uniq),
        ],
        out_specs=_batch_spec(3, F.NLIMBS, tile=tile),
        out_shape=jax.ShapeDtypeStruct((3, F.NLIMBS, n), jnp.int32),
        interpret=interpret,
    )(A, dS, dk, *map(jnp.asarray, uniq))
