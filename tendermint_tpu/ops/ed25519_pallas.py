"""Fused Pallas TPU kernels for batched ed25519 verification.

The XLA path in ops/ed25519_kernel.py expresses the verification
program as thousands of separate HLO ops per scan window; XLA fuses
elementwise chains but every pad/concatenate/reduce materializes an
intermediate, and the scan body round-trips HBM many times per window.
The kernels here run the *same* math (ed25519_kernel's tile body — the
code is shared, not duplicated) inside `pl.pallas_call`, tiled along
the batch axis: intermediates stay in VMEM, the grid pipelines the
byte-row DMA against compute, and the only HBM traffic is rows in and
results out.

Two granularities, because Mosaic compile cost scales with program
size (the monolithic tile is ~37k jaxpr eqns, the dual-mult segment
~7k; both compile for a v5e ahead of time — scripts/aot_check.py):

- verify_pallas: the whole `_verify_tile` body in one kernel
  (decompression + scalar prep + 64-window walk + compare).
- dual_mult_pallas + verify_hybrid: ONLY the dual scalar
  multiplication `[S]B - [k]A` (table build + 64 windows — the
  dominant cost) as the kernel; decompression, mod-L prep, and the
  projective compare remain XLA ops around it, fused by XLA as usual.

Pallas kernels cannot close over array constants, and the field/curve
layer materializes its limb constants (2p, L, the fixed-base niels
table…) at trace time. `_closed()` lifts them off the traced jaxpr
once, dedupes identical arrays (the 2p bias alone appears dozens of
times), and the wrappers feed them to the kernel as broadcast inputs —
every grid step maps block (0, …) of each constant.

Layout per tile: byte rows (32|64, TILE) int32 with the batch in the
lane axis, exactly the batch-minor convention of field25519 — one tile
is (sublanes, lanes=TILE signatures).

This is the device program behind the reference's batch-verifier seam
(crypto/ed25519/ed25519.go:202-237, crypto/crypto.go:53-61); the
ZIP-215 semantics and the per-index validity bitmap are identical to
the XLA path, which remains the fallback on CPU and the differential
oracle in tests.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["TILE", "verify_pallas", "verify_hybrid", "dual_mult_pallas"]

TILE = 128  # lanes per grid step: one full VPU lane tile


def _body_and_avals(kind: str, tile: int):
    from . import ed25519_kernel as K
    from . import field25519 as F

    if kind == "tile":
        fn = lambda pk, sig, dig: K._verify_tile(pk, sig, dig, mosaic=True)
        shapes = ((32, tile), (64, tile), (64, tile))
    elif kind == "dual":
        fn = lambda A, dS, dk: K.dual_mult_sb_minus_ka(
            A, dS, dk, mosaic=True
        )
        shapes = ((4, F.NLIMBS, tile), (64, tile), (64, tile))
    else:  # pragma: no cover
        raise ValueError(kind)
    avals = tuple(jax.ShapeDtypeStruct(s, jnp.int32) for s in shapes)
    return fn, avals


@functools.lru_cache(maxsize=8)
def _closed(kind: str, tile: int):
    """(closed_fn, unique_consts, index_map): the requested body with
    every trace-time array constant hoisted to an explicit argument."""
    fn, avals = _body_and_avals(kind, tile)
    # jax.closure_convert hoists only captured jax arrays; the limb
    # constants here materialize during tracing (np -> jaxpr consts),
    # so lift them straight off the jaxpr instead.
    cj = jax.make_jaxpr(fn)(*avals)
    consts = cj.consts
    n_in = len(avals)

    def closed(*args):
        ins, hoisted = args[:n_in], args[n_in:]
        outs = jax.core.eval_jaxpr(cj.jaxpr, list(hoisted), *ins)
        return outs[0] if len(outs) == 1 else outs

    uniq: list[np.ndarray] = []
    index: list[int] = []
    seen: dict = {}
    for c in consts:
        arr = np.asarray(c)
        key = (arr.shape, arr.dtype.str, arr.tobytes())
        if key not in seen:
            seen[key] = len(uniq)
            uniq.append(arr)
        index.append(seen[key])
    return closed, uniq, index


def _make_kernel(kind: str, tile: int, n_in: int):
    def _kernel(*refs):
        closed, uniq, index = _closed(kind, tile)
        in_refs = refs[:n_in]
        const_refs = refs[n_in : n_in + len(uniq)]
        out_ref = refs[-1]
        consts = [const_refs[j][...] for j in index]
        out = closed(*[r[...] for r in in_refs], *consts)
        if kind == "tile":
            out_ref[...] = out.astype(jnp.int32)[None, :]
        else:
            out_ref[...] = out

    return _kernel


def _const_spec(arr: np.ndarray) -> pl.BlockSpec:
    nd = arr.ndim
    return pl.BlockSpec(
        arr.shape, lambda i, _nd=nd: (0,) * _nd, memory_space=pltpu.VMEM
    )


def _batch_spec(shape) -> pl.BlockSpec:
    """Block over the trailing batch axis; leading axes whole."""
    nd = len(shape)
    return pl.BlockSpec(
        shape, lambda i, _nd=nd: (0,) * (_nd - 1) + (i,),
        memory_space=pltpu.VMEM,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def verify_pallas(pk_b, sig_b, dig_b, interpret: bool = False, tile: int = TILE):
    """pk_b (32, N), sig_b (64, N), dig_b (64, N) int32 byte rows with
    N a multiple of `tile` -> (N,) bool validity bitmap. `tile` stays at
    the 128-lane default on hardware; tests shrink it (with interpret
    mode) to keep the differential cheap."""
    n = pk_b.shape[1]
    assert n % tile == 0, n
    _, uniq, _ = _closed("tile", tile)
    grid = (n // tile,)
    ok = pl.pallas_call(
        _make_kernel("tile", tile, 3),
        grid=grid,
        in_specs=[
            _batch_spec((32, tile)),
            _batch_spec((64, tile)),
            _batch_spec((64, tile)),
            *[_const_spec(c) for c in uniq],
        ],
        out_specs=_batch_spec((1, tile)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(
        pk_b.astype(jnp.int32),
        sig_b.astype(jnp.int32),
        dig_b.astype(jnp.int32),
        *[jnp.asarray(c) for c in uniq],
    )
    return ok[0] != 0


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def dual_mult_pallas(A, dS, dk, interpret: bool = False, tile: int = TILE):
    """[S]B - [k]A as a Pallas kernel. A (4, L, N) extended point,
    dS/dk (64, N) int32 radix-16 digits in [0, 15] -> (3, L, N) T-less
    projective stack (same contract as dual_mult_sb_minus_ka)."""
    from . import field25519 as F

    n = A.shape[-1]
    assert n % tile == 0, n
    _, uniq, _ = _closed("dual", tile)
    grid = (n // tile,)
    return pl.pallas_call(
        _make_kernel("dual", tile, 3),
        grid=grid,
        in_specs=[
            _batch_spec((4, F.NLIMBS, tile)),
            _batch_spec((64, tile)),
            _batch_spec((64, tile)),
            *[_const_spec(c) for c in uniq],
        ],
        out_specs=_batch_spec((3, F.NLIMBS, tile)),
        out_shape=jax.ShapeDtypeStruct((3, F.NLIMBS, n), jnp.int32),
        interpret=interpret,
    )(A, dS, dk, *[jnp.asarray(c) for c in uniq])


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def verify_hybrid(pk_b, sig_b, dig_b, interpret: bool = False, tile: int = TILE):
    """The segmented program: XLA for decompression/scalar prep/compare,
    the Pallas dual-mult kernel for the 64-window scalar multiplication.
    Same signature and semantics as verify_pallas."""
    from . import ed25519_kernel as K

    dual = functools.partial(dual_mult_pallas, interpret=interpret, tile=tile)
    return K._verify_tile(pk_b, sig_b, dig_b, dual_fn=dual)
