"""The chip's compiler, asked without a chip.

libtpu is installed here, and it compiles for a TPU v5e that is only
described (`jax.experimental.topologies`), not attached. These tests
hand it the device programs of the node's main path at the widths
chip_smoke.py drives them at, so what the compiler would refuse on the
chip — a shape, a memory budget, a partitioning — is refused here, at
no chip time. A compile that passes is not a chip run.

Two things steer the compiles, both in the tests and not in the
program: `jax.default_backend` is patched to "tpu" while a program is
traced, because the SHA kernels pick their fully unrolled form from it
(ops/sha512_kernel.py, ops/sha256_kernel.py) and the CPU suite
otherwise only ever sees the scan form; and the persistent compile
cache is off around the compiles, because an entry compiled for a
described chip cannot be read back without one.

Width: on an accelerator `_TpuBatchVerifier.add()` launches a dispatch
per full STREAM_CHUNK (2048), so the 10,000-validator mixed commit —
5,000 signatures a key class — reaches the device as three 2048-lane
batches a class, never as one 8192 or 12288 bucket. That is the bucket
compiled here. The merkle programs are the first level of a
10,000-leaf root (5,000 pairs, padded to 8192) and the proof batch of
all 10,000 leaves (16384 lanes by 16 levels).

The topology is described inside a fixture, once this file's tests
have started, and nowhere at import time: only one process may load
libtpu, the suite's workers each import every test file, and the one
worker that is handed this file must be the only one that loads it.
"""

import os
from unittest import mock

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

# a v5e chip has 16 GB; one program may take a quarter of it, so the
# other buckets' programs and the batches in flight fit beside it
HBM_BYTES = 16 * 10**9
PROGRAM_BUDGET = HBM_BYTES // 4


@pytest.fixture(scope="module")
def topo():
    # or libtpu writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """compile(fn, *shapes) -> (lowered, compiled), traced as on a TPU
    and with the persistent cache off for the module's duration."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile(fn, *shapes):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            lowered = fn.lower(*shapes)
        return lowered, lowered.compile()

    yield compile
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()
    # a trace is cached by function and shapes, whatever jit wraps it:
    # the tiles traced here hold the TPU's kernel (ops/fused_walk.py),
    # which a later test of this worker, on its CPU, could not run
    jax.clear_caches()


@pytest.fixture(scope="module")
def lanes():
    """The bucket a streamed chunk lands in (see module docstring)."""
    from tendermint_tpu.config import DEFAULT_BUCKET_SIZES
    from tendermint_tpu.crypto.tpu_verifier import _TpuBatchVerifier
    from tendermint_tpu.ops.ed25519_kernel import bucket_for

    n = bucket_for(_TpuBatchVerifier.STREAM_CHUNK, DEFAULT_BUCKET_SIZES)
    assert n == 2048
    return n


def _rows(rows: int, n: int, sharding, dtype=jnp.uint8):
    return jax.ShapeDtypeStruct((rows, n), dtype, sharding=sharding)


def _assert_fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (
        m.generated_code_size_in_bytes
        + m.temp_size_in_bytes
        + m.argument_size_in_bytes
        + m.output_size_in_bytes
    )
    assert 0 < total < PROGRAM_BUDGET, m
    return total


def test_ed25519_tile(compile_for_chip, one_chip, lanes):
    from tendermint_tpu.ops import ed25519_kernel as K

    _lowered, compiled = compile_for_chip(
        jax.jit(K._verify_tile),
        _rows(32, lanes, one_chip),
        _rows(64, lanes, one_chip),
        _rows(64, lanes, one_chip),
    )
    _assert_fits(compiled)


def test_sr25519_tile(compile_for_chip, one_chip, lanes):
    from tendermint_tpu.ops import sr25519_kernel as SR

    _lowered, compiled = compile_for_chip(
        jax.jit(SR._verify_tile_sr),
        _rows(32, lanes, one_chip),
        _rows(64, lanes, one_chip),
        _rows(64, lanes, one_chip),
    )
    _assert_fits(compiled)


def test_merlin_challenge_at_the_cells_sign_bytes(compile_for_chip, one_chip, lanes):
    """sr25519's merlin program over M || A || R at the 115-byte
    sign-bytes the benchmark's commits sign: the 24 rounds stay one
    loop on the chip too, and the program is small."""
    from tendermint_tpu.ops.sr25519_kernel import _MERLIN, MERLIN_DEVICE_LANES

    assert lanes >= MERLIN_DEVICE_LANES
    lowered, compiled = compile_for_chip(_MERLIN, _rows(115 + 64, lanes, one_chip))
    assert "stablehlo.while" in lowered.as_text()
    _assert_fits(compiled)


def test_sha512_unrolled_at_the_smokes_sign_bytes(
    compile_for_chip, one_chip, lanes
):
    """SHA-512 over R || A || sign-bytes at the one sign-bytes length
    every phase of chip_smoke.py signs — and in the unrolled form the
    chip gets, which no CPU test otherwise compiles."""
    import chip_smoke as S
    from tendermint_tpu.ops.sha512_kernel import sha512_fixed

    privs, vals = S.make_validators(1, seed=0)
    commit = S.sign_commit(privs, vals, S._block_id(1), 1, S.BASE_TIME_NS)
    (sign_bytes,) = commit.sign_bytes_batch(S.CHAIN_ID)
    lowered, compiled = compile_for_chip(
        jax.jit(sha512_fixed),
        _rows(64 + len(sign_bytes), lanes, one_chip),
    )
    assert "stablehlo.while" not in lowered.as_text()
    _assert_fits(compiled)


def test_merkle_root_first_level_of_10k_leaves(compile_for_chip, one_chip):
    from tendermint_tpu.ops import merkle_kernel as MK
    from tendermint_tpu.ops import sha256_kernel as S256

    pairs = MK._bucket(10_000 // 2)
    assert pairs == 8192
    lowered, compiled = compile_for_chip(
        jax.jit(S256.inner_hash_batch),
        _rows(32, pairs, one_chip),
        _rows(32, pairs, one_chip),
    )
    assert "stablehlo.while" not in lowered.as_text()
    _assert_fits(compiled)


def test_merkle_proof_batch_of_10k_leaves(compile_for_chip, one_chip):
    from tendermint_tpu.ops import merkle_kernel as MK

    k = MK._bucket(10_000)
    depth = MK._bucket(len(MK._sides_for(0, 10_000)))
    assert (k, depth) == (16384, 16)
    _lowered, compiled = compile_for_chip(
        MK._verify_program,
        _rows(32, k, one_chip),
        jax.ShapeDtypeStruct((depth, 32, k), jnp.uint8, sharding=one_chip),
        _rows(depth, k, one_chip, jnp.int32),
    )
    _assert_fits(compiled)


@pytest.fixture(scope="module")
def four_chips(topo):
    from tendermint_tpu.parallel import make_mesh

    mesh = make_mesh(topo.devices)
    assert mesh.devices.size == 4
    return mesh


def _assert_batch_axis_partitioned(compiled, n: int, row_counts) -> None:
    """Each chip holds a quarter of the lanes of every input, and none
    the whole batch."""
    text = compiled.as_text()
    for rows in row_counts:
        assert f"u8[{rows},{n // 4}]" in text, rows
        assert f"u8[{rows},{n}]" not in text, rows


# the three partitioned programs of the cell commit-10k-mixed.cold-4chip
# (`[tpu] devices = 4`), each the mesh verifier's own jitted program:
# (verifier, rows of its three inputs)
SHARDED_TILES = {
    "ed25519": ("ShardedEd25519Verifier", (32, 64, 64)),
    "sr25519": ("ShardedSr25519Verifier", (32, 64, 64)),
}


@pytest.mark.parametrize("key", sorted(SHARDED_TILES))
def test_sharded_tile_on_four_chips(compile_for_chip, four_chips, lanes, key):
    """A mesh verifier's tile over the four described chips, at the
    bucket a streamed chunk lands in: 512 lanes a chip."""
    from tendermint_tpu import parallel
    from tendermint_tpu.ops.verifier import LANES

    name, rows = SHARDED_TILES[key]
    v = getattr(parallel, name)(four_chips)
    n = v._bucket(lanes)
    mat = NamedSharding(four_chips, P(None, "sig"))
    _lowered, compiled = compile_for_chip(
        v._program(v._TILE, LANES), *(_rows(r, n, mat) for r in rows)
    )
    _assert_batch_axis_partitioned(compiled, n, set(rows))
    _assert_fits(compiled)


def test_sharded_sha512_on_four_chips(compile_for_chip, four_chips, lanes):
    """The mesh verifier's SHA-512 over R || A || a 115-byte sign-bytes
    (the benchmark's one length), partitioned like the tile: the
    digests leave each chip as its own quarter and never gather."""
    from tendermint_tpu.ops.ed25519_kernel import _SHA512
    from tendermint_tpu.ops.verifier import ROWS
    from tendermint_tpu.parallel import ShardedEd25519Verifier

    v = ShardedEd25519Verifier(four_chips)
    n = v._bucket(lanes)
    mat = NamedSharding(four_chips, P(None, "sig"))
    lowered, compiled = compile_for_chip(
        v._program(_SHA512, ROWS), _rows(64 + 115, n, mat)
    )
    assert "stablehlo.while" not in lowered.as_text()
    _assert_batch_axis_partitioned(compiled, n, (64 + 115, 64))
    assert "all-gather" not in compiled.as_text()
    _assert_fits(compiled)


def test_sharded_merlin_on_four_chips(compile_for_chip, four_chips, lanes):
    """The mesh verifier's merlin program over M || A || R at the
    benchmark's 115-byte sign-bytes, partitioned like the tile: the
    challenges leave each chip as its own quarter, the tile's operand
    where it lies."""
    from tendermint_tpu.ops.sr25519_kernel import _MERLIN
    from tendermint_tpu.ops.verifier import ROWS
    from tendermint_tpu.parallel import ShardedSr25519Verifier

    v = ShardedSr25519Verifier(four_chips)
    n = v._bucket(lanes)
    mat = NamedSharding(four_chips, P(None, "sig"))
    _lowered, compiled = compile_for_chip(
        v._program(_MERLIN, ROWS), _rows(115 + 64, n, mat)
    )
    _assert_batch_axis_partitioned(compiled, n, (115 + 64, 64))
    assert "all-gather" not in compiled.as_text()
    _assert_fits(compiled)
