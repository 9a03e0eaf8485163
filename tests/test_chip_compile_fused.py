"""The fused window walk, handed to the chip's compiler without a chip.

On a TPU both tile programs run the 64-window walk of `[S]B - [k]A` as
one Pallas kernel (ops/fused_walk.py), picked from
`jax.default_backend()` while the program is traced
(ops/ed25519_kernel.py `walk_form`). The CPU suite otherwise only ever
traces the scan form, so these tests patch the backend as
tests/test_chip_compile.py does and give libtpu, which compiles for a
described `v5e:2x2`, the programs at the widths the cells launch them
at: 8 lanes (an install's probe, the first program a node touches: a
tile narrower than a lane tile), 128 lanes (one grid step:
`commit-150.catchup`, `commit-150.catchup-node`), 2,048 lanes (a
streamed chunk, sixteen grid steps), and over a mesh (`shard_map`)
2,048 over four chips (512 lanes and four grid steps a chip), 8 over
four (two lanes a chip: the probe of a mesh's install) and 2,048 over
three (rounded to 768 a chip: a mesh whose size is no power of two).
What Mosaic would refuse on the chip it refuses here. A compile that
passes is not a chip run.

This is a file of its own so that `--dist loadfile` can hand it to
another worker than test_chip_compile.py's four minutes; the topology
is described inside a fixture for the reasons given there. Two workers
can hold libtpu at once only under `ALLOW_MULTIPLE_LIBTPU_LOAD=1`, as
the tier-1 command sets it: without it the later of the two files
skips.
"""

from unittest import mock

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

# the described topology, the compile with the cache off and the memory
# budget are test_chip_compile.py's; a module-scoped fixture imported
# here is this module's own instance
from tests.test_chip_compile import (  # noqa: F401
    _assert_fits,
    compile_for_chip,
    topo,
)

KERNEL = "tpu_custom_call"  # what a Pallas kernel lowers to

# key class -> (module, tile function, mesh verifier, rows of the inputs)
TILES = {
    "ed25519": ("ed25519_kernel", "_verify_tile", "ShardedEd25519Verifier", (32, 64, 64)),
    "sr25519": ("sr25519_kernel", "_verify_tile_sr", "ShardedSr25519Verifier", (32, 64, 64)),
}  # fmt: skip


def _tile(key):
    import importlib

    module, name, _sharded, rows = TILES[key]
    mod = importlib.import_module(f"tendermint_tpu.ops.{module}")
    return getattr(mod, name), rows


@pytest.mark.parametrize("lanes", (8, 128, 2048))
@pytest.mark.parametrize("key", sorted(TILES))
def test_fused_tile_on_one_chip(compile_for_chip, topo, key, lanes):
    from tendermint_tpu.ops.verifier import _walk_of

    fn, rows = _tile(key)
    one_chip = SingleDeviceSharding(topo.devices[0])
    prog = jax.jit(fn)
    operands = [
        jax.ShapeDtypeStruct((r, lanes), jnp.uint8, sharding=one_chip)
        for r in rows
    ]
    lowered, compiled = compile_for_chip(prog, *operands)
    assert KERNEL in lowered.as_text()
    assert KERNEL in compiled.as_text()
    # what a launch of this program puts on its span
    assert _walk_of(prog, lanes, operands) == "fused"
    _assert_fits(compiled)


# (chips, bucket asked for) -> lanes a chip. The kernel is one for both
# key classes, so the widths only a mesh makes are compiled for one.
MESHES = [
    ("ed25519", 4, 2048, 512),
    ("sr25519", 4, 2048, 512),
    ("ed25519", 4, 8, 2),
    ("sr25519", 3, 2048, 768),
]


@pytest.mark.parametrize("key, chips, bucket, share", MESHES)
def test_fused_tile_over_a_mesh(compile_for_chip, topo, key, chips, bucket, share):
    """The mesh verifier's own program: every chip runs the kernel on
    its own lanes, and no input is gathered to get there."""
    from tendermint_tpu import parallel
    from tendermint_tpu.ops.verifier import LANES, _walk_of

    mesh = parallel.make_mesh(topo.devices[:chips])
    assert mesh.devices.size == chips
    _module, _name, sharded, rows = TILES[key]
    v = getattr(parallel, sharded)(mesh)
    n = v._bucket(bucket)
    assert n == share * chips
    mat = NamedSharding(mesh, P(None, "sig"))
    prog = v._program(v._TILE, LANES)
    operands = [
        jax.ShapeDtypeStruct((r, n), jnp.uint8, sharding=mat) for r in rows
    ]
    lowered, compiled = compile_for_chip(prog, *operands)
    assert KERNEL in lowered.as_text()
    assert _walk_of(prog, n, operands) == "fused"  # through the shard_map
    text = compiled.as_text()
    assert KERNEL in text
    for r in set(rows):
        assert f"u8[{r},{share}]" in text, r
        assert f"u8[{r},{n}]" not in text, r
    assert "all-gather" not in text
    _assert_fits(compiled)


@pytest.mark.parametrize("key", sorted(TILES))
def test_walk_follows_the_backend(key):
    """What picks the form is the backend at trace time: on this CPU
    backend the traced program holds no kernel; traced as on a TPU it
    holds one (the compiles above). What a launch's span then says is
    read off the program (tests/test_fused_walk.py)."""
    from tendermint_tpu.ops.ed25519_kernel import walk_form

    fn, rows = _tile(key)
    assert jax.default_backend() == "cpu"
    assert walk_form() == "scan"
    # a wrapper of its own: the compiles above cached `fn`'s TPU trace
    lowered = jax.jit(lambda *a: fn(*a)).lower(
        *(jax.ShapeDtypeStruct((r, 128), jnp.uint8) for r in rows)
    )
    assert KERNEL not in lowered.as_text()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert walk_form() == "fused"
