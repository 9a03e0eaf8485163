"""The fused window walk (ops/fused_walk.py) against the scan program.

On a TPU both tile programs run `[S]B - [k]A` as one Pallas kernel;
everywhere else, this suite included, as the lax.scan of
ops/ed25519_kernel.py, which is the kernel's oracle. Here the kernel
runs in Pallas's interpreter at one 128-lane tile, the width of a grid
step on the chip: its 3-stack limb for limb against the scan's, and a
tile program with the kernel inside against the CPU batch verifier. The
chip's own compiler sees the kernel in tests/test_chip_compile_fused.py.
"""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import ed25519_math as em
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.crypto.keys import pubkey_from_type_and_bytes
from tendermint_tpu.crypto.sr25519 import PrivKeySr25519
from tendermint_tpu.libs import trace
from tendermint_tpu.ops import ed25519_kernel as K
from tendermint_tpu.ops import edwards as E
from tendermint_tpu.ops import fused_walk
from tendermint_tpu.ops import sr25519_kernel as SR

LANES = fused_walk.TILE
KEYS = ("ed25519", "sr25519")

# the scalars whose digits sit on the recode's edges: nothing to add,
# the largest canonical one, and (8, 7, 7, ..., 7, 0), every window of
# which but the last recodes to -8 (a digit of 8, then 7 + carry)
ALL_MINUS_8 = sum(d << (4 * i) for i, d in enumerate([8] + [7] * 62 + [0]))
EDGE_SCALARS = (0, em.L - 1, ALL_MINUS_8)


def _signed(key, n):
    priv = PrivKeyEd25519 if key == "ed25519" else PrivKeySr25519
    keys = [
        priv.from_seed(hashlib.sha256(b"fused-" + bytes([i])).digest())
        for i in range(n)
    ]
    msgs = [b"fused-walk-msg-%03d" % i for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    return [k.pub_key().bytes() for k in keys], msgs, sigs


def _spans_of(call):
    """(call's result, the spans it opened)."""
    trace.disable()
    trace.reset()
    trace.enable()
    try:
        return call(), trace.snapshot()
    finally:
        trace.disable()
        trace.reset()


def _digits(scalar: int) -> list:
    return [(scalar >> (4 * i)) & 15 for i in range(64)]


@pytest.fixture
def fused(monkeypatch):
    """The walk a TPU traces, runnable here: the pick reads "fused" and
    the kernel goes through Pallas's interpreter."""
    monkeypatch.setattr(K, "walk_form", lambda: "fused")
    monkeypatch.setattr(
        fused_walk,
        "fused_walk",
        functools.partial(fused_walk.fused_walk, interpret=True),
    )


@pytest.mark.parametrize("key", KEYS)
def test_fused_walk_equals_the_scans_stack(key, fused, monkeypatch):
    """Same limbs, not only the same points: the kernel is the scan's
    arithmetic in another order of evaluation, and int32 limb
    arithmetic has no rounding to differ by."""
    pks, _msgs, _sigs = _signed(key, LANES)
    pk = jnp.asarray(K._join_cols(pks, 32, 0)).astype(jnp.int32)
    if key == "ed25519":
        A, ok = E.decompress(
            K._fe_from_bytes_dev(pk & K._TOPCLEAR), pk[31] >> 7
        )
    else:
        A, ok = SR.ristretto_decode_dev(pk)
    assert np.asarray(ok).all()
    rng = np.random.default_rng(36)
    dS = rng.integers(0, 16, (64, LANES))
    dk = rng.integers(0, 16, (64, LANES))
    for lane, scalar in enumerate(EDGE_SCALARS):
        dS[:, lane] = _digits(scalar)
        dk[:, -1 - lane] = _digits(scalar)
    dS[:, 3], dk[:, 3] = _digits(ALL_MINUS_8), _digits(em.L - 1)
    dS = jnp.asarray(dS, dtype=jnp.int32)
    dk = jnp.asarray(dk, dtype=jnp.int32)
    recoded = np.asarray(K._recode_signed(dS))
    assert (recoded[:-1, 2] == -8).all() and recoded.min() == -8
    got = jax.jit(lambda *a: K.dual_mult_sb_minus_ka(*a))(A, dS, dk)
    monkeypatch.setattr(K, "walk_form", lambda: "scan")
    want = jax.jit(lambda *a: K.dual_mult_sb_minus_ka(*a))(A, dS, dk)
    assert want.shape == got.shape == (3, K.F.NLIMBS, LANES)
    assert (np.asarray(want) == np.asarray(got)).all()


def _with_scalar(key, sig: bytes, scalar: int) -> bytes:
    s = bytearray(scalar.to_bytes(32, "little"))
    if key == "sr25519":
        s[31] |= 0x80  # schnorrkel's v1 marker
    return sig[:32] + bytes(s)


@pytest.mark.parametrize("key", KEYS)
def test_fused_tiles_bitmap_equals_the_cpu_factorys(key, fused):
    """A full 128-lane tile with the kernel inside: one corrupted
    signature, the edge scalars in place of three others' S and one
    malformed size. Every well-formed lane reads as the CPU batch
    verifier reads it, and the malformed one is invalid at its index."""
    base, tile = (
        (K.Ed25519Verifier, K._verify_tile)
        if key == "ed25519"
        else (SR.Sr25519Verifier, SR._verify_tile_sr)
    )

    class Fused(base):
        # a jit of its own: the shared one's traces hold the scan
        _TILE = staticmethod(jax.jit(lambda *a: tile(*a)))

    pks, msgs, sigs = _signed(key, LANES)
    sigs[5] = sigs[5][:40] + bytes([sigs[5][40] ^ 1]) + sigs[5][41:]
    for lane, scalar in zip((17, 64, 127), EDGE_SCALARS):
        sigs[lane] = _with_scalar(key, sigs[lane], scalar)
    cpu = crypto_batch.cpu_factory(key)()
    for pk, m, s in zip(pks, msgs, sigs):
        cpu.add(pubkey_from_type_and_bytes(key, pk), m, s)
    want = cpu.verify()[1]
    assert want == [i not in (5, 17, 64, 127) for i in range(LANES)]
    sigs[99] = sigs[99][:63]  # the add() layer would have refused it
    want[99] = False
    got, spans = _spans_of(lambda: Fused([LANES]).verify(pks, msgs, sigs))
    assert got.tolist() == want
    walks = {
        s.attrs["program"]: s.attrs.get("walk")
        for s in spans
        if s.name == "device_launch"
    }
    # read off the launched program: nothing told the verifier
    assert walks.pop("<lambda>") == "fused"
    assert walks == ({"sha512_fixed": None} if key == "ed25519" else {})


def _verifier(key, placement):
    from tendermint_tpu import parallel

    if placement == "one-device":
        return (K.Ed25519Verifier if key == "ed25519" else SR.Sr25519Verifier)([8])
    mesh = parallel.make_mesh(jax.devices()[:4])
    sharded = parallel.ShardedEd25519Verifier, parallel.ShardedSr25519Verifier
    return sharded[key == "sr25519"](mesh, [8])


@pytest.mark.parametrize("placement", ("one-device", "mesh"))
@pytest.mark.parametrize("key", KEYS)
def test_a_tile_launch_says_its_walk_and_sha512_does_not(key, placement):
    """What `fused_walk_share` reads (chipbench/layer_metrics): on this
    backend every tile launch says "scan", on one device and over a
    mesh (two lanes a chip), and SHA-512's span, whose program has no
    walk, carries no `walk` at all."""
    v = _verifier(key, placement)
    pks, msgs, sigs = _signed(key, 5)
    ok, spans = _spans_of(lambda: v.verify(pks, msgs, sigs))
    assert ok.all()
    launches = [s for s in spans if s.name == "device_launch"]
    *sha512, tile = launches
    assert tile.attrs["program"] == (
        "_verify_tile" if key == "ed25519" else "_verify_tile_sr"
    )
    assert tile.attrs["walk"] == "scan"
    assert [s.attrs["program"] for s in sha512] == (
        ["sha512_fixed"] if key == "ed25519" else []
    )
    assert all("walk" not in s.attrs for s in sha512)


@pytest.mark.parametrize("key", KEYS)
def test_the_walk_is_read_off_the_program_not_the_backend(key, monkeypatch):
    """A span says what the launched program holds. The program traced
    on this backend holds the scan and goes on holding it whatever the
    backend reads later: asked again (nothing remembered) with the
    backend reading "tpu", the launch still says "scan"."""
    from tendermint_tpu.ops import verifier

    v = _verifier(key, "one-device")
    pks, msgs, sigs = _signed(key, 5)
    assert v.verify(pks, msgs, sigs).all()
    monkeypatch.setattr(verifier, "_WALKS", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert K.walk_form() == "fused"
    ok, spans = _spans_of(lambda: v.verify(pks, msgs, sigs))
    assert ok.all()
    assert [s.attrs["walk"] for s in spans if "walk" in s.attrs] == ["scan"]


@pytest.mark.parametrize("lanes", (129, 683, 20_000))
def test_a_ragged_width_is_refused_not_floored(lanes):
    """Above one tile the kernel's grid takes whole tiles. A width that
    is none (a third of 2,049, an oversized batch: what `_round` keeps
    from it) raises where the program is traced, `python -O` or not: a
    floored grid would leave the stack's last lanes unwritten."""
    A = jax.ShapeDtypeStruct((4, K.F.NLIMBS, lanes), jnp.int32)
    d = jax.ShapeDtypeStruct((64, lanes), jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.eval_shape(fused_walk.fused_walk, A, d, d)


@pytest.mark.parametrize(
    "devices, lanes, want",
    [
        (1, 8, 8),
        (1, 2048, 2048),
        (1, 20_000, 20_096),  # oversized: 157 tiles
        (3, 8, 9),  # 3 lanes a chip: one narrow tile
        (3, 128, 129),
        (3, 2048, 2304),  # 683 -> 768 a chip
        (4, 8, 8),
        (4, 2048, 2048),
        (4, 12_288, 12_288),
        (5, 512, 515),  # 103 a chip
        (6, 2048, 2304),
        (7, 2048, 2688),
        (8, 16_384, 16_384),
    ],
)
def test_a_chips_share_is_one_tile_or_whole_tiles(devices, lanes, want):
    """`_round` is what keeps a ragged width from the kernel, for every
    mesh `node._device_mesh` accepts and for a batch above the largest
    bucket; the powers of two the cells run are as they were."""
    import types

    mesh = None if devices == 1 else types.SimpleNamespace(
        devices=np.empty((devices,), dtype=object)
    )
    v = K.Ed25519Verifier(mesh=mesh)
    got = v._bucket(lanes)
    assert got == want
    share = got // devices
    assert got % devices == 0
    assert share <= fused_walk.TILE or share % fused_walk.TILE == 0


def _launch(sid, program, **attrs):
    import types

    return types.SimpleNamespace(
        span_id=sid, name="device_launch", start_us=float(sid), dur_us=1.0,
        parent_id=0, root_id=sid, attrs=dict(program=program, bucket=2048, **attrs),
    )  # fmt: skip


@pytest.mark.parametrize(
    "walks, want",
    [
        (("fused", "fused", None, "fused"), 100.0),  # a TPU's window
        (("scan", None, "scan"), 0.0),  # this suite's backend
        (("fused", "scan", "scan", "scan"), 25.0),
        ((None, None), None),  # a parent commit: no span says
        ((), None),
    ],
)
def test_fused_walk_share_reads_the_launches_walk(walks, want):
    """The benchmark's reader over launches built by hand: tile
    launches count, SHA-512's (no `walk`) do not, and a program whose
    spans never say leaves the metric out instead of reading 0."""
    import types

    from chipbench import run as harness

    spans = [
        _launch(i + 1, "sha512_fixed")
        if walk is None
        else _launch(i + 1, "_verify_tile", walk=walk)
        for i, walk in enumerate(walks)
    ]
    read = harness.load_module("layer_metrics", "fused_walk_share").read
    assert read(types.SimpleNamespace(spans=spans, requests=1)) == want
