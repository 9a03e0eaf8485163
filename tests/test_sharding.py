"""ShardedEd25519Verifier on the suite's virtual 8-device CPU mesh:
bucket rounding to mesh multiples, uneven batches, invalid-signature
localization across shards, and the node-level `[tpu] devices` install
seam (reference: the backend choice is config, not code —
crypto/crypto.go:53-61; sharding layout: tendermint_tpu/parallel)."""

import asyncio
import hashlib

import numpy as np
import pytest

import jax

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import tpu_verifier
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.parallel import ShardedEd25519Verifier, make_mesh


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest should provide 8 virtual devices"
    return make_mesh(devs[:8])


def _sign_set(n, tag=b"shard"):
    keys = [
        PrivKeyEd25519.from_seed(hashlib.sha256(tag + bytes([i])).digest())
        for i in range(n)
    ]
    msgs = [b"sharded-msg-" + bytes([i]) for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    return [k.pub_key().bytes() for k in keys], msgs, sigs


def test_bucket_rounds_to_mesh_multiples(mesh):
    v = ShardedEd25519Verifier(mesh, bucket_sizes=[4, 10, 100])
    # every configured bucket is rounded up to a multiple of 8
    assert all(b % 8 == 0 for b in v.bucket_sizes)
    for n in (1, 4, 9, 100, 101, 20_000):  # incl. oversized
        assert v._bucket(n) % 8 == 0
        assert v._bucket(n) >= n


def test_uneven_batch_verifies(mesh):
    # 13 signatures on 8 devices: bucket pads to a multiple of 8
    pks, msgs, sigs = _sign_set(13)
    v = ShardedEd25519Verifier(mesh, bucket_sizes=[8])
    ok = v.verify(pks, msgs, sigs)
    assert ok.shape == (13,) and ok.all()


def test_invalid_sigs_localized_across_shards(mesh):
    # corruptions landing in different device shards of a 16-batch
    pks, msgs, sigs = _sign_set(16)
    bad = {0, 7, 9, 15}  # shard boundaries with 16/8 = 2 per device
    for i in bad:
        sigs[i] = sigs[i][:40] + bytes([sigs[i][40] ^ 1]) + sigs[i][41:]
    v = ShardedEd25519Verifier(mesh, bucket_sizes=[16])
    ok = v.verify(pks, msgs, sigs)
    assert ok.tolist() == [i not in bad for i in range(16)]


def test_matches_single_chip_verifier(mesh):
    from tendermint_tpu.ops.ed25519_kernel import Ed25519Verifier

    pks, msgs, sigs = _sign_set(11, b"eq")
    sigs[3] = b"\x00" * 64
    sharded = ShardedEd25519Verifier(mesh).verify(pks, msgs, sigs)
    single = Ed25519Verifier().verify(pks, msgs, sigs)
    assert sharded.tolist() == single.tolist()


def test_node_installs_sharded_verifier_from_config(tmp_path):
    """`[tpu] devices = 8` routes the node's batch verification through
    a mesh-sharded verifier; a live commit then flows across the mesh."""
    from tendermint_tpu.node.node import make_node

    from tests.test_node import make_genesis, make_home

    async def go():
        priv = PrivKeyEd25519.from_seed(b"\x77" * 32)
        genesis = make_genesis([priv])
        cfg = make_home(tmp_path, 0, genesis, priv)
        cfg.tpu.devices = 8
        node = make_node(cfg)
        try:
            bv = crypto_batch.create_batch_verifier(
                priv.pub_key(), size_hint=64
            )
            assert isinstance(bv, tpu_verifier.TpuEd25519BatchVerifier)
            assert isinstance(bv._verifier, ShardedEd25519Verifier)
            assert bv._verifier.mesh.devices.size == 8
            # and the sharded path actually verifies
            pks, msgs, sigs = _sign_set(9, b"node")
            keys = [
                PrivKeyEd25519.from_seed(
                    hashlib.sha256(b"node" + bytes([i])).digest()
                )
                for i in range(9)
            ]
            for k, m, s in zip(keys, msgs, sigs):
                bv.add(k.pub_key(), m, s)
            ok, bitmap = bv.verify()
            assert ok and bitmap == [True] * 9
        finally:
            tpu_verifier.uninstall()

    asyncio.run(go())


def test_device_mesh_config_validation():
    from tendermint_tpu.node.node import Node

    assert Node._device_mesh(1) is None
    m = Node._device_mesh(0)  # all visible devices
    assert m is not None and m.devices.size == len(jax.devices())
    with pytest.raises(RuntimeError, match="only"):
        Node._device_mesh(10_000)


# ---------------------------------------------------------------------------
# sr25519


def _sr_sign_set(n, tag=b"sr-shard"):
    from tendermint_tpu.crypto.sr25519 import PrivKeySr25519

    keys = [
        PrivKeySr25519.from_seed(hashlib.sha256(tag + bytes([i])).digest())
        for i in range(n)
    ]
    msgs = [b"sr-sharded-" + bytes([i]) for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    return [k.pub_key().bytes() for k in keys], msgs, sigs


def test_sr25519_bucket_rounds_to_mesh_multiples(mesh):
    from tendermint_tpu.parallel import ShardedSr25519Verifier

    v = ShardedSr25519Verifier(mesh, bucket_sizes=[4, 10, 100])
    assert all(b % 8 == 0 for b in v.bucket_sizes)
    for n in (1, 9, 101, 20_000):
        assert v._bucket(n) % 8 == 0 and v._bucket(n) >= n


def test_sr25519_uneven_batch_and_localization(mesh):
    from tendermint_tpu.parallel import ShardedSr25519Verifier

    pks, msgs, sigs = _sr_sign_set(13)
    bad = {2, 8, 12}
    for i in bad:
        sigs[i] = sigs[i][:40] + bytes([sigs[i][40] ^ 1]) + sigs[i][41:]
    v = ShardedSr25519Verifier(mesh, bucket_sizes=[8])
    ok = v.verify(pks, msgs, sigs)
    assert ok.tolist() == [i not in bad for i in range(13)]


def test_sr25519_matches_single_chip(mesh):
    from tendermint_tpu.ops.sr25519_kernel import Sr25519Verifier
    from tendermint_tpu.parallel import ShardedSr25519Verifier

    pks, msgs, sigs = _sr_sign_set(9, b"sr-eq")
    sigs[4] = b"\x00" * 64
    sharded = ShardedSr25519Verifier(mesh).verify(pks, msgs, sigs)
    single = Sr25519Verifier().verify(pks, msgs, sigs)
    assert sharded.tolist() == single.tolist()


def test_mesh_install_shards_sr25519(mesh):
    """install(mesh=...) must route sr25519 batches through the
    sharded verifier too (crypto/crypto.go:53-61: backend is config)."""
    from tendermint_tpu.crypto.sr25519 import PrivKeySr25519
    from tendermint_tpu.parallel import ShardedSr25519Verifier

    tpu_verifier.install(min_batch=2, mesh=mesh)
    try:
        priv = PrivKeySr25519.from_seed(b"\x21" * 32)
        bv = crypto_batch.create_batch_verifier(priv.pub_key(), size_hint=8)
        assert isinstance(bv, tpu_verifier.TpuSr25519BatchVerifier)
        assert isinstance(bv._verifier, ShardedSr25519Verifier)
        for i in range(8):
            m = b"mesh-sr-%d" % i
            bv.add(priv.pub_key(), m, priv.sign(m))
        ok, bitmap = bv.verify()
        assert ok and bitmap == [True] * 8
    finally:
        tpu_verifier.uninstall()


# ---------------------------------------------------------------------------
# the one verifier body (ops/verifier.py): both key classes, both placements


_SHARED: dict = {}  # (key, placement) -> verifier: each compiles once


def _make_verifier(key, placement, mesh, sizes):
    from tendermint_tpu.ops.ed25519_kernel import Ed25519Verifier
    from tendermint_tpu.ops.sr25519_kernel import Sr25519Verifier
    from tendermint_tpu.parallel import ShardedSr25519Verifier

    single, sharded = {
        "ed25519": (Ed25519Verifier, ShardedEd25519Verifier),
        "sr25519": (Sr25519Verifier, ShardedSr25519Verifier),
    }[key]
    if placement == "mesh":
        return sharded(mesh, list(sizes))
    return single(list(sizes))


def _verifier_case(key, placement, mesh):
    """(a verifier with buckets 8 and 32, a signed set of 11, the
    seam's batch class) for a key class on one device or over the
    suite's eight-device mesh."""
    if (key, placement) not in _SHARED:
        _SHARED[key, placement] = _make_verifier(key, placement, mesh, (8, 32))
    sign_set = _sign_set if key == "ed25519" else _sr_sign_set
    seam = (
        tpu_verifier.TpuEd25519BatchVerifier
        if key == "ed25519"
        else tpu_verifier.TpuSr25519BatchVerifier
    )
    return _SHARED[key, placement], sign_set(11, b"one-body-" + key.encode()), seam


CASES = [
    (key, placement)
    for key in ("ed25519", "sr25519")
    for placement in ("one", "mesh")
]


@pytest.mark.parametrize("key, placement", CASES)
def test_bitmap_equals_the_cpu_factorys(mesh, key, placement):
    """One corrupted signature and one malformed-size entry: every
    well-formed lane reads as the CPU batch verifier reads it, and the
    malformed one is invalid at its own index, not an exception."""
    from tendermint_tpu.crypto.keys import pubkey_from_type_and_bytes

    v, (pks, msgs, sigs), _seam = _verifier_case(key, placement, mesh)
    sigs[3] = sigs[3][:40] + bytes([sigs[3][40] ^ 1]) + sigs[3][41:]
    cpu = crypto_batch.cpu_factory(key)()
    for pk, m, s in zip(pks, msgs, sigs):
        cpu.add(pubkey_from_type_and_bytes(key, pk), m, s)
    want = cpu.verify()[1]
    assert want == [i != 3 for i in range(11)]
    sigs[7] = sigs[7][:63]  # the add() layer would have refused it
    want[7] = False
    assert v.verify(pks, msgs, sigs).tolist() == want


@pytest.mark.parametrize("key, placement", CASES)
def test_bucket_rule_has_one_home(mesh, key, placement):
    """`_bucket(n)` holds n, comes from the configured sizes and is a
    multiple of the mesh; the seam's pad-waste for a dispatch is that
    bucket less n, under a mesh too (the seam asks the verifier)."""
    from tendermint_tpu.crypto.keys import pubkey_from_type_and_bytes

    devices = 8 if placement == "mesh" else 1
    r = _make_verifier(key, placement, mesh, (4, 12, 30))  # never dispatched
    assert r.bucket_sizes == ([8, 16, 32] if devices == 8 else [4, 12, 30])
    for n in (1, 4, 5, 11, 13, 30):
        assert r._bucket(n) >= n and r._bucket(n) in r.bucket_sizes
    for n in (1, 31, 33, 20_001):  # oversized included
        assert r._bucket(n) >= n and r._bucket(n) % devices == 0
    v, (pks, msgs, sigs), seam = _verifier_case(key, placement, mesh)
    assert tpu_verifier._bucket_of(v, 11) == v._bucket(11)
    bv = seam(verifier=v)
    for pk, m, s in zip(pks, msgs, sigs):
        bv.add(pubkey_from_type_and_bytes(key, pk), m, s)
    before = tpu_verifier.stats()["pad_waste"]
    ok, bitmap = bv.verify()
    assert ok and bitmap == [True] * 11
    assert tpu_verifier.stats()["pad_waste"] - before == v._bucket(11) - 11


@pytest.mark.parametrize("key, placement", CASES)
def test_one_dispatch_opens_the_named_spans(mesh, key, placement):
    """The benchmark reads these by name: `pack_rows`, `device_launch`
    with `program` the traced function's own name, and under a mesh
    one `shard_place` a host array with the bucket's share a chip."""
    from tendermint_tpu.libs import trace

    v, (pks, msgs, sigs), _seam = _verifier_case(key, placement, mesh)
    trace.disable()
    trace.reset()
    trace.enable()
    try:
        assert v.verify(pks, msgs, sigs).all()
        spans = trace.snapshot()
    finally:
        trace.disable()
        trace.reset()
    bucket = v._bucket(11)
    named = lambda name: [s for s in spans if s.name == name]  # noqa: E731
    (pack,) = named("pack_rows")
    assert pack.attrs["n"] == 11 and pack.attrs["bucket"] == bucket
    launches = named("device_launch")
    assert [s.attrs["program"] for s in launches] == (
        ["sha512_fixed", "_verify_tile"]
        if key == "ed25519"
        else ["_verify_tile_sr"]
    )
    assert all(s.attrs["bucket"] == bucket for s in launches)
    assert len(named("merlin_challenges")) == (key == "sr25519")
    places = named("shard_place")
    if placement == "one":
        assert places == []
        return
    # ed25519: the pre-image under the SHA-512 launch, then pubkeys and
    # signatures under the tile's (the digests are on the mesh already);
    # sr25519: all three operands come from the host
    assert len(places) == 3
    launch_ids = {s.span_id for s in launches}
    for s in places:
        assert s.parent_id in launch_ids
        assert s.attrs["devices"] == 8
        assert s.attrs["lanes_per_device"] == bucket // 8


def test_no_environment_switch_on_the_verification_path():
    """The path a commit's signatures take reads two deployment
    settings from the environment and nothing else: a switch that
    selects a code path there would be a configuration no cell runs."""
    import ast
    import glob
    import os

    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tendermint_tpu",
    )
    files = sorted(
        glob.glob(os.path.join(root, "ops", "*.py"))
        + glob.glob(os.path.join(root, "parallel", "*.py"))
        + [
            os.path.join(root, *p.split("/"))
            for p in (
                "crypto/tpu_verifier.py",
                "crypto/sigcache.py",
                "crypto/batch.py",
                "types/validation.py",
            )
        ]
    )
    assert len(files) > 12
    allowed = {
        "crypto/tpu_verifier.py": "TM_TPU_GATHER_DEADLINE_S",
        "ops/compile_cache.py": "JAX_COMPILATION_CACHE_DIR",
    }
    seen = set()
    for path in files:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        text = open(path, encoding="utf-8").read()
        for node in ast.walk(ast.parse(text)):
            name = getattr(node, "attr", None) or getattr(node, "id", None)
            if name not in ("environ", "environb", "getenv", "putenv"):
                continue
            line = text.splitlines()[node.lineno - 1]
            assert allowed.get(rel, "no variable") in line, (
                f"{rel}:{node.lineno} reads the environment: {line.strip()}"
            )
            seen.add(rel)
    assert seen == set(allowed)
