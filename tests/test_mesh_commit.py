"""`[tpu] devices = 4` held to the plain reference: a mixed
ed25519/sr25519 commit through `verify_commit` and
`verify_commit_light`, installed over a four-device mesh as
`Node.__init__` installs it, on the suite's virtual CPU devices.

The data and the verdicts are the benchmark's own (chipbench/gen.py,
chipbench/reference/commit_verify.py: neither imports the program), so
what the cell `commit-10k-mixed.cold-4chip` compares on the chip is
compared here at 64 validators: 32 signatures a key class, which is
one 32-lane bucket of four 8-lane shards with no padding, so the last
lane of the last shard is a real vote. Every case runs once more with
the streamed path forced on at chunks of 8 (two lanes a chip), as an
accelerator streams at 2,048: `verify_commit` then sends four full
chunks a class, `verify_commit_light` (43 votes checked) two or three
and a remainder.
"""

from __future__ import annotations

import pytest

from chipbench import gen
from chipbench.commit_driver import CommitDriver
from chipbench.reference import commit_verify as R
from tendermint_tpu.config import Config
from tendermint_tpu.crypto import sigcache, tpu_verifier
from tendermint_tpu.libs import trace
from tendermint_tpu.node.node import Node
from tendermint_tpu.parallel import ShardedEd25519Verifier
from tendermint_tpu.types.commit import Commit

SEED = 2_147_483_693
# the benchmark's own chain id, so the sign-bytes (115 bytes) and the
# SHA-512 program are the ones the cell's rehearsal compiles
CONFIG = {
    "name": "mesh-test", "chain_id": "chipbench-10k", "validators": 64,
    "key_classes": ["ed25519", "sr25519"], "voting_power": 10,
}  # fmt: skip
TRAFFIC = {"ring_commits": 1, "warmup_commits": 0, "corrupt_every": 4}
ENTRIES = {"verify_commit": False, "verify_commit_light": True}
FLAG_ABSENT = 1


@pytest.fixture(scope="module")
def drivers():
    """The program-side objects and the reference of one seeded
    deployment, an entry each (they share the signed commit)."""
    return {name: CommitDriver(CONFIG, TRAFFIC, SEED, light) for name, light in ENTRIES.items()}


@pytest.fixture
def mesh_install():
    """What Node.__init__ does with `[tpu] devices = 4`."""
    tpu_verifier.install(
        min_batch=Config().tpu.min_batch_size, mesh=Node._device_mesh(4)
    )
    yield
    tpu_verifier.uninstall()


def _of_class(driver, kind: str) -> list:
    """Commit indexes of a key class among the votes the entry checks:
    lane j of that class's batch is the j-th of them."""
    ring = driver.ring
    return [i for i, v in enumerate(ring.validators[: ring.checked]) if v["kind"] == kind]


def _flip(commit: dict, *indexes) -> dict:
    for idx in indexes:
        commit = gen.corrupted(commit, idx)
    return commit


def _absent(commit: dict, keep: int) -> dict:
    """The commit with every vote after the first `keep` absent."""
    gone = {"flag": FLAG_ABSENT, "address": b"", "time_ns": 0, "sig": b""}
    return dict(commit, votes=commit["votes"][:keep] + [gone] * (len(commit["votes"]) - keep))


CASES = {
    "clean": lambda d, c: c,
    "first-lane-of-shard-0": lambda d, c: _flip(c, _of_class(d, "ed25519")[0]),
    "last-lane-of-the-last-shard": lambda d, c: _flip(c, _of_class(d, "sr25519")[-1]),
    "one-in-each-key-class": lambda d, c: _flip(
        c, _of_class(d, "ed25519")[9], _of_class(d, "sr25519")[12]
    ),
    # 42 of 64 equal powers is under two thirds
    "not-enough-power": lambda d, c: _absent(c, 42),
}


def _wire(commit: dict) -> bytes:
    """gen.encode_commit, with an absent vote as upstream writes it:
    the flag alone."""
    head = gen.encode_commit(dict(commit, votes=[]))
    return head + b"".join(
        R.f_bytes(4, R.f_varint(1, FLAG_ABSENT))
        if v["flag"] == FLAG_ABSENT
        else gen.encode_commit(dict(commit, votes=[v]))[len(head):]
        for v in commit["votes"]
    )


@pytest.mark.parametrize("streamed", [False, True], ids=["one-dispatch", "streamed"])
@pytest.mark.parametrize("entry", sorted(ENTRIES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_verdicts_equal_the_plain_references(
    drivers, mesh_install, monkeypatch, case, entry, streamed
):
    driver = drivers[entry]
    if streamed:
        seam = tpu_verifier._TpuBatchVerifier
        monkeypatch.setattr(seam, "_streaming", staticmethod(lambda: True))
        monkeypatch.setattr(seam, "STREAM_CHUNK", 8)
    commit = CASES[case](driver, driver.ring.commits[0])
    want = driver.reference.verdict(commit, driver.light)
    assert want.split("#")[0] == {
        "clean": "ok", "not-enough-power": "not_enough_power",
    }.get(case, "wrong_signature")  # fmt: skip
    sigcache.reset()
    before = tpu_verifier.stats()
    got = driver._verify(0, Commit.from_proto(_wire(commit)))
    after = tpu_verifier.stats()
    assert got == want
    assert after["faults"] == before["faults"]
    assert after["mesh_devices"] == 4
    if case == "not-enough-power":
        return  # the tally is judged before a signature is sent
    sent = sum(driver.groups.values())
    assert after["sigs"] - before["sigs"] == sent
    chunks = sum(-(-n // 8) if streamed else 1 for n in driver.groups.values())
    assert after["batches"] - before["batches"] == chunks


@pytest.fixture
def spans():
    trace.reset()
    trace.enable(capacity=1 << 14)
    yield trace.snapshot
    trace.disable()
    trace.reset()


def _verify_clean(drivers):
    driver = drivers["verify_commit"]
    sigcache.reset()
    wire = gen.encode_commit(driver.ring.commits[0])
    assert driver._verify(0, Commit.from_proto(wire)) == "ok"


def test_mesh_spans_say_how_wide_a_dispatch_was_spread(drivers, mesh_install, spans):
    assert tpu_verifier.stats()["mesh_devices"] == 4
    backing = tpu_verifier._ed_backing()
    assert isinstance(backing, ShardedEd25519Verifier) and backing.mesh.devices.size == 4
    _verify_clean(drivers)
    by_id = {s.span_id: s for s in spans()}

    def named(name):
        return [s for s in by_id.values() if s.name == name]

    dispatches = named("tpu_dispatch")
    assert len(dispatches) == 2
    assert all(s.attrs["mesh_devices"] == 4 for s in dispatches)
    places = named("shard_place")
    # host arrays a launch: pk, sig and the pre-image's SHA-512 for
    # ed25519 (its digests are on the mesh already: no span); pk, sig
    # and the challenges for sr25519
    assert len(places) == 2 + 1 + 3
    lanes = 32
    rows = {32, 64, 64 + drivers["verify_commit"].ring.sign_bytes_len}
    for s in places:
        assert s.attrs["devices"] == 4 and s.attrs["lanes_per_device"] == lanes // 4
        assert s.attrs["bytes"] // lanes in rows and s.attrs["bytes"] % lanes == 0
        assert by_id[s.parent_id].name == "device_launch"
    launches = named("device_launch")
    assert len(launches) == 3
    per_launch = sorted(sum(p.parent_id == s.span_id for p in places) for s in launches)
    assert per_launch == [1, 2, 3]


def test_streamed_chunks_carry_the_mesh_size(drivers, mesh_install, spans, monkeypatch):
    seam = tpu_verifier._TpuBatchVerifier
    monkeypatch.setattr(seam, "_streaming", staticmethod(lambda: True))
    monkeypatch.setattr(seam, "STREAM_CHUNK", 8)
    _verify_clean(drivers)
    chunks = [s for s in spans() if s.name == "tpu_stream_dispatch"]
    assert len(chunks) == 8
    assert all(s.attrs["mesh_devices"] == 4 for s in chunks)
    assert {s.attrs["lanes_per_device"] for s in spans() if s.name == "shard_place"} == {2}


def test_without_a_mesh_no_shard_place_opens(drivers, spans):
    tpu_verifier.install(min_batch=Config().tpu.min_batch_size)
    try:
        assert tpu_verifier.stats()["mesh_devices"] == 1
        _verify_clean(drivers)
        got = spans()
        assert not [s for s in got if s.name == "shard_place"]
        dispatches = [s for s in got if s.name == "tpu_dispatch"]
        assert len(dispatches) == 2
        assert all(s.attrs["mesh_devices"] == 1 for s in dispatches)
    finally:
        tpu_verifier.uninstall()
    assert tpu_verifier.stats()["mesh_devices"] == 0
