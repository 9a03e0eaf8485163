"""The device merlin challenge (ops/merlin_kernel.py) against the host's
transcripts (crypto/merlin.py, crypto/sr25519.py), and the sr25519
verifier's two forms of its third operand: the program at
MERLIN_DEVICE_LANES and up, the host below (reference model: the
schnorrkel signing transcript behind crypto/sr25519/batch.go)."""

import contextlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import run as harness
from tendermint_tpu.crypto import faults, ristretto as rst
from tendermint_tpu.crypto import sr25519 as sr
from tendermint_tpu.crypto import tpu_verifier as T
from tendermint_tpu.crypto.sr25519 import PrivKeySr25519, PubKeySr25519
from tendermint_tpu.libs import trace
from tendermint_tpu.ops import merlin_kernel as MK
from tendermint_tpu.ops import sr25519_kernel as SK
from tendermint_tpu.ops.ed25519_kernel import _mod_l_dev

# 0, the install's probe (21), the cells' sign-bytes (115), and either
# side of each length where the transcript takes one permutation more
LENGTHS = (0, 21, 115, 128, 129, 130, 294, 295, 296)


def _random_triples(n, mlen, seed):
    rng = np.random.default_rng(seed)
    draw = lambda k: bytes(rng.integers(0, 256, k, dtype=np.uint8))  # noqa: E731
    return (
        [draw(32) for _ in range(n)],
        [draw(mlen) for _ in range(n)],
        [draw(32) + draw(32) for _ in range(n)],
    )


def _signed(n, mlen=115, seed=0):
    privs = [PrivKeySr25519.from_seed(bytes([seed + i + 1]) * 32) for i in range(n)]
    msgs = [bytes([seed + i]) * mlen for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    return [p.pub_key().bytes() for p in privs], msgs, sigs


def _oracle(pks, msgs, sigs):
    return [PubKeySr25519(pk).verify_signature_cpu(m, s) for pk, m, s in zip(pks, msgs, sigs)]


def _traced(fn):
    trace.disable()
    trace.reset()
    trace.enable()
    try:
        out = fn()
        return out, trace.snapshot()
    finally:
        trace.disable()
        trace.reset()


def _named(spans, name):
    return [s for s in spans if s.name == name]


@pytest.fixture
def device_form(monkeypatch):
    """The device form at the suite's small buckets."""
    monkeypatch.setattr(SK, "MERLIN_DEVICE_LANES", 8)


# -- the program against the host's transcripts --------------------------


@pytest.mark.parametrize(
    "mlen, lanes", [(m, 8) for m in LENGTHS] + [(115, 32)]
)
def test_device_challenge_is_the_hosts_byte_for_byte(mlen, lanes):
    n = lanes - 3  # three pad lanes
    pks, msgs, sigs = _random_triples(n, mlen, seed=mlen)
    rs = [s[:32] for s in sigs]
    rows = SK._merlin_rows(pks, msgs, sigs, lanes - n)
    assert rows.shape == (mlen + 64, lanes)
    got = np.asarray(SK._MERLIN(jnp.asarray(rows)))
    assert got.shape == (64, lanes) and got.dtype == np.uint8
    assert (got[:, :n].T == sr.challenge_wides(pks, msgs, rs)).all()
    # the one-transcript path, independent of the batched one
    assert bytes(got[:, 0]) == sr._challenge_wide(
        sr._signing_transcript(msgs[0]), pks[0], rs[0]
    )
    # reduced as the tile reduces it, they are challenge_batch's scalars
    k = np.asarray(jax.jit(_mod_l_dev)(jnp.asarray(got.astype(np.int32))))
    assert [
        int.from_bytes(bytes(k[:, i].astype(np.uint8)), "little") for i in range(n)
    ] == sr.challenge_batch(pks, msgs, rs)


def test_the_schedule_permutes_where_the_rate_says():
    """Two permutations up to 128 message bytes (the message, keys and
    framing fill one 166-byte rate, the challenge's begin-op the next),
    a third from 129, a fourth from 295: the lengths the differential
    straddles."""
    counts = {m: len(MK._schedule(m).blocks) for m in (0, 128, 129, 294, 295)}
    assert counts == {0: 2, 128: 2, 129: 3, 294: 3, 295: 4}
    for m in LENGTHS:
        assert MK._schedule(m).squeezed == (0, 64)


# -- the tile over the device form ----------------------------------------


def _corrupt(case, pks, msgs, sigs, i):
    sig = sigs[i]
    if case == "flipped_sig_bit":
        sigs[i] = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
    elif case == "altered_message":
        msgs[i] = bytes([msgs[i][0] ^ 1]) + msgs[i][1:]
    elif case == "wrong_key":
        pks[i] = pks[i - 1]
    elif case == "noncanonical_s":
        s = int.from_bytes(sig[32:], "little") & ((1 << 255) - 1)
        sigs[i] = sig[:32] + (s + rst.L | (1 << 255)).to_bytes(32, "little")
    elif case == "no_v1_marker":
        sigs[i] = sig[:63] + bytes([sig[63] & 0x7F])
    else:
        assert case == "valid"


CASES = ("valid", "flipped_sig_bit", "altered_message", "wrong_key",
         "noncanonical_s", "no_v1_marker")  # fmt: skip


@pytest.mark.parametrize("case", CASES)
def test_tile_verdicts_through_the_device_form(device_form, case):
    pks, msgs, sigs = _signed(6)
    _corrupt(case, pks, msgs, sigs, 2)
    want = _oracle(pks, msgs, sigs)
    assert want == [case == "valid" or i != 2 for i in range(6)]
    v = SK.Sr25519Verifier([8, 32])
    got, spans = _traced(lambda: v.verify(pks, msgs, sigs))
    assert got.tolist() == want
    (merlin,) = _named(spans, "merlin_challenges")
    assert merlin.attrs == dict(merlin.attrs, form="device", n=6)
    launches = _named(spans, "device_launch")
    assert [s.attrs["program"] for s in launches] == ["merlin_challenge", "_verify_tile_sr"]
    assert launches[0].parent_id == merlin.span_id


def test_the_host_form_below_the_width(monkeypatch):
    """Below MERLIN_DEVICE_LANES nothing launches but the tile, and
    the verdicts are the device form's."""
    pks, msgs, sigs = _signed(6)
    _corrupt("flipped_sig_bit", pks, msgs, sigs, 4)
    v = SK.Sr25519Verifier([8, 32])
    host, spans = _traced(lambda: v.verify(pks, msgs, sigs))
    (merlin,) = _named(spans, "merlin_challenges")
    assert merlin.attrs == dict(merlin.attrs, form="host", n=6)
    assert [s.attrs["program"] for s in _named(spans, "device_launch")] == ["_verify_tile_sr"]
    monkeypatch.setattr(SK, "MERLIN_DEVICE_LANES", 8)
    assert v.verify(pks, msgs, sigs).tolist() == host.tolist() == _oracle(pks, msgs, sigs)


@pytest.mark.parametrize(
    "n, host", [(1, True), (128, True), (129, False), (2048, False), (10_000, False)]
)
def test_the_host_makes_the_challenges_below_the_width(n, host):
    """sr25519's operand is host work where the launch's bucket is
    narrower than MERLIN_DEVICE_LANES (128 lanes; 129 pads to 512);
    ed25519's SHA-512 never is."""
    from tendermint_tpu.ops.ed25519_kernel import Ed25519Verifier

    assert SK.MERLIN_DEVICE_LANES == 512
    assert SK.Sr25519Verifier().host_operand(n) is host
    assert Ed25519Verifier().host_operand(n) is False


@pytest.mark.parametrize("streaming", [False, True], ids=["one-launch", "streamed"])
@pytest.mark.parametrize("n", [100, 2 * 2048, 2 * 2048 + 100, 2 * 2048 + 904])
def test_the_seam_asks_for_its_narrowest_launch(monkeypatch, streaming, n):
    """Streamed, a class launches full chunks and then its remainder:
    the remainder's width decides. In one launch the whole batch's."""
    monkeypatch.setattr(T._TpuBatchVerifier, "_streaming", staticmethod(lambda: streaming))
    bv = T.TpuSr25519BatchVerifier(SK.Sr25519Verifier())
    remainder = n % 2048 if streaming and n > 2048 else n
    assert bv.host_operand(n) is (0 < remainder <= 128)


def test_a_mixed_length_batch_meets_on_the_host(monkeypatch):
    """A length group as wide as MERLIN_DEVICE_LANES takes a launch of
    its own, narrower ones the host's transcripts; the wides meet in
    one operand for one tile."""
    monkeypatch.setattr(SK, "MERLIN_DEVICE_LANES", 32)
    long_ = _signed(20, mlen=115, seed=0)
    short = _signed(3, mlen=21, seed=40)
    empty = _signed(2, mlen=0, seed=80)
    pks, msgs, sigs = (a + b + c for a, b, c in zip(long_, short, empty))
    _corrupt("flipped_sig_bit", pks, msgs, sigs, 7)
    _corrupt("altered_message", pks, msgs, sigs, 21)
    want = _oracle(pks, msgs, sigs)
    assert want.count(False) == 2
    v = SK.Sr25519Verifier([8, 32])
    got, spans = _traced(lambda: v.verify(pks, msgs, sigs))
    assert got.tolist() == want
    forms = sorted((s.attrs["form"], s.attrs["n"]) for s in _named(spans, "merlin_challenges"))
    assert forms == [("device", 20), ("host", 2), ("host", 3)]
    launches = [(s.attrs["program"], s.attrs["bucket"]) for s in _named(spans, "device_launch")]
    assert launches == [("merlin_challenge", 32), ("_verify_tile_sr", 32)]
    # the benchmark's reader: rows the device made over all rows
    read = harness.load_module("layer_metrics", "merlin_device_share").read
    assert read(types.SimpleNamespace(spans=spans, requests=1)) == pytest.approx(80.0)
    # a parent commit's spans carry no form: nothing to read
    for s in _named(spans, "merlin_challenges"):
        del s.attrs["form"]
    assert read(types.SimpleNamespace(spans=spans, requests=1)) is None
    assert read(types.SimpleNamespace(spans=[], requests=0)) is None


def test_over_a_four_device_mesh_the_challenges_stay_on_it(device_form):
    """The program runs a shard a chip, and its output is the tile's
    operand where it lies: the message rows are placed, the challenges
    are not, so a chunk places three host arrays as before."""
    from tendermint_tpu.ops.verifier import ROWS
    from tendermint_tpu.parallel import ShardedSr25519Verifier, make_mesh

    mesh = make_mesh(jax.devices()[:4])
    v = ShardedSr25519Verifier(mesh, [8, 32])
    pks, msgs, sigs = _signed(11)
    _corrupt("wrong_key", pks, msgs, sigs, 9)
    got, spans = _traced(lambda: v.verify(pks, msgs, sigs))
    assert got.tolist() == _oracle(pks, msgs, sigs)
    bucket = v._bucket(11)
    launches = {s.attrs["program"]: s for s in _named(spans, "device_launch")}
    assert set(launches) == {"merlin_challenge", "_verify_tile_sr"}
    places = _named(spans, "shard_place")
    assert len(places) == 3
    under = lambda prog: [s for s in places if s.parent_id == launches[prog].span_id]  # noqa: E731
    (rows,) = under("merlin_challenge")
    assert rows.attrs["bytes"] == (115 + 64) * bucket
    assert sorted(s.attrs["bytes"] for s in under("_verify_tile_sr")) == [32 * bucket, 64 * bucket]
    assert all(s.attrs["devices"] == 4 for s in places)
    wide = v._third_operand(pks, msgs, sigs, bucket, None)
    assert isinstance(wide, jax.Array) and wide.shape == (64, bucket)
    assert wide.sharding.mesh == mesh and wide.sharding.spec == ROWS


def _raising_merlin(rows):
    raise faults.DeviceFault("injected device fault at the merlin launch")


_raising_merlin.__name__ = "merlin_challenge"


@pytest.mark.parametrize("where", ["tpu.dispatch", "merlin_launch"])
def test_a_fault_on_the_merlin_launch_is_contained(device_form, monkeypatch, where):
    """A dispatch that faults, whether the seam's fault point or the
    merlin program's own launch, is re-verified on the CPU: the same
    bitmap, the same wrong-signature message out of verify_commit, and
    a fault counted against the batch."""
    from tendermint_tpu.types import InvalidCommitError, verify_commit

    from .test_sr25519 import _mixed_commit

    vals, commit, block_id, _privs, _order = _mixed_commit(3, 6)
    bad = next(i for i, v in enumerate(vals.validators) if v.pub_key.type() == "sr25519")
    sig = commit.signatures[bad].signature
    commit.signatures[bad].signature = sig[:5] + bytes([sig[5] ^ 1]) + sig[6:]
    pks, msgs, sigs = _signed(6)
    _corrupt("no_v1_marker", pks, msgs, sigs, 3)
    want = _oracle(pks, msgs, sigs)
    try:
        T.install(min_batch=2)
        with pytest.raises(InvalidCommitError) as clean:
            verify_commit("mixed-chain", vals, block_id, 5, commit)
        if where == "merlin_launch":
            monkeypatch.setattr(SK, "_MERLIN", _raising_merlin)
            armed = contextlib.nullcontext()
        else:
            armed = faults.inject("tpu.dispatch", mode="raise", key="sr25519")
        faults0 = T.stats()["faults"]
        with armed:
            bv = T.TpuSr25519BatchVerifier(SK.Sr25519Verifier([8, 32]))
            for pk, m, s in zip(pks, msgs, sigs):
                bv.add(PubKeySr25519(pk), m, s)
            assert bv.verify() == (False, want)
            assert bv.faulted
            with pytest.raises(InvalidCommitError) as faulted:
                verify_commit("mixed-chain", vals, block_id, 5, commit)
        assert str(faulted.value) == str(clean.value)
        assert f"#{bad}" in str(clean.value)
        assert T.stats()["faults"] >= faults0 + 1
    finally:
        T.uninstall()
