"""Rehearsals of the benchmark at a tiny size on the CPU backend.

The TPU requirement is lifted HERE, by replacing run.require_tpu; it is
never an option of run.py. Nothing these tests print is a speed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import types

import pytest

import run as harness
from chipbench import trace_reduce, work
from chipbench.reference import commit_verify as R

ROOT = harness.ROOT
MANIFEST = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))

# `cache` is the verified-signature cache's capacity a generation, cut
# with the ring so that the rehearsal stays cold as the cells are:
# (ring - 1) x checked signatures must pass two generations
TINY = {
    "verify_commit_light": {"validators": 13, "ring_commits": 12, "corrupt_every": 4, "cache": 40},
    "verify_commit": {"validators": 16, "ring_commits": 3, "corrupt_every": 4, "cache": 16},
}


@pytest.fixture
def tiny(monkeypatch):
    """Each cell as the manifest has it, with the deployment's scale
    and the ring cut so that a CPU holds it; the look for a chip is
    skipped."""
    from tendermint_tpu.crypto import sigcache

    real = harness.load_cell

    def load_cell(name):
        cell = real(name)
        cut = TINY[cell.traffic["driver"]]
        sigcache.reset()
        sigcache.set_capacity(cut["cache"])
        cell.config = dict(cell.config, validators=cut["validators"])
        cell.traffic = dict(
            cell.traffic, ring_commits=cut["ring_commits"], warmup_commits=1,
            corrupt_every=cut["corrupt_every"], trace_requests=3,
        )
        return cell

    monkeypatch.setattr(harness, "load_cell", load_cell)
    monkeypatch.setattr(
        harness, "require_tpu",
        lambda chips: {"platform": "cpu", "kind": "rehearsal", "count": chips},
    )
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 0)
    yield
    sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)
    sigcache.reset()


def _args(cell, trace=0, seconds=1.5, seed=2_147_483_659):
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace)


CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_runs_end_to_end_and_is_correct(tiny, cell):
    result = harness.run_cell(_args(cell))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert list(result)[-1] == "checks"
    wanted = {m["name"] for m in MANIFEST["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == wanted
    assert result["checks"]["corrupted_requests_min"]["value"] >= 1
    json.dumps(result)


@pytest.mark.parametrize("cell", CELLS[:1])
def test_a_traced_run_reports_per_layer_metrics_only(tiny, cell, monkeypatch):
    # no peaks for a rehearsal device: lend it the v5e's row
    peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    real = harness.load_json
    monkeypatch.setattr(
        harness, "load_json",
        lambda p: {"rehearsal": peaks["TPU v5 lite"]} if p.endswith("peaks.json") else real(p),
    )
    result = harness.run_cell(_args(cell, trace=1))
    assert result["correct"], result["checks"]
    names = {m["name"] for m in MANIFEST["per_layer"]}
    assert set(result["metrics"]) <= names
    # counters and spans read on any backend; the device's own numbers
    # have nothing to read without a device plane and are left out
    for name in ("sigcache_hit_share", "pad_waste_share", "window_compiles",
                 "validation_host_ms", "dispatch_host_prep_ms"):
        assert name in result["metrics"], name
    assert result["metrics"]["sigcache_hit_share"]["value"] == 0
    assert result["metrics"]["window_compiles"]["value"] == 0
    for name in ("sigverify_roofline", "kernel_device_ms", "device_idle_share"):
        assert name not in result["metrics"], name


def _patch_gather(monkeypatch, alter):
    """Alter the device's bitmap where it is produced, in both kernels."""
    from tendermint_tpu.ops import ed25519_kernel, sr25519_kernel

    for cls in (ed25519_kernel.Ed25519Verifier, sr25519_kernel.Sr25519Verifier):
        real = cls.gather
        monkeypatch.setattr(
            cls, "gather", lambda self, h, real=real: alter(real(self, h))
        )


def _all_lanes_true(monkeypatch):
    """An answer altered where it is produced: the device's bitmap
    reports every lane valid."""
    import numpy as np

    _patch_gather(monkeypatch, np.ones_like)


def _device_route_open(monkeypatch):
    """The device bypassed: the batch routes' breakers are open, so the
    CPU answers, correctly."""
    from tendermint_tpu.crypto import breaker

    for name in ("ed25519", "sr25519"):
        for _ in range(10):
            breaker.breaker_for(name).record_failure()


FAULTS = {
    "bitmap-all-true": (_all_lanes_true, "verdict_mismatches"),
    "device-bypassed": (_device_route_open, "bypassed_requests"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(tiny, cell, fault, monkeypatch):
    plant, check = FAULTS[fault]
    result = harness.run_cell(_args(cell), prepare=lambda d: plant(monkeypatch))
    assert not result["correct"]
    assert result["checks"][check]["value"] > 0
    assert result["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_comparison(tiny, cell):
    result = harness.run_cell(_args(cell), prepare=lambda d: d.use_control())
    assert not result["correct"]
    assert (
        result["checks"]["verdict_mismatches"]["value"]
        == result["checks"]["corrupted_requests_min"]["value"]
        >= 1
    )


def test_without_a_tpu_the_command_refuses_and_prints_no_result(capsys):
    code = harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert code != 0 and out.out == "" and "needs" in out.err


def test_reference_encodes_what_the_program_signs():
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.canonical import PRECOMMIT_TYPE, vote_sign_bytes

    commit = {"height": 1_000_007, "round": 0, "block_hash": b"\x11" * 32,
              "parts_total": 1, "parts_hash": b"\x22" * 32}
    ns = 1_700_000_123 * 10**9 + 400_000_001
    bid = BlockID(hash=commit["block_hash"],
                  part_set_header=PartSetHeader(total=1, hash=commit["parts_hash"]))
    assert R.sign_bytes(R.sign_bytes_parts("c", commit), ns) == vote_sign_bytes(
        "c", PRECOMMIT_TYPE, commit["height"], 0, bid, ns
    )


def test_plain_sr25519_agrees_with_the_program():
    """The reference's own schnorrkel and the program's CPU one accept
    each other's signatures and refuse each other's forgeries."""
    from chipbench.reference import sr25519_plain as S
    from tendermint_tpu.crypto.sr25519 import PrivKeySr25519, PubKeySr25519

    secrets = [S.secret_scalar(bytes([i]) * 32) for i in range(6)]
    pubs = S.public_keys(secrets)
    msgs = [bytes([i]) * 115 for i in range(6)]
    sigs = S.sign_many(secrets, pubs, msgs)
    for pub, msg, sig in zip(pubs, msgs, sigs):
        assert PubKeySr25519(pub).verify_signature_cpu(msg, sig)
        assert not PubKeySr25519(pub).verify_signature_cpu(msg[:-1] + b"x", sig)
    bad = bytes([sigs[2][0] ^ 1]) + sigs[2][1:]
    triples = list(zip(pubs, msgs, sigs[:2] + [bad] + sigs[3:]))
    assert S.verify_many(triples) == [True, True, False, True, True, True]
    priv = PrivKeySr25519.from_seed(b"\x05" * 32)
    sig = priv.sign(b"a vote")
    assert S.verify_one(priv.pub_key().bytes(), b"a vote", sig)
    assert not S.verify_one(priv.pub_key().bytes(), b"a vole", sig)


def test_pool_maps_chunks_in_order():
    from chipbench import pool
    from chipbench.reference import sr25519_plain as S

    secrets = [S.secret_scalar(i.to_bytes(2, "little") * 16) for i in range(300)]
    assert pool.map_chunks(S.public_keys, secrets, 64) == S.public_keys(secrets)


def test_work_counts_against_a_hand_worked_case():
    # 1519 squarings of 210 and 2060 multiplications of 400 limb products
    assert work.CURVE_MADDS == 1519 * 210 + 2060 * 400 == 1_142_990
    # a 115-byte sign-bytes: 64 + 115 + 17 = 196 bytes -> 2 SHA-512 blocks
    assert work.per_signature("ed25519", 115) == {
        "madds": 1_142_990 + 2 * 9_600, "bytes": 32 + 64 + 115 + 1}
    # merlin: 40 + 0 + 64 + 115 = 219 bytes -> 2 permutations, +1 to squeeze
    assert work.per_signature("sr25519", 115)["madds"] == 1_142_990 + 3 * 7_200
    peaks = {"int32_madd_per_s": {"value": 1e12}, "hbm_bytes_per_s": {"value": 8.19e11}}
    least, bound = work.least_seconds(1_162_190 * 101, 212 * 101, peaks)
    assert bound == "compute" and least == pytest.approx(1.1738119e-4)


def test_reducer_on_the_recorded_trace():
    path = os.path.join(harness.HERE, "testdata", "commit-150.xplane.pb.gz")
    got = trace_reduce.reduce_file(path)
    expected = harness.load_json(os.path.join(harness.HERE, "testdata", "commit-150.reduced.json"))
    assert got["requests"] == expected["requests"] > 0
    assert got["busy_s"] == pytest.approx(expected["busy_s"])
    assert got["program_s"] == pytest.approx(expected["program_s"])
    assert got["window_s"] == pytest.approx(expected["window_s"])
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["device_ops"][0][0] == expected["device_ops"][0][0]
    assert sum(s for _n, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)


def test_reducer_intervals():
    assert trace_reduce.union_length([(0, 4), (2, 6), (10, 11)]) == 7
    gaps = trace_reduce.gaps([(2, 4), (6, 7)], 0, 10)
    assert gaps == [(0, 2), (4, 6), (7, 10)]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_names_units_and_moves():
    m = MANIFEST
    metrics = m["end_to_end"] + m["per_layer"]
    names = (
        [x["name"] for x in metrics + m["workloads"] + m["configs"]]
        + [w["config"] for w in m["workloads"]]
        + [w["traffic"] for w in m["workloads"]]
        + [k for c in m["configs"] for k in c["reduced"]]
    )
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(x["unit"]) for x in metrics)
    assert len({x["name"] for x in metrics}) == len(metrics)
    texts = (
        [x["why"] for x in m["workloads"] + m["configs"]]
        + [c["source"] for c in m["configs"]]
        + [x["layer"] for x in m["per_layer"]]
        + m["command"]
    )
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t for t in texts)
    cells =[w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x.get("workloads", cells) for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for layer in m["per_layer"]:
        for cell in layer.get("workloads", cells):
            assert cell in e2e[layer["moves"]], (layer["name"], cell)
        assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", layer["name"] + ".py"))
    for c in m["configs"]:
        assert c["file"].startswith("chipbench/") and os.path.exists(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in m["workloads"])
        assert set(c["reduced"]) == set(harness.load_json(os.path.join(ROOT, c["file"]))["reduced"])
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(cells) // 2)
