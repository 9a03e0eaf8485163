"""Rehearsals of the benchmark at a tiny size on the CPU backend: every
cell end to end, its control and its planted faults; the hits
accounting with the shape a node's catch-up will have; one traced
rehearsal a cell (conftest.py `traced`) that every reader's case reads;
the reducer, work.py, the plain sr25519 and the manifest.

Every case that runs a cell is in this one file, so that a run split by
file (`--dist loadfile`) loads each cell's programs in one worker only.
"""

from __future__ import annotations

import json
import os
import re
import types

import pytest

from chipbench import run as harness
from chipbench import trace_reduce, work
from chipbench.commit_driver import CommitDriver
from chipbench.reference import commit_verify as R
from chipbench.tests.rehearsal import CELLS, MANIFEST, ROOT, args, metric, rehearsing, tiny_of

# -- one traced rehearsal a cell, read by every reader's case ---------
# (first in the file: a cell's other cases then find its programs loaded)

COUNTED = ("sigcache_hit_share", "pad_waste_share", "window_compiles",
           "validation_host_ms", "dispatch_host_prep_ms")  # fmt: skip
OFF_CHIP = ("sigverify_roofline", "verify_mfu", "kernel_device_ms", "device_idle_share",
            "ladder_device_ms", "decode_points_device_ms")  # fmt: skip
SPAN_READ = (
    "signbytes_host_ms", "sigcache_host_ms", "commit_plan_host_ms", "batch_route_host_ms",
    "batch_add_host_ms", "cpu_disprove_host_ms", "pack_rows_host_ms", "launch_host_ms",
    "device_launches", "gather_wait_ms", "span_coverage_share",
)  # fmt: skip


def test_a_traced_run_reports_per_layer_metrics_only(traced, cell):
    run = traced(cell)
    assert run.result["correct"], run.result["checks"]
    assert run.result["failed"] == 0
    wanted = {m["name"] for m in MANIFEST["per_layer"] if cell in m.get("workloads", [cell])}
    assert set(run.metrics) <= wanted
    # counters and spans read on any backend; the device's own numbers
    # have nothing to read without a device plane and are left out
    for name in COUNTED:
        assert name in run.metrics, name
    assert run.metrics["sigcache_hit_share"]["value"] == 0
    assert run.metrics["window_compiles"]["value"] == 0
    for name in OFF_CHIP:
        assert name not in run.metrics, name
    assert run.result["device"]["memory_peak_device"] == 0
    json.dumps(run.result)


def test_the_rehearsal_of_each_cell_reports_the_span_read_metrics(traced, cell):
    """On the CPU backend every metric that reads the program's spans
    and needs no device is in the traced line of each cell. A cell with
    two key classes (the configuration's `key_classes`, not the cell's
    name) streams in the rehearsal as on the chip, so the streamed
    chunks' metric reads where the manifest lists the cell, and merlin's
    where it lists it; cell 1's and the light client's tiny batches
    stream nothing."""
    run = traced(cell)
    got = run.metrics
    mixed = len(run.config["key_classes"]) > 1
    for name in SPAN_READ:
        assert name in got, name
    assert ("stream_dispatch_host_ms" in got) == mixed
    assert ("merlin_host_ms" in got) == (mixed and cell in metric("merlin_host_ms")["workloads"])
    lo, hi = tiny_of(harness.load_cell(cell).traffic["driver"])["device_launches"]
    assert lo <= got["device_launches"]["value"] <= hi
    assert got["span_coverage_share"]["value"] > 90
    assert got["gather_wait_ms"]["value"] > 0


def test_a_traced_rehearsal_reads_100_and_0_with_the_library_disabled(traced, cell):
    from tendermint_tpu import native

    if native.commit_scan_lib() is None:
        pytest.skip("no native toolchain")
    assert traced(cell).metrics["decode_native_share"] == {"value": 100.0, "unit": "%"}
    generic = traced(cell, native=False)
    assert generic.result["correct"], generic.result["checks"]
    assert generic.result["failed"] == 0
    assert generic.metrics["decode_native_share"] == {"value": 0.0, "unit": "%"}


def test_a_traced_rehearsal_reads_the_cells_key_classes(traced, cell):
    run = traced(cell)
    classes = len(run.config["key_classes"])
    assert run.metrics["drain_overlapped_classes"] == {"value": float(classes), "unit": "count"}


def test_a_traced_rehearsal_reports_the_heap_and_no_settle_lands_in_the_window(traced, cell):
    run = traced(cell)
    frozen = run.metrics["heap_frozen_objects"]
    assert frozen["unit"] == "count" and frozen["value"] >= 100_000
    assert run.metrics["window_compiles"]["value"] == 0
    at_window_start, after = run.settles
    assert at_window_start >= 1 and after == at_window_start


# -- each cell end to end, its control and its planted faults ---------


def test_a_cell_runs_end_to_end_and_is_correct(tiny, cell):
    result = harness.run_cell(args(cell))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert list(result)[-1] == "checks"
    wanted = {m["name"] for m in MANIFEST["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == wanted
    assert result["checks"]["corrupted_requests_min"]["value"] >= 1
    json.dumps(result)


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
def test_a_broken_timed_path_is_not_correct(tiny, cell, fault, monkeypatch):
    plant, check = FAULTS[fault]
    result = harness.run_cell(args(cell), prepare=lambda d: plant(monkeypatch))
    assert not result["correct"]
    assert result["checks"][check]["value"] > 0
    assert result["failed"] > 0


def test_the_control_fails_the_comparison(tiny, cell):
    result = harness.run_cell(args(cell), prepare=lambda d: d.use_control())
    assert not result["correct"]
    assert (
        result["checks"]["verdict_mismatches"]["value"]
        == result["checks"]["corrupted_requests_min"]["value"]
        >= 1
    )


# -- hits held to the driver's count: the shape a catch-up will have --


class NoHits(CommitDriver):
    """What a node's catch-up does with one commit (upstream v0.35
    blocksync `poolRoutine`, then `validateBlock` on the next block's
    LastCommit; blocksync/reactor.py `_verify_apply`,
    state/execution.py `validate_block`): decode it and
    `verify_commit_light`; decode it afresh and `verify_commit`, which
    finds the first call's signatures in the cache. A commit that fails
    the first is not applied, so the second never runs. This one
    declares no hits, and is judged as a cold cell."""

    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        from tendermint_tpu.types import validation as V

        super().__init__(config, traffic, seed, light=True)
        self._full = V.verify_commit
        self.unchecked = len(self.ring.validators) - self.ring.checked

    def run(self, token: tuple, annotate=None) -> str:
        verdict = super().run(token, annotate)
        if verdict != "ok":
            return verdict
        slot = token[0]
        light, self._entry = self._entry, self._full
        try:
            return self._verify(slot, self._decode(self.ring.wire[slot]))
        finally:
            self._entry = light

    def expected(self, tokens: list) -> list:
        clean = sorted({t for t in tokens if not t[1]})
        self.reference.prime([self._commit(t) for t in clean], False)
        full = {t: self.reference.verdict(self._commit(t), False) for t in clean}
        return [full[t] if t in full and v == "ok" else v for t, v in zip(tokens, super().expected(tokens))]

    def sent(self, token: tuple, min_batch: int, chunk) -> tuple:
        batches, sigs = super().sent(token, min_batch, chunk)
        return (batches, sigs) if token[1] else (batches + 1, sigs + self.unchecked)



class LightThenFull(NoHits):
    def hits(self, token: tuple) -> tuple:
        return (0, 0) if token[1] else (self.ring.checked, 0)


class OneTooMany(LightThenFull):
    def hits(self, token: tuple) -> tuple:
        return (0, 0) if token[1] else (self.ring.checked + 1, 0)


class OneTooFew(LightThenFull):
    def hits(self, token: tuple) -> tuple:
        return (0, 0) if token[1] else (self.ring.checked - 1, 0)


# 25 validators: the light tally checks 17 (a 32-lane bucket) and the
# full one finds them and sends the other 8, the install's min_batch (an
# 8-lane bucket): 17 hits, two dispatches, 25 signatures a clean request,
# as commit-150.catchup-node's 101, two and 150. 11 x 25 insertions
# between two visits of a ring slot pass two generations of 40, and a
# generation holds a request's 17 until its second call.
CATCHUP_VALIDATORS = 25


@pytest.mark.parametrize(
    "driver, correct",
    [(LightThenFull, True), (NoHits, False), (OneTooMany, False), (OneTooFew, False)],
    ids=lambda p: getattr(p, "__name__", None),
)
def test_hits_are_held_to_the_drivers_count_exactly(monkeypatch, driver, correct):
    real = harness.load_module

    def load_module(subdir, name):
        if subdir != "drivers":
            return real(subdir, name)
        return types.SimpleNamespace(setup=driver)

    monkeypatch.setattr(harness, "load_module", load_module)
    with rehearsing(monkeypatch, validators=CATCHUP_VALIDATORS):
        result = harness.run_cell(args(CELLS[0]))
    checks = result["checks"]
    assert checks["verdict_mismatches"]["value"] == 0
    assert checks["warmup_verdict_mismatches"]["value"] == 0
    assert checks["corrupted_requests_min"]["value"] >= 1
    assert result["correct"] == correct, checks
    clean = result["attempted"] - checks["corrupted_requests_min"]["value"]
    if correct:
        assert checks["bypassed_requests"]["value"] == 0 and result["failed"] == 0
    else:
        # every clean request: the corrupted ones stop at the light call, with no hit due
        assert checks["bypassed_requests"]["value"] == result["failed"] == clean > 0


def test_a_driver_without_hits_is_held_to_none():
    """judge on a window made by hand: the three drivers there are
    judged as before, a hit in either counter fails the request, and so
    does a hit the driver declared that did not come."""
    at = dict.fromkeys(harness.COUNTERS, 0)

    def window(**moved):
        after = dict(at, batches=1, sigs=9, **moved)
        return {"tokens": [(0, False)], "verdicts": ["ok"], "starts": [0.0], "ends": [1.0],
                "t_open": 0.0, "counters": [tuple(at.values()), tuple(after.values())]}  # fmt: skip

    cold = types.SimpleNamespace(expected=lambda tokens: ["ok"], sent=lambda *a: (1, 9))
    warm = types.SimpleNamespace(expected=cold.expected, sent=cold.sent, hits=lambda token: (9, 1))
    log = types.SimpleNamespace(between=lambda t0, t1: [])
    installed = {"min_batch": 8, "chunk": None}

    def bypassed(driver, **moved):
        return harness.judge(driver, window(**moved), installed, log)["bypassed"]

    assert bypassed(cold) == []
    assert bypassed(cold, cache_hits=1) == [0] and bypassed(cold, memo_hits=1) == [0]
    assert bypassed(warm, cache_hits=9, memo_hits=1) == []
    assert bypassed(warm) == [0] and bypassed(warm, cache_hits=9) == [0]
    assert bypassed(warm, cache_hits=10, memo_hits=1) == [0]


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
    """One device plane: every number as recorded, to the digit (the
    idle gaps of all planes, averaged, are the one plane's own)."""
    path = os.path.join(harness.HERE, "testdata", "commit-150.xplane.pb.gz")
    got = trace_reduce.reduce_file(path)
    expected = harness.load_json(os.path.join(harness.HERE, "testdata", "commit-150.reduced.json"))
    assert got["requests"] == expected["requests"] > 0 and got["devices"] == 1
    assert got["busy_s"] == pytest.approx(expected["busy_s"])
    assert got["program_s"] == pytest.approx(expected["program_s"])
    assert got["window_s"] == pytest.approx(expected["window_s"])
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["device_ops"][0][0] == expected["device_ops"][0][0]
    assert got["idle_gaps"] == expected["idle_gaps"]
    assert sum(s for _n, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)


def _plane(name: str, **lines):
    """A profiler plane as trace_reduce reads one: lines of events with
    a start, a duration (ns) and a name."""
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=line.replace("_", " "), events=[
            types.SimpleNamespace(start_ns=s, duration_ns=d, name=n) for s, d, n in events
        ])
        for line, events in lines.items()
    ])  # fmt: skip


def test_idle_gaps_are_the_mean_over_every_plane_that_ran_anything():
    """Two chips and one request of 100 ns: the host decodes for 40 and
    verifies for 60. The first chip is busy 10..30 and idle while the
    second works, 50..90; a third plane ran nothing and does not count."""
    host = _plane("/host:CPU", python3=[
        (0, 100, "cb_request"), (0, 40, "cb_decode"), (40, 60, "cb_entry"),
    ])  # fmt: skip
    first = _plane("/device:TPU:0", XLA_Ops=[(10, 20, "%fusion.1 = fusion()")],
                   XLA_Modules=[(10, 20, "jit_tile(1)")])  # fmt: skip
    second = _plane("/device:TPU:1", XLA_Ops=[(50, 40, "%fusion.1 = fusion()")],
                    XLA_Modules=[(50, 40, "jit_tile(1)")])  # fmt: skip
    unused = _plane("/device:TPU:2", XLA_Ops=[])
    got = trace_reduce.reduce(types.SimpleNamespace(planes=[host, first, second, unused]))
    assert got["devices"] == 2 and got["requests"] == 1
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx((20 + 40) / 2 * 1e-9)
    assert got["program_s"] == pytest.approx(60e-9)
    # first: decode 0..10 and 30..40, entry 40..100; second: decode 0..40, entry 40..50, 90..100
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"cb_entry": (60 + 20) / 2 * 1e-9, "cb_decode": (20 + 40) / 2 * 1e-9})
    assert [name for name, _s in got["idle_gaps"]] == ["cb_entry", "cb_decode"]
    assert sum(s for _n, s in got["idle_gaps"]) == pytest.approx(got["window_s"] - got["busy_s"])
    # the first plane alone: what the reducer named before it read them all
    alone = trace_reduce.reduce(types.SimpleNamespace(planes=[host, first]))
    assert dict(alone["idle_gaps"]) == pytest.approx({"cb_entry": 60e-9, "cb_decode": 20e-9})


@pytest.mark.parametrize("config, share", [({"devices": 1}, 40.0), ({"devices": 4}, 10.0), ({}, 40.0)],
                         ids=["one-device", "four-devices", "names-none"])  # fmt: skip
def test_verify_mfu_is_over_the_ceiling_the_deployment_has(config, share):
    """Two requests of 1e9 multiply-adds in a 5 ms window under a
    ceiling of 1e12 a chip: 40% of one chip, 10% of four. The host's
    count is not asked: a one-device deployment on a four-chip host
    keeps its reading."""
    ctx = types.SimpleNamespace(
        config=config, tokens=[(0, False)], work=None,
        trace={"requests": 2, "window_s": 5e-3, "program_s": 1e-3},
        driver=types.SimpleNamespace(work=lambda token, work: {"madds": 10**9, "bytes": 212}),
        peaks={"int32_madd_per_s": {"value": 1e12}, "hbm_bytes_per_s": {"value": 8.19e11}},
    )  # fmt: skip
    assert harness.load_module("layer_metrics", "verify_mfu").read(ctx) == pytest.approx(share)
    ctx.trace = None
    assert harness.load_module("layer_metrics", "verify_mfu").read(ctx) is None


def test_the_fullest_chip_is_named(monkeypatch):
    monkeypatch.setattr(harness, "_memory_peaks", lambda: [5, 9, 7, 9])
    assert harness.memory_peak_bytes() == 9 and harness.memory_peak_device() == 1


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
