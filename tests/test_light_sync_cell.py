"""The benchmark's light-client cell, `light-150.sync`, rehearsed on the
CPU backend: its data against the program's own hashes and decoder, the
plain reference against the program on clean and corrupted syncs, the
cell end to end through `chipbench/run.run_cell` with the look for a
chip replaced in the test (never in run.py), the control and two
planted faults, which must not come out correct, and the readers of the
`light client` layer's metrics on spans made by hand.

The merged windows run because the group affinity is pinned here to the
rehearsal's own tiny window (an install decides it from the backend).
Nothing these tests print is a speed.
"""

from __future__ import annotations

import argparse
import json
import os
import types

import pytest

from chipbench import light_gen
from chipbench import run as harness
from chipbench.drivers import light_sync
from chipbench.reference import light_verify as L

CELL = "light-150.sync"
MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
LIGHT_METRICS = (
    "light_window_hops", "light_fetch_host_ms", "light_header_checks_host_ms",
    "light_store_host_ms", "light_fallback_hops",
)  # fmt: skip
# 13 validators: the light tally checks 9, so a 3-hop window is 27
# signatures, inside the 32-lane bucket the suite's other rehearsals
# compile. `cache` is the verified-signature cache's capacity a
# generation, cut with the ring so the rehearsal stays cold as the cell
# is: 3 segments x (6 x 9 triples + 6 memos) = 180 insertions between
# two visits of a segment, more than two generations of 60
TINY = {
    "validators": 13, "headers_per_sync": 6, "window_hops": 3, "ring_segments": 4,
    "corrupt_every": 4, "first_corrupted_in": [1, 3], "cache": 60,
}  # fmt: skip
SEED = 2_147_483_659


def _cut(config: dict, traffic: dict) -> tuple:
    config = dict(config, validators=TINY["validators"], headers_per_sync=TINY["headers_per_sync"])
    traffic = dict(
        traffic, window_hops=TINY["window_hops"], ring_segments=TINY["ring_segments"],
        corrupt_every=TINY["corrupt_every"], first_corrupted_in=TINY["first_corrupted_in"],
        trace_requests=3,
    )  # fmt: skip
    return config, traffic


def _files() -> tuple:
    return (
        harness.load_json(os.path.join(harness.HERE, "configs", "light-150.json")),
        harness.load_json(os.path.join(harness.HERE, "traffic", "sync.json")),
    )


@pytest.fixture
def windows():
    """The merged-window path at the rehearsal's window, and the cache
    cut with the ring."""
    from tendermint_tpu.crypto import batch, sigcache

    state = batch.group_affinity_state()
    batch.set_group_affinity(TINY["window_hops"])
    sigcache.reset()
    sigcache.set_capacity(TINY["cache"])
    yield
    batch.restore_group_affinity(state)
    sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)
    sigcache.reset()


@pytest.fixture
def tiny(monkeypatch, windows):
    """The cell as the manifest has it, its scale and ring cut so that
    a CPU holds it."""
    from tendermint_tpu.crypto import breaker, tpu_verifier
    from tendermint_tpu.ops import merkle_kernel

    real = harness.load_cell

    def load_cell(name):
        cell = real(name)
        cell.config, cell.traffic = _cut(cell.config, cell.traffic)
        return cell

    peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    load_json = harness.load_json
    monkeypatch.setattr(harness, "load_cell", load_cell)
    monkeypatch.setattr(
        harness, "require_tpu",
        lambda chips: {"platform": "cpu", "kind": "rehearsal", "count": chips},
    )  # fmt: skip
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 0)
    # no peaks for a rehearsal device: lend it the v5e's row
    monkeypatch.setattr(
        harness, "load_json",
        lambda p: {"rehearsal": peaks["TPU v5 lite"]} if p.endswith("peaks.json") else load_json(p),
    )  # fmt: skip
    yield
    tpu_verifier.uninstall()
    merkle_kernel.uninstall()
    breaker.reset_all()


def _args(trace=0, seconds=3.0):
    return argparse.Namespace(workload=CELL, seed=SEED, seconds=seconds, trace=trace)


# -- the manifest -------------------------------------------------------


def test_the_manifest_has_the_cell_its_configuration_and_its_readers():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]  # by name: later PRs append
    # four chips for steadiness alone (PERF.md, PR 32): the deployment
    # itself is one device, and no more than half the cells ask for four
    assert cell == dict(cell, name=CELL, config="light-150", traffic="sync", chips=4)
    assert len(cell["why"]) <= 200 and "64" in cell["why"] and "steadiness" in cell["why"]
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= len(MANIFEST["workloads"]) // 2
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "light-150"]
    config, traffic = _files()
    assert entry["name"] == config["name"] == "light-150"
    assert entry["file"] == "chipbench/configs/light-150.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == ["headers_per_sync"]
    assert config["headers_per_sync"] == 64 and config["published"]["headers_per_sync"] == 10_000
    assert config["reference"] == "light_verify" and config["validators"] == 150
    assert config["devices"] == 1
    assert len(config["chain_id"]) == 13 and len(config["guarantees"]) == 4
    assert traffic["driver"] == "light_sync" and traffic["window_hops"] == 32
    assert (traffic["ring_segments"], traffic["warmup_segments"], traffic["corrupt_every"]) == (16, 2, 32)
    new = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == list(LIGHT_METRICS)
    at = MANIFEST["per_layer"].index(new[0])
    assert MANIFEST["per_layer"][at : at + len(new)] == new  # added as one block
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "commits_per_s"
        assert m["layer"] == "light client (light/client.py, light/verifier.py)"
        assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", m["name"] + ".py"))
    # the ring stays cold: the insertions between two visits of a
    # segment pass the two generations the triples and the memos share
    from tendermint_tpu.crypto import sigcache

    a_sync = config["headers_per_sync"] * (101 + 1)
    assert (traffic["ring_segments"] - 1) * a_sync > 2 * 2 * sigcache.DEFAULT_CAPACITY


# -- the data -----------------------------------------------------------


@pytest.fixture(scope="module")
def chain():
    return light_gen.Chain(*_cut(*_files()), SEED)


def test_the_generators_hashes_and_wire_bytes_are_the_programs(chain):
    from tendermint_tpu.types.light import LightBlock

    assert chain.checked == 9 and chain.sign_bytes_len == 115
    for block, wire in list(zip(chain.blocks, chain.wire))[:: len(chain.blocks) // 5]:
        lb = LightBlock.from_proto(wire)
        header = lb.signed_header.header
        assert header.hash() == block["header"]["hash"] == L.header_hash(block["header"])
        assert lb.validator_set.hash() == L.validators_hash(chain.validators)
        assert header.validators_hash == header.next_validators_hash == lb.validator_set.hash()
        assert lb.to_proto() == wire
        lb.validate_basic(chain.chain_id)
        assert [v.pub_key.bytes() for v in lb.validator_set.validators] == [
            v["pub"] for v in chain.validators
        ]
    # the chain chains, and neighbouring segments share one header
    for before, after in zip(chain.blocks, chain.blocks[1:]):
        assert after["header"]["last_block_hash"] == before["header"]["hash"]
        assert after["header"]["height"] == before["header"]["height"] + 1
    assert chain.span(0)[1] == chain.span(1)[0]
    # a corrupted variant differs from its header's block in one bit
    for segment in (chain.segment(chain.corrupt_offset), chain.n_ring + chain.n_warm - 1):
        at, block, wire = chain.bad_variant(segment)
        first, last = chain.span(segment)
        assert first < at <= last
        diff = [a ^ b for a, b in zip(wire, chain.wire[at])]
        assert len(wire) == len(chain.wire[at]) and sum(bin(d).count("1") for d in diff) == 1
        assert block["header"] is chain.blocks[at]["header"]


def test_merkle_root_against_the_programs_on_every_small_size():
    from tendermint_tpu.crypto import merkle

    for n in range(0, 20):
        items = [bytes([i]) * (i + 1) for i in range(n)]
        assert L.merkle_root(items) == merkle.hash_from_byte_slices(items)


# -- the reference and the program --------------------------------------


def test_the_reference_and_the_program_agree_on_clean_and_corrupted_syncs(windows):
    driver = light_sync.setup(*_cut(*_files()), SEED)
    tokens = driver.warmup_requests() + [driver.window_request(i) for i in range(9)]
    assert [t[1] for t in tokens].count(True) == 1 + 2
    got = [driver.run(t) for t in tokens]
    assert got == driver.expected(tokens)
    bad = tokens[1]
    at = driver.chain.bad_variant(bad[0])[0]
    height = driver.chain.blocks[at]["header"]["height"]
    root = driver.chain.blocks[driver.chain.span(bad[0])[0]]["header"]["height"]
    index = int(driver.chain.bad_index[bad[0]])
    assert got[1] == f"wrong_signature:{height}#{index};stored={root}-{height - 1}"
    assert got[0].startswith(f"ok:{root}:") and got[0].endswith(f";stored={root - 6}-{root}")
    # one decode entry a request; a corrupted sync reaches its bad window only
    assert len(driver.decode_s) == len(tokens) and min(driver.decode_s) > 0
    assert driver.sent(tokens[0], 8, None) == (2, 54)
    reached = (at - driver.chain.span(bad[0])[0] - 1) // 3 + 1  # windows up to the bad hop's
    assert driver.sent(bad, 8, None) == (reached, 27 * reached)
    assert driver.sent(tokens[0], 8, 20) == (4, 54)
    per_signature = types.SimpleNamespace(per_signature=lambda kind, n: {"madds": 7, "bytes": n})
    need = driver.work(tokens[0], per_signature)
    assert need == {"madds": 7 * 54, "bytes": 115 * 54}


def test_the_reference_names_other_faults_than_signatures(chain):
    reference = L.Reference(chain.chain_id, chain.validators, 14 * 86400 * 10**9, 10**10, chain.now_ns)
    blocks = chain.segment_blocks(0, False)
    trust = blocks[0]["header"]["hash"]
    root = blocks[0]["header"]["height"]
    assert reference.verdict(blocks, trust).startswith(f"ok:{root + 6}:")
    assert reference.verdict(blocks, b"\x00" * 32) == f"invalid:{root}:trust_root;stored="
    late = [dict(b) for b in blocks]
    late[3] = dict(late[3], header=dict(late[3]["header"], next_validators_hash=b"\x01" * 32))
    # the header no longer hashes to what its commit signs
    assert reference.verdict(late, trust) == (
        f"invalid:{root + 3}:commit_signs_another_header;stored={root}-{root + 2}"
    )
    gap = blocks[:2] + blocks[3:]
    assert reference.verdict(gap, trust) == f"invalid:{root + 3}:not_adjacent;stored={root}-{root + 1}"
    old = L.Reference(chain.chain_id, chain.validators, 1, 10**10, chain.now_ns)
    assert old.verdict(blocks, trust) == f"invalid:{root}:trust_root_expired;stored="
    control = reference.verdict(chain.segment_blocks(chain.n_ring + 1, True), chain.blocks[
        chain.span(chain.n_ring + 1)[0]]["header"]["hash"], check_signatures=False)
    assert control.startswith("ok:")


# -- the cell, end to end -----------------------------------------------


def test_the_cell_rehearses_correct(tiny):
    result = harness.run_cell(_args())
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["checks"]["corrupted_requests_min"]["value"] >= 1
    assert result["checks"]["bypassed_requests"]["value"] == 0
    assert set(result["metrics"]) == {"commits_per_s", "verify_p95_ms", "setup_s"}
    assert list(result)[-1] == "checks"
    json.dumps(result)


def test_the_control_fails_the_cells_comparison(tiny):
    result = harness.run_cell(_args(), prepare=lambda d: d.use_control())
    assert not result["correct"]
    assert (
        result["checks"]["verdict_mismatches"]["value"]
        == result["checks"]["corrupted_requests_min"]["value"]
        >= 1
    )


def _all_lanes_true(monkeypatch):
    """The device's bitmap altered where it is produced: every lane
    reads valid."""
    import numpy as np

    from tendermint_tpu.ops import ed25519_kernel

    real = ed25519_kernel.Ed25519Verifier.gather
    monkeypatch.setattr(
        ed25519_kernel.Ed25519Verifier, "gather", lambda self, h: np.ones_like(real(self, h))
    )


def _device_route_open(monkeypatch):
    """The device bypassed: the batch route's breaker is open, so the
    CPU answers, correctly."""
    from tendermint_tpu.crypto import breaker

    for _ in range(10):
        breaker.breaker_for("ed25519").record_failure()


FAULTS = {
    "bitmap-all-true": (_all_lanes_true, "verdict_mismatches"),
    "device-bypassed": (_device_route_open, "bypassed_requests"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(tiny, fault, monkeypatch):
    plant, check = FAULTS[fault]
    result = harness.run_cell(_args(), prepare=lambda d: plant(monkeypatch))
    assert not result["correct"]
    assert result["checks"][check]["value"] > 0 and result["failed"] > 0


def test_a_traced_rehearsal_reports_the_light_clients_metrics(tiny):
    result = harness.run_cell(_args(trace=1))
    assert result["correct"], result["checks"]
    got = result["metrics"]
    wanted = {m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(got) <= wanted and set(LIGHT_METRICS) <= set(got)
    assert got["light_window_hops"] == {"value": 3.0, "unit": "count"}
    assert got["light_fallback_hops"] == {"value": 0.0, "unit": "count"}
    for name in ("light_fetch_host_ms", "light_header_checks_host_ms", "light_store_host_ms",
                 "decode_host_ms", "validation_host_ms", "dispatch_host_prep_ms", "gather_wait_ms"):
        assert got[name]["value"] > 0, name
    assert got["sigcache_hit_share"]["value"] == 0 and got["window_compiles"]["value"] == 0
    assert got["drain_overlapped_classes"]["value"] == 1
    # every merged window crossed the seam as columns
    assert got["bulk_add_share"] == {"value": 100.0, "unit": "%"}
    # a tile and its SHA-512 a window, two windows a clean sync
    assert 3 < got["device_launches"]["value"] <= 4
    # 27 signatures in a 32-lane bucket
    assert got["pad_waste_share"]["value"] == pytest.approx(100 * 5 / 32)
    for name in ("sigverify_roofline", "verify_mfu", "kernel_device_ms", "device_idle_share",
                 "stream_dispatch_host_ms", "merlin_host_ms", "lanes_per_chip"):
        assert name not in got, name
    # every accepted metric that lists no cells has to be in a traced
    # line of this cell too; a CPU rehearsal lacks only the device's,
    # and the set-up's compiles where the process had the programs
    unlisted = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    off_chip = {"sigverify_roofline", "verify_mfu", "kernel_device_ms", "device_idle_share",
                "ladder_device_ms", "decode_points_device_ms", "setup_cache_load_s",
                "gather_device_idle_ms"}  # fmt: skip
    assert unlisted - off_chip - set(got) == set()
    # the gather's split adds up to its wait, less collections in the job
    split = [got[m]["value"] for m in ("gather_handoff_ms", "gather_ready_ms", "gather_fetch_ms")]
    assert min(split) >= 0 and sum(split) <= got["gather_wait_ms"]["value"] * (1 + 1e-9)
    assert got["signbytes_host_ms"]["value"] > 0 and got["commit_plan_host_ms"]["value"] > 0
    assert all(isinstance(m["value"], (int, float)) for m in got.values())
    json.dumps(result)


# -- the readers, on spans made by hand ---------------------------------


def _reader(metric: str):
    return harness.load_module("layer_metrics", metric).read


def _ctx(spans=(), requests=1):
    return types.SimpleNamespace(spans=list(spans), requests=requests)


def _span(sid, name, start, dur, parent=0, **attrs):
    return types.SimpleNamespace(
        span_id=sid, name=name, start_us=float(start), dur_us=float(dur),
        parent_id=parent, root_id=1, attrs=attrs,
    )  # fmt: skip


def _a_sync(fallback=False):
    """One sync of two windows as the program's spans draw it: fetches
    whose decodes are spans of their own, header checks around merkle
    roots, the merged verification, the saves."""
    spans = [
        _span(2, "light_fetch", 0, 300, parent=1, first=9, last=9, bulk=False),
        _span(3, "commit_decode", 100, 100, parent=2),
        _span(4, "light_fetch", 300, 900, parent=1, first=2, last=4, bulk=True),
        _span(5, "commit_decode", 400, 300, parent=4),
        _span(7, "light_header_checks", 1200, 500, parent=6, hops=3),
        _span(8, "merkle_hash", 1300, 200, parent=7, leaves=14),
        _span(9, "light_header_checks", 1700, 400, parent=6, hops=3),
        _span(10, "verify_commit_light_bulk", 2100, 2000, parent=6, commits=3),
        _span(6, "light_window", 1200, 3000, parent=1, hops=3, first=2, last=4),
        _span(11, "light_store_save", 4200, 600, parent=1, blocks=3),
        _span(12, "light_window", 5000, 2000, parent=1, hops=5, first=5, last=9),
        _span(13, "light_store_save", 7000, 250, parent=1, blocks=1),
        _span(14, "gc_collect", 7100, 50, parent=13, generation=0),
    ]
    if fallback:
        spans.append(_span(15, "light_fallback", 7300, 900, parent=1, hops=5, reason="ValueError"))
    spans.append(_span(1, "light_sync", 0, 9000, from_height=1, to_height=9, mode="sequential"))
    return spans


def test_the_readers_on_a_sync_built_by_hand():
    spans = _a_sync()
    assert _reader("light_window_hops")(_ctx(spans)) == pytest.approx(4.0)
    # 300 - 100 and 900 - 300
    assert _reader("light_fetch_host_ms")(_ctx(spans)) == pytest.approx(0.8)
    assert _reader("light_fetch_host_ms")(_ctx(spans, requests=2)) == pytest.approx(0.4)
    # 500 - 200 and 400
    assert _reader("light_header_checks_host_ms")(_ctx(spans)) == pytest.approx(0.7)
    # 600 and 250 less the collection inside it
    assert _reader("light_store_host_ms")(_ctx(spans)) == pytest.approx(0.8)
    assert _reader("light_fallback_hops")(_ctx(spans)) == 0
    assert _reader("light_fallback_hops")(_ctx(_a_sync(fallback=True), requests=2)) == pytest.approx(2.5)


@pytest.mark.parametrize("metric", LIGHT_METRICS)
def test_a_program_without_the_spans_has_nothing_to_read(metric):
    """A parent commit verifies the same windows and opens none of the
    light client's spans: the reader returns nothing and does not
    raise, and the line leaves the metric out."""
    others = [s for s in _a_sync() if not s.name.startswith("light_")]
    assert _reader(metric)(_ctx(others)) is None
    assert _reader(metric)(_ctx([], requests=0)) is None
