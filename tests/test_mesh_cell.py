"""The benchmark's four-chip cell, `commit-10k-mixed.cold-4chip`,
rehearsed on four of the suite's virtual CPU devices, and the readers
of the `mesh` layer's metrics (chipbench/layer_metrics/) on spans and
traces made by hand.

`pytest chipbench/tests` rehearses every cell too, but its conftest
gives JAX one device, so there this cell runs on a mesh of one. Here
`chipbench/run.run_cell` installs over four, exactly as on the chip:
the look for a TPU is replaced in the test, never in run.py. Nothing
these tests print is a speed; a trace built by hand reads as a device
only inside this file.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import types

import pytest

from chipbench import run as harness
from chipbench import trace_reduce

CELL = "commit-10k-mixed.cold-4chip"
MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
MESH_METRICS = ("shard_place_host_ms", "lanes_per_chip", "chips_busy", "chip_busy_skew_share")
# chipbench/tests/test_rehearse.py's tiny size of a verify_commit cell
TINY = {"validators": 16, "ring_commits": 3, "corrupt_every": 4, "cache": 16}


def _reader(metric: str):
    return harness.load_module("layer_metrics", metric).read


@pytest.fixture
def tiny(monkeypatch):
    """The cell as the manifest has it, its scale and ring cut so that
    a CPU holds it; four virtual devices stand where the chips would."""
    from tendermint_tpu.crypto import sigcache, tpu_verifier
    from tendermint_tpu.ops import merkle_kernel

    real = harness.load_cell

    def load_cell(name):
        cell = real(name)
        sigcache.reset()
        sigcache.set_capacity(TINY["cache"])
        cell.config = dict(cell.config, validators=TINY["validators"])
        cell.traffic = dict(
            cell.traffic, ring_commits=TINY["ring_commits"], warmup_commits=1,
            corrupt_every=TINY["corrupt_every"], trace_requests=3,
        )  # fmt: skip
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
    sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)
    sigcache.reset()


def _args(trace=0, seconds=1.5, seed=2_147_483_659):
    return argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=trace)


def _installed_mesh_sizes() -> list:
    from tendermint_tpu.crypto import tpu_verifier

    return [v.mesh.devices.size for v in (tpu_verifier._ed_backing(), tpu_verifier._sr_backing())]


def test_the_manifest_has_the_cell_its_configuration_and_its_readers():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="commit-10k-mixed-4chip", traffic="cold", chips=4)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
    one_chip = harness.load_json(os.path.join(harness.HERE, "configs", "commit-10k-mixed.json"))
    assert config["devices"] == 4 and config["reference"] == "commit_verify"
    assert config["reduced"] == entry["reduced"] == ["merkle_proof_batch"]
    assert config["source"] == entry["source"] != one_chip["source"]
    for key in ("validators", "key_classes", "voting_power", "chain_id", "guarantees"):
        assert config[key] == one_chip[key], key
    assert set(one_chip["assumed"]) < set(config["assumed"])
    new = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == list(MESH_METRICS)
    at = MANIFEST["per_layer"].index(new[0])
    assert MANIFEST["per_layer"][at : at + len(new)] == new  # added as one block
    for m in new:
        assert m["layer"] == "mesh (parallel/sharding.py)" and m["moves"] == "commits_per_s"
        assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", m["name"] + ".py"))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert 1 <= four <= max(1, len(MANIFEST["workloads"]) // 2)


def test_the_cell_rehearses_correct_over_four_devices(tiny):
    result = harness.run_cell(_args())
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert result["checks"]["corrupted_requests_min"]["value"] >= 1
    assert set(result["metrics"]) == {"commits_per_s", "verify_p95_ms", "setup_s"}
    assert _installed_mesh_sizes() == [4, 4]
    json.dumps(result)


def test_the_control_fails_the_cells_comparison(tiny):
    result = harness.run_cell(_args(), prepare=lambda d: d.use_control())
    assert not result["correct"]
    assert result["checks"]["verdict_mismatches"]["value"] >= 1


# -- a profiler trace written field by field (tsl xplane.proto) ---------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint((number << 3) | 2) + _varint(len(value)) + value


def _plane(name: str, lines: dict) -> bytes:
    """XPlane `name` with {line name: [(event name, start us, length
    us)]}; every line starts at the trace's zero."""
    names = sorted({e[0] for events in lines.values() for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = _field(2, name)
    for n, i in ids.items():
        out += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
    for line, events in lines.items():
        body = _field(2, line) + _field(3, 0)
        for n, start_us, dur_us in events:
            body += _field(4, _field(1, ids[n]) + _field(2, start_us * 10**6) + _field(3, dur_us * 10**6))
        out += _field(3, body)
    return out


def _xspace(path, requests: list, chips: list) -> str:
    """A trace file: the harness's `cb_request` annotations on the host
    plane, then a device plane a chip, each {"ops": [...], "modules":
    [...]} of (name, start us, length us)."""
    planes = [_plane("/host:CPU", {"python3": [("cb_request", s, d) for s, d in requests]})]
    for n, chip in enumerate(chips):
        lines = {"XLA Ops": chip["ops"], "XLA Modules": chip.get("modules")}
        planes.append(_plane(f"/device:TPU:{n}", {k: v for k, v in lines.items() if v}))
    with open(path, "wb") as f:
        f.write(b"".join(_field(1, p) for p in planes))
    return str(path)


def _ctx(spans=(), requests=1, trace=None):
    return types.SimpleNamespace(spans=list(spans), requests=requests, trace=trace)


def _span(sid, name, start, dur, parent=0, **attrs):
    return types.SimpleNamespace(
        span_id=sid, name=name, start_us=float(start), dur_us=float(dur),
        parent_id=parent, root_id=1, attrs=attrs,
    )  # fmt: skip


def _one_launch_each():
    """A tile launch with three placements, a SHA-512 launch with one,
    and a tile launch whose digests were on the mesh already."""
    return [
        _span(2, "shard_place", 100, 200, parent=1, devices=4, lanes_per_device=512, bytes=65536),
        _span(3, "shard_place", 300, 300, parent=1, devices=4, lanes_per_device=512, bytes=131072),
        _span(4, "shard_place", 600, 100, parent=1, devices=4, lanes_per_device=512, bytes=65536),
        _span(1, "device_launch", 0, 1000, program="_verify_tile_sr", bucket=2048),
        _span(6, "shard_place", 1100, 400, parent=5, devices=4, lanes_per_device=32, bytes=22912),
        _span(5, "device_launch", 1000, 600, program="sha512_fixed", bucket=128),
        _span(8, "shard_place", 2100, 100, parent=7, devices=4, lanes_per_device=128, bytes=16384),
        _span(9, "shard_place", 2200, 100, parent=7, devices=4, lanes_per_device=128, bytes=32768),
        _span(7, "device_launch", 2000, 500, program="_verify_tile", bucket=512),
    ]


def test_span_readers_on_launches_built_by_hand():
    spans = _one_launch_each()
    assert _reader("shard_place_host_ms")(_ctx(spans)) == pytest.approx(1.2)
    assert _reader("shard_place_host_ms")(_ctx(spans, requests=2)) == pytest.approx(0.6)
    # the enqueue alone: 1000 - 600, 600 - 400, 500 - 200
    assert _reader("launch_host_ms")(_ctx(spans)) == pytest.approx(0.9)
    # the tiles' five placements, not the SHA-512's
    assert _reader("lanes_per_chip")(_ctx(spans)) == pytest.approx((3 * 512 + 2 * 128) / 5)
    # a one-chip install or a parent commit opens no such span
    launches = [s for s in spans if s.name == "device_launch"]
    for metric in ("shard_place_host_ms", "lanes_per_chip"):
        assert _reader(metric)(_ctx(launches)) is None
        assert _reader(metric)(_ctx([], requests=0)) is None


def test_trace_readers_on_two_planes_built_by_hand(tmp_path, monkeypatch):
    from chipbench import stage_time

    skew = harness.load_module("layer_metrics", "chip_busy_skew_share")
    read_skew = skew.read
    read_chips = _reader("chips_busy")
    requests = [(10, 40), (60, 40)]  # the traced window: 10..100 us
    first = {"ops": [("%fusion.1", 0, 20), ("%while.2", 15, 10), ("%fusion.1", 50, 10), ("%copy.3", 95, 20)]}
    second = {"ops": [("%fusion.1", 20, 10), ("%fusion.1", 70, 5)]}
    path = _xspace(tmp_path / "two.xplane.pb", requests, [first, second])
    monkeypatch.setattr(stage_time, "find_trace", lambda: path)
    reduced = trace_reduce.reduce_file(path)
    assert reduced["requests"] == 2 and reduced["devices"] == 2
    # inside the window the first chip is busy 10..25, 50..60, 95..100; the second 15 us
    assert skew.busy_by_plane(trace_reduce.load(path)) == [30_000, 15_000]
    assert read_skew(_ctx(trace=reduced)) == pytest.approx(50.0)
    assert read_chips(_ctx(trace=reduced)) == 2
    # a chip of the mesh that ran nothing: not busy, and all the skew
    idle = _xspace(tmp_path / "idle.xplane.pb", requests, [first, {"ops": []}])
    monkeypatch.setattr(stage_time, "find_trace", lambda: idle)
    reduced = trace_reduce.reduce_file(idle)
    assert read_chips(_ctx(trace=reduced)) == 1
    assert read_skew(_ctx(trace=reduced)) == pytest.approx(100.0)
    # no device plane (a CPU rehearsal), no trace, or no file: nothing to read
    host_only = _xspace(tmp_path / "host.xplane.pb", requests, [])
    monkeypatch.setattr(stage_time, "find_trace", lambda: host_only)
    reduced = trace_reduce.reduce_file(host_only)
    for read in (read_skew, read_chips):
        assert read(_ctx(trace=reduced)) is None
        assert read(_ctx(trace=None)) is None
    monkeypatch.setattr(stage_time, "find_trace", lambda: None)
    assert read_skew(_ctx(trace={"requests": 2, "devices": 2})) is None


def test_a_traced_rehearsal_over_a_constructed_trace_reports_the_mesh_metrics(tiny, monkeypatch):
    """A traced run of the cell whose profiler is replaced by a trace
    written by hand: three requests, four device planes, the last chip
    a tenth less busy. The line holds the four new metrics, every
    span-read metric of the cell, and nothing that is not a number."""

    @contextlib.contextmanager
    def profiling(self):
        yield
        where = os.path.join(self.dir, "plugins", "profile", "by_hand")
        os.makedirs(where)
        requests = [(100 * i, 80) for i in range(self.requests)]
        chips = [
            {
                "ops": [("%while.32", 100 * i + 10, 40 if n < 3 else 36) for i in range(self.requests)],
                "modules": [("jit__verify_tile(1)", 100 * i + 10, 40) for i in range(self.requests)],
            }
            for n in range(4)
        ]
        _xspace(os.path.join(where, "hand.xplane.pb"), requests, chips)

    monkeypatch.setattr(harness.Tracer, "profiling", profiling)
    result = harness.run_cell(_args(trace=1))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert _installed_mesh_sizes() == [4, 4]
    got = result["metrics"]
    wanted = {m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(got) <= wanted
    assert set(MESH_METRICS) <= set(got)
    assert got["chips_busy"] == {"value": 4, "unit": "count"}
    assert got["chip_busy_skew_share"]["value"] == pytest.approx(10.0)
    # 8 signatures a key class: an 8-lane bucket, two lanes a device
    assert got["lanes_per_chip"] == {"value": 2.0, "unit": "count"}
    assert got["shard_place_host_ms"]["value"] > 0
    # one tile a class and one SHA-512, six host arrays among them
    assert got["device_launches"]["value"] == 3
    # both key classes crossed the seam as columns
    assert got["bulk_add_share"] == {"value": 100.0, "unit": "%"}
    for name in ("launch_host_ms", "gather_wait_ms", "sigverify_roofline", "verify_mfu",
                 "kernel_device_ms", "device_idle_share", "pad_waste_share", "window_compiles"):
        assert name in got, name
    assert "stream_dispatch_host_ms" not in got and "merlin_host_ms" not in got
    assert all(isinstance(m["value"], (int, float)) for m in got.values())
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    json.dumps(result)
