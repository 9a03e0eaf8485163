"""The readers of the program's own span tree (chipbench/span_tree.py,
the span-read metrics under layer_metrics/, chipbench/stage_time.py):
on a span list built by hand and on the recorded trace. The traced
rehearsal of each cell that reads them end to end is test_rehearse.py's.
Nothing here is a speed.
"""

from __future__ import annotations

import os
import types

import pytest

from chipbench import run as harness
from chipbench import span_tree, stage_time
from chipbench.tests.rehearsal import CELLS, metric


def span(sid, name, start, dur, parent=0, root=None, **attrs):
    return types.SimpleNamespace(
        span_id=sid, name=name, start_us=float(start), dur_us=float(dur),
        parent_id=parent, root_id=root if root is not None else sid, attrs=attrs,
    )


def one_request():
    """A decode and a verification as the program records them, in
    microseconds, children before parents as the ring holds them."""
    return [
        span(2, "gc_collect", 100, 200, parent=1, root=1, generation=0),
        span(1, "commit_decode", 0, 1000, sigs=10, bytes=1100),
        span(4, "commit_plan", 2000, 500, parent=3, root=3),
        span(6, "gc_collect", 2600, 300, parent=5, root=3, generation=2),
        span(5, "sign_bytes", 2500, 1000, parent=3, root=3, rows=10),
        span(7, "sigcache_probe", 3500, 500, parent=3, root=3),
        span(8, "batch_route", 4000, 400, parent=3, root=3, inline=0),
        # a streamed chunk: its children overlap each other by 100 us
        span(11, "pack_rows", 5000, 300, parent=10, root=3),
        span(12, "merlin_challenges", 5200, 400, parent=10, root=3),
        span(13, "device_launch", 5700, 200, parent=10, root=3),
        span(10, "tpu_stream_dispatch", 5000, 1000, parent=9, root=3, chunk=0),
        span(9, "batch_add", 4400, 4000, parent=3, root=3, key="sr25519"),
        span(15, "pack_rows", 8400, 200, parent=14, root=3),
        span(16, "device_launch", 8600, 100, parent=14, root=3),
        span(17, "device_launch", 8700, 100, parent=14, root=3),
        span(18, "tpu_gather", 8900, 2000, parent=14, root=3),
        span(19, "cpu_disprove", 10900, 400, parent=14, root=3, lanes=1),
        span(14, "tpu_dispatch", 8400, 3000, parent=3, root=3, host_prep_s=0.0005),
        span(20, "sigcache_populate", 11400, 400, parent=3, root=3, keys=10),
        span(3, "batch_accumulate", 2000, 10000, sigs=10),
    ]


def ctx_of(spans, requests=1, trace=None):
    return types.SimpleNamespace(spans=spans, requests=requests, trace=trace)


def test_self_time_leaves_out_children_overlaps_and_the_collector():
    tree = span_tree.of(ctx_of(one_request()))
    by_id = tree.by_id
    assert tree.self_us(by_id[1]) == 800  # the collection inside the decode
    assert tree.self_us(by_id[5]) == 700
    # 300 + 400 + 200 with 100 shared: the union is 800 of the chunk's 1000
    assert tree.covered_us(by_id[10]) == 800 and tree.self_us(by_id[10]) == 200
    assert tree.self_us(by_id[9]) == 3000  # the chunk's whole duration goes
    assert tree.self_us(by_id[14]) == 200
    # phases cover 2000..11800 of 2000..12000
    assert tree.self_us(by_id[3]) == 200
    assert tree.self_us(by_id[11]) == 300  # a leaf keeps its duration
    groups = tree.by_root()
    assert sorted(groups) == [1, 3]
    assert [s.span_id for s in groups[1]] == [2, 1]
    assert len(groups[3]) == 18


def test_a_parent_program_without_root_id_groups_by_parents():
    spans = one_request()
    for s in spans:
        del s.root_id
    assert sorted(len(g) for g in span_tree.Tree(spans).by_root().values()) == [2, 18]


def test_a_child_that_outlasts_its_parent_is_clipped():
    spans = [span(2, "late", 50, 100, parent=1, root=1), span(1, "top", 0, 100)]
    tree = span_tree.Tree(spans)
    assert tree.self_us(tree.by_id[1]) == 50


EXPECTED = {  # milliseconds a request, from one_request()
    "signbytes_host_ms": 0.7,
    "sigcache_host_ms": 0.9,
    "commit_plan_host_ms": 0.5,
    "batch_route_host_ms": 0.4,
    "batch_add_host_ms": 3.0,
    "stream_dispatch_host_ms": 1.0,
    "cpu_disprove_host_ms": 0.4,
    "pack_rows_host_ms": 0.5,
    "merlin_host_ms": 0.4,
    "launch_host_ms": 0.4,
    "device_launches": 3,
    "gather_wait_ms": 2.0,
    # the decode whole and 9,800 of the verification's 10,000 us
    "span_coverage_share": 100.0 * 10800 / 11000,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reader_on_the_hand_built_request(metric):
    read = harness.load_module("layer_metrics", metric).read
    assert read(ctx_of(one_request())) == pytest.approx(EXPECTED[metric])
    # over two requests the same spans are half as much a request
    assert read(ctx_of(one_request(), requests=2)) == pytest.approx(
        EXPECTED[metric] / (1 if metric == "span_coverage_share" else 2)
    )
    # a program without the phase spans (the parent commit) has nothing
    # to read; its one dispatch span covers 3,000 us of the 10,000
    old = [s for s in one_request() if s.name in ("tpu_dispatch", "batch_accumulate")]
    assert read(ctx_of(old)) == (30.0 if metric == "span_coverage_share" else None)
    assert read(ctx_of([], requests=0)) is None


def test_the_span_read_metrics_are_in_the_manifest_and_have_readers():
    for name in sorted(set(EXPECTED) | {"ladder_device_ms", "decode_points_device_ms"}):
        assert metric(name)["moves"] == "commits_per_s"
        assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", name + ".py"))
    # the streamed chunks' span opens wherever a batch reaches 2,048: not in cell 1's 101
    assert metric("stream_dispatch_host_ms")["workloads"] == CELLS[1:4]
    # merlin runs in both 10k cells, and is listed in the one-chip one alone: PERF.md section 7
    assert metric("merlin_host_ms")["workloads"] == CELLS[1:2]


def test_stage_reader_on_the_recorded_trace_finds_no_scope_and_says_so():
    """The recorded trace predates the stage names (and its events'
    stats were cut): the window and the operations are found, no stage
    is, and the two stage metrics are left out."""
    path = os.path.join(harness.HERE, "testdata", "commit-150.xplane.pb.gz")
    got = stage_time.stage_seconds(path)
    assert got["requests"] == 2 and got["ops"] > 10_000
    assert got["stages"] == {} and got["staged_ops"] == 0
    for metric in ("ladder_device_ms", "decode_points_device_ms"):
        read = harness.load_module("layer_metrics", metric).read
        assert read(ctx_of([], trace=None)) is None


def test_stage_reader_on_a_trace_built_by_hand(tmp_path):
    """An xplane file written field by field: two operations of one
    scope that overlap, one of another found through a referred string,
    one of none; only what lies inside the request counts."""

    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(number, value):
        if isinstance(value, int):
            return varint(number << 3) + varint(value)
        if isinstance(value, str):
            value = value.encode()
        return varint((number << 3) | 2) + varint(len(value)) + value

    def entry(key, message):  # one entry of a map<int64, Message>
        return field(1, key) + field(2, message)

    def event(meta, offset_ps, dur_ps, stats=b""):
        return field(1, meta) + field(2, offset_ps) + field(3, dur_ps) + stats

    host = (
        field(2, "/host:CPU")
        + field(4, entry(1, field(1, 1) + field(2, "cb_request")))
        + field(3, field(2, "python3") + field(3, 1000) + field(4, event(1, 0, 10_000_000)))
    )
    op_name = field(1, 1) + field(2, "tf_op")  # stat metadata 1
    scope_b = field(1, 2) + field(2, "jit(_verify_tile)/jit(main)/decode_points/mul")
    metas = [
        field(1, 1) + field(2, "%while.32 = while(...)")
        + field(5, field(1, 1) + field(5, "jit(_verify_tile)/jit(main)/dual_mult/while")),
        field(1, 2) + field(2, "%fusion.7 = fusion(...)")
        + field(5, field(1, 1) + field(5, "jit(_verify_tile)/jit(main)/dual_mult/while/body/mul")),
        field(1, 3) + field(2, "%fusion.9 = fusion(...)"),  # its scope rides on the event
        field(1, 4) + field(2, "%copy.1 = copy(...)")
        + field(5, field(1, 1) + field(5, "jit(_verify_tile)/jit(main)/dual_multiply/x")),
    ]
    device = (
        field(2, "/device:TPU:0")
        + b"".join(field(4, entry(i + 1, m)) for i, m in enumerate(metas))
        + field(5, entry(1, op_name)) + field(5, entry(2, scope_b))
        + field(3, field(2, "XLA Ops") + field(3, 1000) + b"".join(
            field(4, e) for e in (
                event(1, 1_000_000, 4_000_000),  # the loop: 1..5 us
                event(2, 2_000_000, 1_000_000),  # its body, inside it
                event(3, 6_000_000, 1_000_000, field(4, field(1, 1) + field(7, 2))),
                event(4, 7_000_000, 1_000_000),  # `dual_multiply` is no stage
                event(1, 9_000_000, 4_000_000),  # runs past the request: 1 us counts
            )))
    )
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device))
    got = stage_time.stage_seconds(str(path))
    assert got["requests"] == 1 and got["ops"] == 5 and got["staged_ops"] == 4
    assert got["stages"] == pytest.approx({"dual_mult": 5e-6, "decode_points": 1e-6})
