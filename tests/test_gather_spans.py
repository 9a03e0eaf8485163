"""The gather split where it runs: `tpu_gather` on the caller, and the
job that does the gather (`gather_job` -> `gather_ready`,
`gather_fetch`) on whichever thread runs it, following the caller's
span (crypto/tpu_verifier.py `_gather_guarded`, ops/verifier.py
`BucketedVerifier.gather`). Then the four readers of the split
(chipbench/layer_metrics/gather_*.py) on spans and a profiler trace
made by hand. Nothing here is a speed."""

from __future__ import annotations

import threading

import pytest

from chipbench import run as harness
from chipbench import stage_time, trace_reduce
from tendermint_tpu.crypto import tpu_verifier as T
from tendermint_tpu.libs import trace

from .test_faults import _fill, _triples
from .test_mesh_cell import _ctx, _field, _plane, _reader, _span


@pytest.fixture(autouse=True)
def _clean_trace():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _split(spans, waiting: str):
    """(the caller's span, its job, the job's ready and fetch spans)."""
    (caller,) = [s for s in spans if s.name == waiting]
    (job,) = [s for s in spans if s.name == "gather_job"]
    (ready,) = [s for s in spans if s.name == "gather_ready"]
    (fetch,) = [s for s in spans if s.name == "gather_fetch"]
    return caller, job, ready, fetch


def _assert_follows(caller, job, ready, fetch):
    assert job.attrs["follows"] == caller.span_id
    assert job.root_id == caller.root_id and job.parent_id == 0
    assert ready.parent_id == fetch.parent_id == job.span_id
    assert ready.root_id == fetch.root_id == caller.root_id
    assert ready.start_us + ready.dur_us <= fetch.start_us
    # the job lies inside the caller's wait
    assert caller.start_us <= job.start_us
    assert job.start_us + job.dur_us <= caller.start_us + caller.dur_us


@pytest.mark.parametrize("deadline", ["5", None], ids=["watchdog", "inline"])
def test_the_gather_is_split_on_the_thread_that_runs_it(monkeypatch, deadline):
    if deadline is None:
        monkeypatch.delenv("TM_TPU_GATHER_DEADLINE_S", raising=False)
    else:
        monkeypatch.setenv("TM_TPU_GATHER_DEADLINE_S", deadline)
    assert (T.gather_deadline() is None) == (deadline is None)
    triples = _triples(4, tag=b"split")
    assert _fill(T.TpuEd25519BatchVerifier(), triples).verify() == (True, [True] * 4)
    trace.enable()
    assert _fill(T.TpuEd25519BatchVerifier(), triples).verify() == (True, [True] * 4)
    spans = trace.snapshot()
    caller, job, ready, fetch = _split(spans, "tpu_gather")
    _assert_follows(caller, job, ready, fetch)
    assert job.attrs == {"key": "ed25519", "lanes": 4, "follows": caller.span_id}
    # the gather keeps its wait whole: no child but the collector's
    assert {s.name for s in spans if s.parent_id == caller.span_id} <= {"gc_collect"}
    on_caller = job.tid == caller.tid
    assert on_caller == (deadline is None)
    if not on_caller:
        names = {t.ident: t.name for t in threading.enumerate()}
        assert names.get(job.tid) == "tpu-gather-watchdog"
    assert ready.tid == fetch.tid == job.tid


def test_the_probes_gather_follows_tpu_probe(monkeypatch):
    monkeypatch.setenv("TM_TPU_GATHER_DEADLINE_S", "5")
    assert T._device_probe("ed25519", T._ed_backing)
    trace.enable()
    assert T._device_probe("ed25519", T._ed_backing)
    spans = trace.snapshot()
    caller, job, ready, fetch = _split(spans, "tpu_probe")
    _assert_follows(caller, job, ready, fetch)
    assert job.attrs["lanes"] == 1 and job.tid != caller.tid


def test_tracing_off_opens_none_of_the_gathers_spans(monkeypatch):
    monkeypatch.setenv("TM_TPU_GATHER_DEADLINE_S", "5")
    made = []
    real = trace.Span.__init__

    def counting(self, *a, **kw):
        made.append(a[0])
        real(self, *a, **kw)

    monkeypatch.setattr(trace.Span, "__init__", counting)
    v = _fill(T.TpuEd25519BatchVerifier(), _triples(4, tag=b"off"))
    assert v.verify() == (True, [True] * 4)
    assert made == [] and trace.snapshot() == []


# -- the readers, on spans made by hand ---------------------------------


def _one_gather(gc_us=0.0):
    """A request's gather of 1,000 us: the job runs 100..900 on the
    worker, ready 150..650 and fetch 660..860 inside it; a collection
    of `gc_us` inside the job (its pause is nobody's phase)."""
    spans = [
        _span(2, "tpu_gather", 0, 1000, parent=1),
        _span(3, "gather_job", 100, 800, follows=2, key="ed25519", lanes=8),
        _span(4, "gather_ready", 150, 500, parent=3),
        _span(5, "gather_fetch", 660, 200, parent=3),
    ]
    if gc_us:
        spans.append(_span(6, "gc_collect", 870, gc_us, parent=3))
    return spans


READERS = ("gather_handoff_ms", "gather_ready_ms", "gather_fetch_ms")


def test_the_three_span_readers_add_up_to_the_gathers_wait():
    ctx = _ctx(_one_gather(), requests=2)
    got = [_reader(m)(ctx) for m in READERS]
    # handoff 1000 - 800; ready 500; fetch 200 + the job's own 100
    assert got == pytest.approx([0.1, 0.25, 0.15])
    assert sum(got) == pytest.approx(_reader("gather_wait_ms")(ctx))
    # the collector's pause inside the job is in no part of the split
    ctx = _ctx(_one_gather(gc_us=20), requests=2)
    got = [_reader(m)(ctx) for m in READERS]
    assert got == pytest.approx([0.1, 0.25, 0.14])
    assert sum(got) == pytest.approx(_reader("gather_wait_ms")(ctx) - 0.01)


def test_a_job_that_follows_no_gather_is_no_part_of_the_handoff():
    spans = _one_gather() + [
        _span(12, "tpu_probe", 2000, 300),
        _span(13, "gather_job", 2050, 200, follows=12),
    ]
    assert _reader("gather_handoff_ms")(_ctx(spans)) == pytest.approx(0.2)


def test_a_program_without_the_split_reads_nothing():
    """The parent's tree: a gather and nothing following it."""
    spans = [_span(2, "tpu_gather", 0, 1000, parent=1)]
    for metric in READERS:
        assert _reader(metric)(_ctx(spans)) is None, metric
        assert _reader(metric)(_ctx(_one_gather(), requests=0)) is None, metric


# -- gather_device_idle_ms, on a profiler trace written by hand ---------


def _trace_file(path, requests, ready, chips) -> str:
    """The harness's `cb_request` annotations on the request thread's
    line, `gather_ready` annotations on the watchdog's, then a device
    plane a chip of (op name, start us, length us)."""
    host = {"python3": [("cb_request", s, d) for s, d in requests]}
    if ready:
        host["tpu-gather-watchdog"] = [("gather_ready", s, d) for s, d in ready]
    planes = [_plane("/host:CPU", host)]
    for n, ops in enumerate(chips):
        planes.append(_plane(f"/device:TPU:{n}", {"XLA Ops": ops} if ops else {}))
    with open(path, "wb") as f:
        f.write(b"".join(_field(1, p) for p in planes))
    return str(path)


def test_gather_device_idle_ms_on_a_trace_with_a_worker_line(tmp_path, monkeypatch):
    read = _reader("gather_device_idle_ms")
    requests = [(10, 40), (60, 40)]  # the traced window: 10..100 us
    # 20..40 and 70..90 inside the window; 0..5 before it
    ready = [(0, 5), (20, 20), (70, 20)]
    first = [("%while.1", 25, 10), ("%while.1", 65, 30)]  # idle 10 us inside ready
    second = [("%fusion.2", 50, 5)]  # idle 40 us inside ready
    path = _trace_file(tmp_path / "gather.xplane.pb", requests, ready, [first, second, []])
    monkeypatch.setattr(stage_time, "find_trace", lambda: path)
    reduced = trace_reduce.reduce_file(path)
    assert reduced["requests"] == 2 and reduced["devices"] == 2
    idle = harness.load_module("layer_metrics", "gather_device_idle_ms").idle_ns
    # the chip that ran nothing is no plane of the average
    assert idle(trace_reduce.load(path)) == pytest.approx((10_000 + 40_000) / 2)
    assert read(_ctx(trace=reduced)) == pytest.approx(25e-3 / 2)
    # never more than the ready spans themselves: 40 us inside the window
    assert read(_ctx(trace=reduced)) <= 40e-3 / 2
    # a program whose gather opens no annotation (the parent), no
    # device plane, no trace, no file: nothing to read
    bare = _trace_file(tmp_path / "bare.xplane.pb", requests, [], [first])
    host = _trace_file(tmp_path / "host.xplane.pb", requests, ready, [])
    for p in (bare, host):
        monkeypatch.setattr(stage_time, "find_trace", lambda p=p: p)
        assert read(_ctx(trace=trace_reduce.reduce_file(p))) is None
    assert read(_ctx(trace=None)) is None
    monkeypatch.setattr(stage_time, "find_trace", lambda: None)
    assert read(_ctx(trace=reduced)) is None


def test_the_manifest_adds_the_four_readers_as_one_block():
    manifest = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
    new = [m for m in manifest["per_layer"] if m["name"].startswith("gather_") and m["name"] != "gather_wait_ms"]
    assert [m["name"] for m in new] == [*READERS, "gather_device_idle_ms"]
    at = manifest["per_layer"].index(new[0])
    # the block sits where it was appended, after the 47 metrics it found
    assert at == 47 and at + 4 <= len(manifest["per_layer"])
    assert manifest["per_layer"][at : at + 4] == new
    for m in new:
        assert "workloads" not in m and m["moves"] == "commits_per_s"
        assert m["unit"] == "ms" and m["better"] == "lower"
    assert [m["source"] for m in new] == ["program_span"] * 3 + ["device_trace"]
