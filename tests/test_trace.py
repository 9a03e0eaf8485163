"""Span-tracing tests: nesting + attributes, the allocation-free
disabled path, histogram feeding, Chrome-trace export, and the
commit-pipeline span tree (addVote → batch_accumulate → tpu_dispatch
with merkle_hash in the same tree) from a live 4-validator consensus
run with the device batch-verifier seam installed."""

import asyncio
import contextlib
import gc
import json
import threading

import pytest

from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import Histogram


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts and ends with tracing off and an empty ring."""
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


class TestSpans:
    def test_nesting_records_parent_ids(self):
        trace.enable()
        with trace.span("outer", layer=1):
            with trace.span("middle"):
                with trace.span("inner"):
                    trace.add_attrs(deep=True)
        spans = trace.snapshot()
        # children exit (and record) before their parents
        assert [s.name for s in spans] == ["inner", "middle", "outer"]
        inner, middle, outer = spans
        assert inner.parent_id == middle.span_id
        assert middle.parent_id == outer.span_id
        assert outer.parent_id == 0
        assert inner.attrs["deep"] is True
        assert outer.attrs["layer"] == 1
        assert all(s.dur_us >= 0 for s in spans)

    def test_sibling_spans_share_parent(self):
        trace.enable()
        with trace.span("root"):
            with trace.span("a"):
                pass
            with trace.span("b"):
                pass
        a, b, root = trace.snapshot()
        assert a.parent_id == root.span_id
        assert b.parent_id == root.span_id

    def test_exception_recorded_and_context_restored(self):
        trace.enable()
        with pytest.raises(ValueError):
            with trace.span("boom"):
                raise ValueError("x")
        (s,) = trace.snapshot()
        assert s.attrs["error"] == "ValueError"
        assert trace.current() is None

    def test_disabled_path_allocates_nothing(self):
        """Kill switch: span() hands back the shared no-op singleton —
        no Span object, no ring entry, no live-span context."""
        assert not trace.is_enabled()
        s1 = trace.span("hot")
        s2 = trace.span("hot2")
        assert s1 is s2 is trace.NOOP_SPAN
        with s1:
            trace.add_attrs(ignored=1)  # no live span: no-op
            assert trace.current() is None
        assert trace.snapshot() == []

    def test_disabled_path_on_the_verify_path(self, monkeypatch):
        """With tracing off the commit-verification call sites — the
        decode, the phases of validation, the batch seam and the
        kernels' dispatch — create no Span, call no mirror and leave no
        collector hook behind."""
        made = []
        real_init = trace.Span.__init__

        def counting_init(self, *a, **kw):
            made.append(a[0] if a else kw.get("name"))
            real_init(self, *a, **kw)

        monkeypatch.setattr(trace.Span, "__init__", counting_init)
        mirrored = []
        from tendermint_tpu.crypto import sigcache

        with _device_seam(chunk=8):
            # in place of the profiler's annotation install() registers
            trace.set_mirror(
                lambda name: mirrored.append(name)
                or contextlib.nullcontext()
            )
            _verify_both(20)
            assert made == [] and mirrored == []
            assert trace._on_gc not in gc.callbacks
            assert trace.snapshot() == []
            # the same calls with tracing on, the cache cold again
            sigcache.reset()
            trace.enable()
            _verify_both(20)
        assert "tpu_stream_dispatch" in made and "pack_rows" in mirrored

    def test_span_feeds_histogram_enabled_and_disabled(self):
        h = Histogram("t_span_h", "help", buckets=(0.5, 10.0))
        # disabled: degrades to exactly hist.time()
        with trace.span("timed", hist=h):
            pass
        assert h.count() == 1
        assert trace.snapshot() == []
        # enabled: observes AND records
        trace.enable()
        with trace.span("timed", hist=h):
            pass
        assert h.count() == 2
        assert [s.name for s in trace.snapshot()] == ["timed"]

    def test_ring_bounded_and_resizable(self):
        trace.enable(capacity=4)
        for i in range(10):
            with trace.span(f"s{i}"):
                pass
        names = [s.name for s in trace.snapshot()]
        assert names == ["s6", "s7", "s8", "s9"]
        trace.set_capacity(2)
        assert [s.name for s in trace.snapshot()] == ["s8", "s9"]
        # restore default for other tests
        trace.set_capacity(trace.DEFAULT_CAPACITY)

    def test_chrome_trace_export_is_valid(self):
        trace.enable()
        with trace.span("parent", kind="test"):
            with trace.span("child"):
                pass
        doc = json.loads(trace.to_chrome_trace())
        events = doc["traceEvents"]
        assert len(events) == 2
        by_name = {e["name"]: e for e in events}
        assert (
            by_name["child"]["args"]["parent_id"]
            == by_name["parent"]["args"]["span_id"]
        )
        for e in events:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], float)
            assert isinstance(e["dur"], float)


class TestMirrorRootAndCollector:
    def test_mirror_enters_and_leaves_in_nesting_order(self):
        log = []

        @contextlib.contextmanager
        def mirror(name):
            log.append(("enter", name))
            try:
                yield
            finally:
                log.append(("exit", name))

        trace.set_mirror(mirror)
        try:
            # off: neither the no-op span nor hist.time() touches it
            with trace.span("off"):
                pass
            with trace.span("off", hist=Histogram("t_mir_h", "help")):
                pass
            with trace.NOOP_SPAN:
                pass
            assert log == []
            trace.enable()
            with trace.span("outer"):
                with trace.span("inner"):
                    pass
                with trace.span("second"):
                    pass
            assert log == [
                ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
                ("enter", "second"), ("exit", "second"), ("exit", "outer"),
            ]
            with pytest.raises(ValueError):
                with trace.span("boom"):
                    raise ValueError("x")
            assert log[-2:] == [("enter", "boom"), ("exit", "boom")]
            # cleared: spans go on, the mirror hears nothing
            trace.set_mirror(None)
            with trace.span("unmirrored"):
                pass
            assert ("enter", "unmirrored") not in log
        finally:
            trace.set_mirror(None)

    def test_root_id_is_shared_down_a_tree_and_differs_between_trees(self):
        trace.enable()
        for _ in range(2):
            with trace.span("root"):
                with trace.span("child"):
                    with trace.span("grandchild"):
                        pass
        spans = trace.snapshot()
        first, second = spans[:3], spans[3:]
        assert {s.root_id for s in first} == {first[-1].span_id}
        assert {s.root_id for s in second} == {second[-1].span_id}
        assert first[-1].span_id != second[-1].span_id
        doc = json.loads(trace.to_chrome_trace())
        assert {e["args"]["root_id"] for e in doc["traceEvents"]} == {
            first[-1].span_id, second[-1].span_id
        }

    def test_collection_inside_a_span_is_a_child_span(self):
        trace.enable()
        assert trace._on_gc in gc.callbacks
        with trace.span("phase"):
            gc.collect()
        spans = trace.snapshot()
        phase = next(s for s in spans if s.name == "phase")
        pauses = [s for s in spans if s.name == "gc_collect"]
        assert pauses and all(s.parent_id == phase.span_id for s in pauses)
        full = [s for s in pauses if s.attrs["generation"] == 2]
        assert full and "collected" in full[0].attrs
        assert full[0].root_id == phase.span_id
        # outside any span a collection is nobody's child: not recorded
        trace.reset()
        gc.collect()
        assert trace.snapshot() == []
        trace.disable()
        assert trace._on_gc not in gc.callbacks


class TestFollows:
    """`span(..., follows=s)`: a span on another thread (the gather's
    job on its watchdog, crypto/tpu_verifier.py) in the tree of the
    span it follows, and no child of it."""

    def _on_a_thread(self, fn):
        t = threading.Thread(target=fn)
        t.start()
        t.join()

    def test_a_follower_shares_the_root_keeps_no_parent_and_nests_its_own(self):
        trace.enable()
        seen = {}
        with trace.span("request"):
            with trace.span("waiting") as waiting:

                def job():
                    with trace.span("job", follows=waiting, lanes=2) as j:
                        seen["current"] = trace.current()
                        with trace.span("step"):
                            pass
                    seen["after"] = trace.current()
                    seen["job"] = j

                self._on_a_thread(job)
        step, job, waiting, request = trace.snapshot()
        assert (step.name, job.name) == ("step", "job")
        assert job is seen["job"] is seen["current"] and seen["after"] is None
        assert job.root_id == waiting.root_id == request.span_id
        assert job.parent_id == 0 and job.tid != waiting.tid
        assert job.attrs == {"lanes": 2, "follows": waiting.span_id}
        # its children nest under it, in the followed span's tree
        assert step.parent_id == job.span_id and step.root_id == request.span_id
        # the followed span gains no child
        assert [s for s in trace.snapshot() if s.parent_id == waiting.span_id] == []
        args = {
            e["name"]: e["args"]
            for e in json.loads(trace.to_chrome_trace())["traceEvents"]
        }
        assert args["job"]["follows"] == args["waiting"]["span_id"]
        assert args["job"]["parent_id"] == 0
        assert args["job"]["root_id"] == args["request"]["span_id"]

    def test_on_the_same_thread_a_follower_is_current_but_no_child(self):
        trace.enable()
        with trace.span("waiting") as waiting:
            with trace.span("job", follows=waiting) as job:
                assert trace.current() is job
                with trace.span("step"):
                    pass
            assert trace.current() is waiting
        step, job, waiting = trace.snapshot()
        assert job.parent_id == 0 and job.root_id == waiting.span_id
        assert step.parent_id == job.span_id

    def test_none_opens_an_ordinary_span(self):
        trace.enable()
        with trace.span("outer") as outer:
            with trace.span("job", follows=None):
                pass
        job, _outer = trace.snapshot()
        assert job.parent_id == outer.span_id and "follows" not in job.attrs

    def test_disabled_follower_is_the_noop_singleton(self):
        trace.enable()
        with trace.span("waiting") as waiting:
            pass
        trace.disable()
        made = []
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(trace.Span, "__init__", lambda *a, **k: made.append(a))
            assert trace.span("job", follows=waiting, key="k") is trace.NOOP_SPAN
            assert trace.span("job", follows=None) is trace.NOOP_SPAN
        assert made == []

    def test_a_follower_is_mirrored_on_its_own_thread(self):
        log = []

        @contextlib.contextmanager
        def mirror(name):
            log.append((name, threading.get_ident()))
            yield

        trace.set_mirror(mirror)
        try:
            trace.enable()
            with trace.span("waiting") as waiting:

                def job():
                    with trace.span("job", follows=waiting):
                        pass

                self._on_a_thread(job)
            assert [name for name, _tid in log] == ["waiting", "job"]
            assert log[0][1] == threading.get_ident() != log[1][1]
        finally:
            trace.set_mirror(None)


@contextlib.contextmanager
def _device_seam(chunk):
    """The device factories installed on the CPU backend, streaming as
    on an accelerator in chunks of `chunk` (a configured bucket, so no
    further program shape is compiled)."""
    from tendermint_tpu.crypto import tpu_verifier

    seam = tpu_verifier._TpuBatchVerifier
    held = (seam.__dict__["_streaming"], seam.STREAM_CHUNK)
    seam._streaming = staticmethod(lambda: True)
    seam.STREAM_CHUNK = chunk
    tpu_verifier.install(min_batch=2)
    try:
        yield
    finally:
        tpu_verifier.uninstall()
        seam._streaming, seam.STREAM_CHUNK = held


def _verify_both(n):
    """verify_commit and verify_commit_light of one n-validator commit,
    decoded from its wire bytes as a node does with a block, each
    against a cold verified-signature cache."""
    from tendermint_tpu.crypto import sigcache
    from tendermint_tpu.types import (
        Commit,
        verify_commit,
        verify_commit_light,
    )

    from .test_types import CHAIN_ID
    from .test_validation import make_commit

    vals, bid, commit = make_commit(n)
    wire = commit.to_proto()
    verify_commit(CHAIN_ID, vals, bid, 1, Commit.from_proto(wire))
    sigcache.reset()
    verify_commit_light(CHAIN_ID, vals, bid, 1, Commit.from_proto(wire))


# the phase spans of one commit verification: name -> the span it
# opens under (docs/metrics.md "Span tracing")
PHASE_PARENTS = {
    "commit_plan": "batch_accumulate",
    "sign_bytes": "batch_accumulate",
    "sigcache_probe": "batch_accumulate",
    "batch_route": "batch_accumulate",
    "batch_drain": "batch_accumulate",
    "batch_add": "batch_drain",
    "tpu_stream_dispatch": "batch_add",
    "tpu_early_dispatch": "batch_add",
    "tpu_dispatch": "batch_drain",
    "tpu_gather": "tpu_dispatch",
    # the proven keys under the drain, the commit memo's entry after it
    "sigcache_populate": ("batch_drain", "batch_accumulate"),
}


def test_commit_verification_phase_tree():
    """One commit verification is one tree: every phase of section B
    under its parent, the drain's span around `batch_add` and
    `tpu_dispatch`, the streamed chunks and the early launch of the
    remainder under `batch_add`, the gather under `tpu_dispatch`, the
    kernels' packing and launches under the two launching spans and
    none left under `tpu_dispatch`, and next to nothing of
    `batch_accumulate` left without a name."""
    pytest.importorskip("jax")
    trace.enable(capacity=65536)
    with _device_seam(chunk=8):
        _verify_both(20)  # verify_commit: 8 + 8 + 4; light: 8 + 6
    spans = trace.snapshot()
    by_id = {s.span_id: s for s in spans}
    # the gathers' jobs follow their `tpu_gather` (tests/test_gather_spans.py)
    roots = [s for s in spans if not s.parent_id and "follows" not in s.attrs]
    assert [s.name for s in roots] == [
        "commit_decode", "batch_accumulate",
        "commit_decode", "batch_accumulate",
    ]
    assert roots[0].attrs["sigs"] == 20 and roots[0].attrs["bytes"] > 0
    for full, light in ((roots[1], False), (roots[3], True)):
        tree = [s for s in spans if s.root_id == full.span_id]
        for s in tree:
            if s.name in PHASE_PARENTS:
                assert by_id[s.parent_id].name in PHASE_PARENTS[s.name], s.name
        names = [s.name for s in tree]
        for name in PHASE_PARENTS:
            assert name in names, (name, light)
        streamed = [s for s in tree if s.name == "tpu_stream_dispatch"]
        assert [s.attrs["chunk"] for s in streamed] == (
            [0] if light else [0, 1]
        )
        assert all(
            s.attrs["n"] == s.attrs["bucket"] == 8
            and s.attrs["key"] == "ed25519"
            for s in streamed
        )
        (early,) = [s for s in tree if s.name == "tpu_early_dispatch"]
        assert early.attrs == {
            "key": "ed25519", "n": 6 if light else 4, "bucket": 8,
            "chunk": len(streamed), "mesh_devices": 1,
        }  # fmt: skip
        for leaf in ("pack_rows", "device_launch"):
            under = {
                by_id[s.parent_id].name for s in tree if s.name == leaf
            }
            assert under == {"tpu_stream_dispatch", "tpu_early_dispatch"}, leaf
        launches = [s for s in tree if s.name == "device_launch"]
        # a tile and its SHA-512 a dispatch
        assert len(launches) == 2 * (len(streamed) + 1)
        assert {s.attrs["program"] for s in launches} == {
            "_verify_tile", "sha512_fixed"
        }
        (dispatch,) = [s for s in tree if s.name == "tpu_dispatch"]
        # the remainder's packing and launch, wherever they ran
        # (rounded to the microsecond)
        assert dispatch.attrs["host_prep_s"] >= early.dur_us / 1e6 - 1e-6
        (drain,) = [s for s in tree if s.name == "batch_drain"]
        assert drain.attrs == {"classes": 1, "overlapped": 1}
        assert "device_wall_s" not in dispatch.attrs
        (gather,) = [s for s in tree if s.name == "tpu_gather"]
        assert gather.attrs["handles"] == len(streamed) + 1
        assert gather.attrs["sigs"] == (14 if light else 20)
        (add,) = [s for s in tree if s.name == "batch_add"]
        # the device verifier took the class's columns whole
        assert add.attrs == {
            "key": "ed25519",
            "sigs": gather.attrs["sigs"],
            "bulk": gather.attrs["sigs"],
        }
        assert full.attrs["sigcache_misses"] == gather.attrs["sigs"]
        # what the phases leave of batch_accumulate is its self time
        kids = [s for s in tree if s.parent_id == full.span_id]
        covered = sum(s.dur_us for s in kids)
        assert covered >= 0.9 * full.dur_us, (covered, full.dur_us)


class _FakeKernel:
    """Backing device verifier with the dispatch()/gather() pair and
    bucket shapes, minus the XLA program — the spans and telemetry in
    _TpuBatchVerifier.verify() are what's under test, and the inputs
    are honestly signed (see the consensus run below)."""

    bucket_sizes = (8, 32, 128)

    def dispatch(self, pks, msgs, sigs):
        return [True] * len(pks)

    def gather(self, handle):
        return handle


def _ancestor_names(span, by_id):
    names = []
    cur = span
    while cur.parent_id:
        cur = by_id.get(cur.parent_id)
        if cur is None:
            break
        names.append(cur.name)
    return names


def test_commit_pipeline_span_tree():
    """Acceptance: a commit verification emits a span tree rooted at
    addVote containing batch_accumulate → tpu_dispatch (with batch-size
    and pad-waste attributes) and merkle_hash, exportable as valid
    Chrome-trace JSON."""
    pytest.importorskip("jax")
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto import sigcache
    from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
    from tendermint_tpu.crypto.tpu_verifier import TpuEd25519BatchVerifier
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    from .test_consensus_state import CHAIN, Node, RelayNet

    fake = _FakeKernel()
    cbatch.register_device_factory(
        "ed25519",
        lambda hint: TpuEd25519BatchVerifier(fake) if hint >= 2 else None,
    )
    trace.enable(capacity=65536)

    async def go():
        privs = [
            PrivKeyEd25519.from_seed(bytes([i + 140]) * 32)
            for i in range(4)
        ]
        genesis = GenesisDoc(
            chain_id=CHAIN,
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=[
                GenesisValidator(pub_key=p.pub_key(), power=10)
                for p in privs
            ],
        )
        nodes = [Node(p, genesis) for p in privs]
        RelayNet(nodes)
        for n in nodes:
            await n.cs.start()
        try:
            await asyncio.gather(
                *(n.cs.wait_for_height(3, timeout=60.0) for n in nodes)
            )
        finally:
            for n in nodes:
                await n.cs.stop()

    try:
        # cache off: a warm LastCommit legitimately skips the device
        # (zero misses -> nothing to dispatch); this test asserts the
        # dispatch INSTRUMENTATION, so force every triple to batch
        with sigcache.disabled():
            asyncio.run(go())
        spans = trace.snapshot()
        by_id = {s.span_id: s for s in spans}

        dispatches = [s for s in spans if s.name == "tpu_dispatch"]
        assert dispatches, "no tpu_dispatch spans recorded"
        # full chain: tpu_dispatch under batch_accumulate under addVote
        chained = [
            s
            for s in dispatches
            if "batch_accumulate" in _ancestor_names(s, by_id)
            and "addVote" in _ancestor_names(s, by_id)
        ]
        assert chained, "no tpu_dispatch nested under addVote"
        d = chained[0]
        assert d.attrs["batch"] >= 2  # a 4-validator LastCommit
        assert d.attrs["bucket"] == 8  # smallest fake bucket
        assert d.attrs["pad_waste"] == 8 - d.attrs["batch"]
        assert "warm" in d.attrs
        assert d.attrs["host_prep_s"] >= 0.0
        # batch_accumulate carries the commit's signature count
        acc = by_id[d.parent_id]
        while acc.name != "batch_accumulate":
            acc = by_id[acc.parent_id]
        assert acc.attrs["sigs"] == 4
        # merkle hashing appears in the same addVote-rooted tree
        merkles = [
            s
            for s in spans
            if s.name == "merkle_hash"
            and "addVote" in _ancestor_names(s, by_id)
        ]
        assert merkles, "no merkle_hash in an addVote tree"
        # the whole ring exports as valid Chrome-trace JSON
        doc = json.loads(trace.to_chrome_trace())
        assert any(
            e["name"] == "tpu_dispatch" for e in doc["traceEvents"]
        )
        assert any(
            e["name"] == "block_execute" for e in doc["traceEvents"]
        )
    finally:
        cbatch.unregister_device_factory("ed25519")
