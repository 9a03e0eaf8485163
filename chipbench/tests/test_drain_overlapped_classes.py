"""`drain_overlapped_classes`: the reader on spans built by hand, and
its manifest entry. The traced rehearsal of each cell (2 where the
commit mixes two key classes, 1 where it has one) is test_rehearse.py's.
Nothing here is a speed.
"""

from __future__ import annotations

import os

from chipbench import run as harness
from chipbench.tests.rehearsal import metric
from chipbench.tests.test_program_spans import ctx_of, one_request, span

READ = harness.load_module("layer_metrics", "drain_overlapped_classes").read


def test_reader_on_spans_built_by_hand():
    drains = [
        span(2, "batch_drain", 10, 500, parent=1, root=1, classes=2, overlapped=2),
        span(1, "batch_accumulate", 0, 600),
        span(4, "batch_drain", 710, 500, parent=3, root=3, classes=2, overlapped=1),
        span(3, "batch_accumulate", 700, 600),
        # a drain that raised before its launches were counted says nothing
        span(6, "batch_drain", 1410, 100, parent=5, root=5, classes=2, error="ValueError"),
        span(5, "batch_accumulate", 1400, 200),
    ]
    assert READ(ctx_of(drains, requests=3)) == 1.5
    assert READ(ctx_of(drains[:2])) == 2.0
    assert READ(ctx_of(drains[4:])) is None
    # a parent commit drains a class at a time and opens no such span
    assert READ(ctx_of(one_request())) is None
    assert READ(ctx_of([], requests=0)) is None


def test_the_metric_is_in_the_manifest_and_has_a_reader():
    assert metric("drain_overlapped_classes") == {
        "name": "drain_overlapped_classes", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "commit verification (types/validation.py)",
        "moves": "commits_per_s",
    }  # fmt: skip
    assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", "drain_overlapped_classes.py"))
