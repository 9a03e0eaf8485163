"""`drain_overlapped_classes`: the reader on spans built by hand, and in
a traced CPU rehearsal of each cell: 2 where the commit mixes two key
classes, 1 where it has one, nothing on a program without the drain's
span. Run by hand like the other files here; nothing is a speed.
"""

from __future__ import annotations

import os

import pytest

import run as harness
from test_decode_native_share import lent_peaks  # noqa: F401  (a fixture)
from test_program_spans import ctx_of, one_request, span
from test_rehearse import CELLS, MANIFEST, _args, tiny  # noqa: F401  (a fixture)

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


def test_the_metric_is_the_manifests_last_entry_and_has_a_reader():
    entry = MANIFEST["per_layer"][-1]
    assert entry == {
        "name": "drain_overlapped_classes", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "commit verification (types/validation.py)",
        "moves": "commits_per_s",
    }  # fmt: skip
    assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", entry["name"] + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reads_the_cells_key_classes(tiny, lent_peaks, cell):
    result = harness.run_cell(_args(cell, trace=1))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    classes = len(harness.load_cell(cell).config["key_classes"])
    assert classes == (1 if cell == "commit-150.catchup" else 2)
    assert result["metrics"]["drain_overlapped_classes"] == {
        "value": float(classes), "unit": "count"
    }
