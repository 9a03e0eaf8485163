"""`decode_native_share`: the reader on spans built by hand, and in a
traced CPU rehearsal of each cell with the native scan present and with
it disabled. Run by hand like the other files here; nothing is a speed.
"""

from __future__ import annotations

import os

import pytest

import run as harness
from test_program_spans import ctx_of, span
from test_rehearse import CELLS, MANIFEST, _args, tiny  # noqa: F401  (a fixture)

READ = harness.load_module("layer_metrics", "decode_native_share").read


def test_reader_on_spans_built_by_hand():
    decodes = [
        span(1, "commit_decode", 0, 100, sigs=10, bytes=1100, path="native"),
        span(2, "commit_decode", 200, 900, sigs=10, bytes=1100, path="generic"),
        span(3, "commit_decode", 1200, 100, sigs=10, bytes=1100, path="native"),
        span(4, "batch_accumulate", 1400, 100, path="native"),
    ]
    assert READ(ctx_of(decodes)) == pytest.approx(200 / 3)
    assert READ(ctx_of(decodes[:1])) == 100.0
    assert READ(ctx_of(decodes[1:2])) == 0.0
    # a parent commit's span carries no `path`: nothing to read
    assert READ(ctx_of([span(1, "commit_decode", 0, 100, sigs=10, bytes=1100)])) is None
    assert READ(ctx_of([], requests=0)) is None


def test_the_metric_is_the_manifests_last_entry():
    entry = MANIFEST["per_layer"][-1]
    assert entry == {
        "name": "decode_native_share", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "block decode (types/commit.py)",
        "moves": "commits_per_s",
    }  # fmt: skip
    assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", entry["name"] + ".py"))


@pytest.fixture
def lent_peaks(monkeypatch):
    """No peaks for a rehearsal device: lend it the v5e's row."""
    peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    real = harness.load_json
    monkeypatch.setattr(
        harness, "load_json",
        lambda p: {"rehearsal": peaks["TPU v5 lite"]} if p.endswith("peaks.json") else real(p),
    )


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reads_100_and_0_with_the_library_disabled(
    tiny, lent_peaks, cell, monkeypatch
):
    from tendermint_tpu import native

    if native.commit_scan_lib() is None:
        pytest.skip("no native toolchain")
    result = harness.run_cell(_args(cell, trace=1))
    assert result["correct"], result["checks"]
    assert result["metrics"]["decode_native_share"] == {"value": 100.0, "unit": "%"}
    # what TM_TPU_NO_NATIVE or a machine without a compiler leaves
    monkeypatch.setitem(native._LIBS, "commit_scan", None)
    result = harness.run_cell(_args(cell, trace=1))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert result["metrics"]["decode_native_share"] == {"value": 0.0, "unit": "%"}
