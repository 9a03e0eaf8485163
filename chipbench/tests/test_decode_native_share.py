"""`decode_native_share`: the reader on spans built by hand, and its
manifest entry. The traced rehearsal of each cell with the native scan
present and disabled is test_rehearse.py's. Nothing here is a speed.
"""

from __future__ import annotations

import os

import pytest

from chipbench import run as harness
from chipbench.tests.rehearsal import metric
from chipbench.tests.test_program_spans import ctx_of, span

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


def test_the_metric_is_in_the_manifest_and_has_a_reader():
    assert metric("decode_native_share") == {
        "name": "decode_native_share", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "block decode (types/commit.py)",
        "moves": "commits_per_s",
    }  # fmt: skip
    assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", "decode_native_share.py"))
