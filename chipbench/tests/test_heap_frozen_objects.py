"""`heap_frozen_objects`: the reader on a program with the gauge and on
one without, and in a traced CPU rehearsal of each cell, where the
set-up's settles must leave none for the window. Run by hand like the
other files here; nothing is a speed.
"""

from __future__ import annotations

import os

import pytest

import run as harness
from test_decode_native_share import lent_peaks  # noqa: F401  (a fixture)
from test_rehearse import CELLS, MANIFEST, _args, tiny  # noqa: F401  (a fixture)

READ = harness.load_module("layer_metrics", "heap_frozen_objects").read


def test_reader_reads_the_gauge_and_nothing_on_a_program_without_it(monkeypatch):
    from tendermint_tpu.crypto import tpu_verifier

    parents = {"batches": 3, "sigs": 303, "warm_misses": 1, "mesh_devices": 1}
    monkeypatch.setattr(tpu_verifier, "stats", lambda: dict(parents))
    assert READ(None) is None
    monkeypatch.setattr(
        tpu_verifier, "stats",
        lambda: dict(parents, heap_settles=4, heap_frozen_objects=1_180_000),
    )
    assert READ(None) == 1_180_000
    # installed and nothing compiled yet: the gauge reads 0, and that is a reading
    monkeypatch.setattr(
        tpu_verifier, "stats", lambda: dict(parents, heap_settles=0, heap_frozen_objects=0)
    )
    assert READ(None) == 0


def test_the_metric_is_in_the_manifest_and_has_a_reader():
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == "heap_frozen_objects")
    assert entry == {
        "name": "heap_frozen_objects", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "python runtime (garbage collector)",
        "moves": "verify_p95_ms",
    }  # fmt: skip
    assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", entry["name"] + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reports_it_and_no_settle_lands_in_the_window(
    tiny, lent_peaks, cell
):
    import jax

    from tendermint_tpu.crypto import tpu_verifier

    tpu_verifier.uninstall()  # thawed, so the count is this run's own
    jax.clear_caches()  # and its programs are first touches again
    at_window_start = []
    result = harness.run_cell(
        _args(cell, trace=1),
        prepare=lambda _driver: at_window_start.append(tpu_verifier.stats()["heap_settles"]),
    )
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    frozen = result["metrics"]["heap_frozen_objects"]
    assert frozen["unit"] == "count" and frozen["value"] >= 100_000
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert at_window_start[0] >= 1
    assert tpu_verifier.stats()["heap_settles"] == at_window_start[0]
