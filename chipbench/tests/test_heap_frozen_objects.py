"""`heap_frozen_objects`: the reader on a program with the gauge and on
one without, and its manifest entry. The traced rehearsal of each cell,
where the set-up's settles must leave none for the window, is
test_rehearse.py's. Nothing here is a speed.
"""

from __future__ import annotations

import os

from chipbench import run as harness
from chipbench.tests.rehearsal import metric

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
    entry = metric("heap_frozen_objects")
    assert entry == {
        "name": "heap_frozen_objects", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "python runtime (garbage collector)",
        "moves": "verify_p95_ms",
    }  # fmt: skip
    assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", entry["name"] + ".py"))
