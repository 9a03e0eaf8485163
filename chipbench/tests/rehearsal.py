"""What the rehearsals share: each driver's tiny sizes, the manifest by
name, and the patches that stand in for a chip (conftest.py makes the
fixtures of them)."""

from __future__ import annotations

import argparse
import contextlib
import os

import pytest

from chipbench import run as harness

ROOT = harness.ROOT
MANIFEST = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def tiny_of(driver: str) -> dict:
    """tiny/<driver>.json, a driver's cut of its cells so that a CPU
    holds them: `config` and `traffic` keys laid over the cell's own
    (the deployment's scale and the ring), `cache` (the
    verified-signature cache's capacity a generation, cut with the ring
    so that the rehearsal stays cold as the cell is: what is inserted
    between two visits of a ring slot must pass two generations) and
    `device_launches` (the range a request's launches fall in, in the
    traced rehearsal). A PR that adds a driver adds its file; a cell
    whose driver has none is skipped, not failed."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny", driver + ".json")
    if not os.path.exists(path):
        pytest.skip(f"no tiny sizes for driver {driver!r}: add {path}")
    return harness.load_json(path)


def args(cell: str, trace: int = 0, seconds: float = 1.5, seed: int = 2_147_483_659):
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace)


def metric(name: str) -> dict:
    """A per-layer metric's manifest entry, by its name and not by its
    place: later PRs append."""
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    return entry


@contextlib.contextmanager
def rehearsing(mp, **cut_besides):
    """Each cell as the manifest has it, cut by its driver's tiny file
    (and by `cut_besides`, configuration keys a test lays over that);
    the look for a chip skipped, and the v5e's peaks lent to the
    rehearsal's device. `mp` is a pytest MonkeyPatch."""
    from tendermint_tpu.crypto import batch, breaker, sigcache, tpu_verifier
    from tendermint_tpu.ops import merkle_kernel

    real_cell, real_json = harness.load_cell, harness.load_json
    peaks = real_json(os.path.join(harness.HERE, "peaks.json"))
    affinity = batch.group_affinity_state()

    def load_cell(name):
        cell = real_cell(name)
        cut = tiny_of(cell.traffic["driver"])
        sigcache.reset()
        sigcache.set_capacity(cut["cache"])
        if "window_hops" in cut["traffic"]:  # the merged window a TPU would give
            batch.set_group_affinity(cut["traffic"]["window_hops"])
        cell.config = {**cell.config, **cut["config"], **cut_besides}
        cell.traffic = dict(cell.traffic, **cut["traffic"], trace_requests=3)
        return cell

    mp.setattr(harness, "load_cell", load_cell)
    mp.setattr(
        harness, "require_tpu",
        lambda chips: {"platform": "cpu", "kind": "rehearsal", "count": chips},
    )  # fmt: skip
    mp.setattr(
        harness, "load_json",
        lambda p: {"rehearsal": peaks["TPU v5 lite"]} if p.endswith("peaks.json") else real_json(p),
    )  # fmt: skip
    try:
        yield
    finally:
        tpu_verifier.uninstall()
        merkle_kernel.uninstall()
        breaker.reset_all()
        batch.restore_group_affinity(affinity)
        sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)
        sigcache.reset()


def stream_in_chunks_of_8(mp) -> None:
    """Let the rehearsal stream as the chip does: a full chunk leaves
    before verify(), at a configured bucket's width."""
    from tendermint_tpu.crypto import tpu_verifier

    seam = tpu_verifier._TpuBatchVerifier
    mp.setattr(seam, "_streaming", staticmethod(lambda: True))
    mp.setattr(seam, "STREAM_CHUNK", 8)
