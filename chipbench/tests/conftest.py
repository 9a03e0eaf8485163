"""chipbench's tests (`pytest chipbench/tests`): the benchmark's own
control, planted faults, readers and reducer, rehearsed on the CPU
backend at tiny sizes. They live under the benchmark's `paths`, where a
later PR cannot weaken them; the repo's tier-1 run of `tests/` does not
collect this directory (PERF.md, Open questions).

The look for a chip is replaced HERE, never by an option of run.py.
Nothing these tests print is a speed.
"""

from __future__ import annotations

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from chipbench import run as harness  # noqa: E402
from chipbench.tests.rehearsal import CELLS, args, rehearsing, stream_in_chunks_of_8  # noqa: E402


@pytest.fixture(scope="session", params=CELLS)
def cell(request):
    """Every cell of the manifest. A fixture of the session and not a
    `parametrize`, so that pytest runs all of one cell's cases together:
    they share the programs its traced rehearsal loaded."""
    return request.param


@pytest.fixture
def tiny(monkeypatch):
    with rehearsing(monkeypatch):
        yield


@pytest.fixture(scope="session")
def traced():
    """`traced(cell, native=True)`: ONE traced rehearsal a cell, made
    when first asked for and read by every reader's case after it (a
    rehearsal's cost is loading its programs, 20-50 s a cell; each file
    running its own made this suite 767 s). A cell with two key classes
    streams in chunks of 8 (16 validators: one chunk a class), so the
    streamed spans have something in them. The compiled programs are
    dropped first, so the cell's are first touches and the heap's
    settles are this run's own; `settles` holds the program's
    `heap_settles` at the window's start and after the run.
    `native=False` is a second run, made straight after while the
    programs are loaded, with the native commit scan disabled, as
    TM_TPU_NO_NATIVE or a machine without a compiler leaves it."""
    import jax

    from tendermint_tpu import native as native_libs
    from tendermint_tpu.crypto import tpu_verifier

    runs: dict = {}

    def rehearse(cell: str, native: bool):
        with pytest.MonkeyPatch.context() as mp, rehearsing(mp):
            config = harness.load_cell(cell).config
            if len(config["key_classes"]) > 1:
                stream_in_chunks_of_8(mp)
            if not native:
                mp.setitem(native_libs._LIBS, "commit_scan", None)
            settles = []
            result = harness.run_cell(
                args(cell, trace=1),
                prepare=lambda _d: settles.append(tpu_verifier.stats()["heap_settles"]),
            )
            settles.append(tpu_verifier.stats()["heap_settles"])
        return types.SimpleNamespace(
            result=result, metrics=result["metrics"], config=config, settles=settles
        )

    def get(cell: str, native: bool = True):
        if (cell, True) not in runs:
            tpu_verifier.uninstall()  # thawed, so the frozen count is this run's own
            jax.clear_caches()  # and its programs are first touches again
            runs[cell, True] = rehearse(cell, True)
            if native_libs.commit_scan_lib() is not None:
                runs[cell, False] = rehearse(cell, False)
        return runs[cell, native]

    return get
