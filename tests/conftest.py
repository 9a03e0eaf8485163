"""Test configuration.

Tests run on a virtual 8-device CPU mesh so sharding/collective code paths
(`tendermint_tpu.parallel`) are exercised without TPU hardware. This must be
set before jax is imported anywhere.
"""

import os

# The chip machine's environment selects the TPU; unit tests always run
# on a virtual 8-device CPU mesh so sharding and collective paths are
# exercised deterministically (the chip is chip_smoke.py's business).
# jax.config wins over the env pin.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

from tendermint_tpu.ops import compile_cache  # noqa: E402

# Persist XLA compilations across test runs (the ed25519 kernel is a
# big program; first compile is ~1-4 min, cached reloads are instant).
compile_cache.enable()


def parse_walk_cpu_s() -> float:
    """CPU seconds this thread takes, now, to parse and walk every
    source of the package once: the unit of the whole-package
    analyzers' run-time budgets (tests/test_tmrace.py, test_tmlive.py).
    Every analyzer starts with exactly this step, so a budget of so many
    units follows the machine's speed at this moment (tier-1's six
    workers share the cores, and sandboxes differ by a factor of two)
    and the package's size, and pins what the analysis costs on top."""
    import ast
    import glob
    import time

    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tendermint_tpu",
    )
    t0 = time.thread_time()
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            for _ in ast.walk(ast.parse(f.read())):
                pass
    return time.thread_time() - t0


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """Drop compiled-executable references after every test module.

    Each XLA:CPU LoadedExecutable holds many mmap'd regions; across the
    full suite they accumulate to the kernel's vm.max_map_count limit
    (65530 — observed 65313 maps one minute before a C-level abort in
    backend_compile_and_load at the late test_sharding module, 4 runs
    in a row, never in isolation). Clearing jax's caches lets the
    executables GC and unmap, so the per-process peak stays at the
    biggest single module, not the sum of all modules. Recompiles on
    module boundaries are mostly persistent-cache hits.

    A module that installed the device seam and dispatched may have left
    the heap frozen (libs/heap.py): thaw first, or the executables'
    cycles would sit in the permanent generation, where no collection
    finds them."""
    yield
    import gc

    jax.clear_caches()
    gc.unfreeze()
    gc.collect()


@pytest.fixture(autouse=True, scope="module")
def _byz_plane_leak_guard():
    """Fail fast when a test module leaks the byzantine plane.

    The adversary plane is ambient process state (TM_TPU_BYZ env,
    byzantine._RULES, the installed-harness registry): a module that
    arms it and forgets to disarm silently turns every LATER module's
    consensus nodes byzantine — failures would surface far from the
    leak (the tmmc model checker is especially exposed: its builds
    call byzantine.maybe_install on every node). Checked at every
    module boundary; the plane is healed before failing so one leak
    produces one failure, not a cascade."""
    yield
    import os as _os

    from tendermint_tpu.consensus import byzantine

    leaks = []
    if _os.environ.get("TM_TPU_BYZ"):
        leaks.append(f"TM_TPU_BYZ={_os.environ['TM_TPU_BYZ']!r} still set")
    n_rules = len(byzantine.rules())
    if n_rules:
        leaks.append(f"{n_rules} armed rule(s)")
    n_harn = len(byzantine.harnesses())
    if n_harn:
        leaks.append(f"{n_harn} registered harness(es)")
    if leaks:
        _os.environ.pop("TM_TPU_BYZ", None)
        byzantine.reset()
        pytest.fail(
            "byzantine plane leaked past a test module: "
            + "; ".join(leaks)
            + " (arm via monkeypatch/ExitStack and reset() in teardown)"
        )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running gates (ASAN sweep, big e2e runs)"
    )


# The suites that exercise real cross-thread lock interleavings
# (breaker probes, gather watchdogs, fault-plane chaos, schedule
# fuzzing) run under the lockwatch observer; everything else skips the
# wrapping overhead.
_LOCKWATCH_FILES = {
    "test_chaos_consensus.py",
    "test_faults.py",
    "test_fuzz.py",
    "test_schedule_fuzz.py",
}


@pytest.fixture(autouse=True)
def _lockwatch_guard(request):
    """Record the lock-acquisition graph during the chaos/fault/fuzz
    suites and fail the test on any witnessed lock-order cycle or
    rank-table violation — the runtime analog of `go test -race`
    plus Go's lockrank (tendermint_tpu/analysis/lockwatch.py; the
    proven-acyclic order is documented in its RANK table). Long holds
    are reported as warnings, not failures — a loaded CI box parks
    threads for unpredictable stretches — but every overrun also lands
    in the structured lockwatch.HOLD_LOG record, and
    tests/test_tmlive.py::test_witnessed_overruns_statically_explained
    asserts each one is either a tmlive-flagged/suppressed blocking
    site under that lock or covered by holdflow.OVERRUN_OK's reviewed
    scheduler-noise rationale."""
    if os.path.basename(str(request.node.fspath)) not in _LOCKWATCH_FILES:
        yield
        return
    from tendermint_tpu.analysis import lockwatch

    lockwatch.enable()
    try:
        yield
    finally:
        report = lockwatch.disable()
        assert not report.cycles, (
            "lockwatch: lock-order cycle witnessed\n" + report.render()
        )
        assert not report.order_violations(), (
            "lockwatch: rank-table violation\n" + report.render()
        )
        if report.long_holds:
            import warnings

            warnings.warn(
                "lockwatch: hold-time budget exceeded\n" + report.render(),
                stacklevel=1,
            )


@pytest.fixture(autouse=True)
def _fresh_fault_plane():
    """Disarm the fault plane and drop every circuit breaker after each
    test: a chaos test that tripped a route breaker must not silently
    reroute a later test's device-path assertions to the CPU factory.
    Breakers are created on demand (closed) so non-fault tests see the
    exact pre-breaker behavior."""
    yield
    from tendermint_tpu.crypto import breaker, faults

    faults.reset()
    breaker.reset_all()


@pytest.fixture(autouse=True)
def _fresh_sigcache():
    """Start every test with a cold verified-signature cache: the test
    fixtures are deterministic (fixed seeds/timestamps), so identical
    triples recur across modules and the process-global cache would
    otherwise make crypto-call-count and device-dispatch assertions
    order-dependent. The cache is pure speed — resetting never changes
    behavior."""
    from tendermint_tpu.crypto import sigcache

    sigcache.reset()
    yield


@pytest.fixture
def tmp_home(tmp_path):
    from tendermint_tpu.config import Config

    cfg = Config()
    cfg.base.home = str(tmp_path)
    cfg.ensure_dirs()
    return cfg
