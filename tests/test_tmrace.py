"""tmrace: the whole-program static data-race / lock-order gate.

Three jobs: (1) run tmrace over the whole package on every tier-1
invocation, failing on anything beyond the (empty) race baseline —
the static complement of lockwatch's runtime witness; (2) unit-test
the analysis against the seeded mini-packages in tests/data/race/;
(3) pin the RANK_EDGES contract: every edge lockwatch declares static
must be derivable from source, so the rank table can't drift.
"""

import importlib.util
import os
import subprocess
import sys
import threading
import time

import pytest

from .conftest import parse_walk_cpu_s
from tendermint_tpu.analysis import lockwatch, tmrace
from tendermint_tpu.analysis.tmlint import (
    Violation,
    load_baseline,
    new_violations,
    save_baseline,
)
from tendermint_tpu.analysis.tmcheck.callgraph import build_package
from tendermint_tpu.analysis.tmrace.lockorder import (
    STATIC_RANK_NAMES,
    ranked_edges,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "data", "race")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RANK_FIXTURE_NAMES = {"mod.py:a_lock": "A", "mod.py:b_lock": "B"}


def _fixture_report(name: str, **kwargs):
    pkg = build_package(os.path.join(FIXTURES, name))
    kwargs.setdefault("include_test_roots", False)
    kwargs.setdefault("rank_edges", {})
    kwargs.setdefault("rank_names", {})
    return tmrace.analyze(pkg, **kwargs)


# ---------------------------------------------------------------------------
# THE gate: whole package against the checked-in (empty) baseline


@pytest.fixture(scope="module")
def head_report():
    return tmrace.analyze()


def test_package_clean_against_baseline(head_report):
    """tmrace over the whole package; anything beyond
    tmrace/race_baseline.json fails tier-1 — fix it, suppress it with
    a justified `# tmrace: race-ok`/`guarded-by=`, or consciously
    re-baseline (docs/static_analysis.md)."""
    new = new_violations(
        head_report.violations, load_baseline(tmrace.RACE_BASELINE_PATH)
    )
    assert not new, "new tmrace violations:\n" + "\n".join(
        v.render() for v in new
    )


def test_race_baseline_is_checked_in_and_empty():
    """Every true positive the first full run surfaced was fixed (the
    faults.py env-latch ordering, the kernel _DEFAULT double-construct)
    or carries an in-file justified suppression, so the baseline must
    stay empty — new findings fail loudly, not silently grandfather."""
    assert os.path.exists(tmrace.RACE_BASELINE_PATH)
    assert load_baseline(tmrace.RACE_BASELINE_PATH) == {}


def test_full_package_run_under_budget():
    """Runtime budget: the race pass runs on every tier-1 invocation
    and must stay bounded for the whole package, call-graph build
    included. The pin was 10 s of wall time, twice the ~5 s the pass
    took when it was set; under tier-1's six workers, and on a sandbox
    whose CPU runs the pass alone in 12-15 s, that measured the
    machine. It is now 32 parse-and-walks of the package in CPU time of
    this thread (conftest.parse_walk_cpu_s, taken before and after):
    the pass costs 14-17 of them, so the headroom is the same factor
    of two and a blow-up still fails."""
    unit = parse_walk_cpu_s()
    t0 = time.thread_time()
    tmrace.analyze()
    elapsed = time.thread_time() - t0
    unit = (unit + parse_walk_cpu_s()) / 2
    assert elapsed < 32 * unit, (
        f"tmrace full-package run took {elapsed:.1f}s of CPU, "
        f"{elapsed / unit:.1f} parse-and-walks of the package"
    )


# ---------------------------------------------------------------------------
# thread-root discovery over the real package


def test_head_root_catalog(head_report):
    """The statically enumerated entry points include the idioms the
    codebase actually uses: spawned threads (breaker probe, gather
    watchdog), the probe retry Timer, the asyncio main loop with the
    consensus receive loop labeled, and RPC registration tables."""
    by_key = {}
    for r in head_report.roots:
        by_key.setdefault((r.kind, r.key[0]), []).append(r)
    assert ("thread", "crypto/breaker.py") in by_key
    assert ("thread", "crypto/tpu_verifier.py") in by_key
    assert ("timer", "crypto/breaker.py") in by_key
    kinds = {r.kind for r in head_report.roots}
    assert "receive-loop" in kinds
    assert "rpc" in kinds
    # spawned identities race themselves; the single event loop doesn't
    assert all(
        r.self_concurrent for r in head_report.roots if r.kind == "thread"
    )
    assert not any(
        r.self_concurrent for r in head_report.roots if r.kind == "async"
    )


def test_callback_escape_reaches_probe_thread(head_report):
    """The breaker set_probe idiom: _device_probe is only ever CALLED
    through CircuitBreaker._run_probe's stored callback, so it must be
    rooted under the probe thread's identity — the chain that makes
    tpu_verifier's watchdog/deadline machinery concurrent with the
    main loop."""
    ids = head_report.identities.get(
        ("crypto/tpu_verifier.py", "_device_probe"), set()
    )
    assert "thread:crypto/breaker.py:CircuitBreaker._run_probe" in ids


def test_concurrent_region_covers_shared_metrics(head_report):
    """Metric mutators are reachable from the main loop AND the probe
    machinery — exactly the multi-root shape the lockset pass exists
    to check."""
    ids = head_report.identities.get(("libs/metrics.py", "Counter.inc"))
    assert ids is not None and len(ids) >= 2
    assert ("libs/metrics.py", "Counter.inc") in head_report.concurrent_region


# ---------------------------------------------------------------------------
# seeded fixtures (tests/data/race/): each check fails when violated


def test_fixture_unguarded_global_flagged():
    report = _fixture_report("unguarded_pkg")
    rules = {(v.rule, v.line) for v in report.violations}
    assert ("race-unguarded-global", 14) in rules, [
        v.render() for v in report.violations
    ]
    # the _lock-guarded twin of the same shape passes
    assert not any("GUARDED" in v.message for v in report.violations)


def test_fixture_cross_identity_single_degree_endpoints_flagged():
    """A race whose endpoints are each reachable from only ONE root
    identity (handler: main-loop only; worker_write: its own thread
    only) must still be paired — the concurrency cut is per VARIABLE
    over the union of the sites' identities, not per function, so
    neither endpoint being in the concurrent region is no excuse."""
    report = _fixture_report("split_pkg")
    flagged = {
        v.line
        for v in report.violations
        if v.rule == "race-unguarded-global" and "global `SPLIT`" in v.message
    }
    assert flagged == {18, 36}, [v.render() for v in report.violations]
    # handler is main-loop-only (degree 1): NOT in the per-function
    # concurrent region, so its line-36 site is only reachable through
    # the per-variable union — the line the old collector dropped
    # (worker_write IS in the region: a spawned thread root is
    # self-concurrent, start() may run twice)
    assert ("mod.py", "handler") not in report.concurrent_region
    assert report.identities[("mod.py", "handler")] == {"main-loop"}
    # the locked twin with the same split shape passes
    assert not any("SPLIT_GUARDED" in v.message for v in report.violations)


def test_fixture_nested_def_scopes_do_not_leak():
    """Global declarations and locally-bound names are per-SCOPE:
    a nested `global N` must not reclassify the enclosing function's
    plain local `N = 1` as a module-global write, and a name bound
    only inside a nested def must not shadow the outer function's
    read of the same-named module global (which pairs reader's thread
    identity with writer_handler's main-loop write)."""
    from tendermint_tpu.analysis.tmrace.lockset import Summarizer

    pkg = build_package(os.path.join(FIXTURES, "nested_pkg"))
    report = _fixture_report("nested_pkg")
    # the de-shadowed read makes M a two-identity variable: flagged
    m_lines = {
        v.line
        for v in report.violations
        if v.rule == "race-unguarded-global" and "global `M`" in v.message
    }
    assert m_lines == {38}, [v.render() for v in report.violations]
    # N never crosses identities — no violation either way; the scope
    # split is asserted at the summary level
    assert not any("global `N`" in v.message for v in report.violations)
    s = Summarizer(pkg)
    outer = s.summarize_function(pkg.functions[("mod.py", "outer_local")])
    assert not any(
        a.var == ("g", "mod.py", "N") and a.write for a in outer.accesses
    ), "enclosing local write leaked into global classification"
    helper_key = next(
        k
        for k in pkg.functions
        if k[0] == "mod.py" and k[1].endswith("helper_n")
    )
    nested = s.summarize_function(pkg.functions[helper_key])
    assert any(
        a.var == ("g", "mod.py", "N") and a.write for a in nested.accesses
    ), "the nested def's OWN global write must still be seen"


def test_fixture_unguarded_witness_names_both_roots():
    report = _fixture_report("unguarded_pkg")
    v = next(
        v for v in report.violations if v.rule == "race-unguarded-global"
    )
    assert "main-loop" in v.message
    assert "thread:" in v.message


def test_fixture_rank_contradiction_flagged():
    report = _fixture_report(
        "rank_pkg", rank={"A": 10, "B": 5}, rank_names=RANK_FIXTURE_NAMES
    )
    lock_order = [
        v for v in report.violations if v.rule == "race-lock-order"
    ]
    assert any(
        "contradicts lockwatch RANK" in v.message for v in lock_order
    )


def test_fixture_cycle_flagged_without_any_rank():
    """c_lock/d_lock are unranked: the A->B B->A cycle is still a
    latent deadlock and must be flagged on the raw static graph."""
    report = _fixture_report("rank_pkg")
    assert any(
        v.rule == "race-lock-order" and "cycle" in v.message
        for v in report.violations
    )


def test_fixture_rank_drift_flagged():
    """An edge declared static in RANK_EDGES that the source does not
    produce is itself a violation — the drift direction lockwatch
    cannot see."""
    report = _fixture_report(
        "rank_pkg",
        rank={},
        rank_names=RANK_FIXTURE_NAMES,
        rank_edges={("B", "A"): "static"},
    )
    assert any(
        v.rule == "race-rank-drift" for v in report.violations
    )
    # and an unknown classification string is an error, not a skip
    report = _fixture_report(
        "rank_pkg",
        rank={},
        rank_names=RANK_FIXTURE_NAMES,
        rank_edges={("A", "B"): "sometimes"},
    )
    assert any(
        v.rule == "race-rank-drift" and "sometimes" in v.message
        for v in report.violations
    )


def test_fixture_suppression_forms_pass():
    """race-ok, guarded-by=, and a justified tmlint
    lock-global-mutation disable each silence the finding."""
    report = _fixture_report("suppressed_pkg")
    assert report.violations == [], [
        v.render() for v in report.violations
    ]


def test_fixture_baseline_round_trip(tmp_path):
    """Counted-fingerprint semantics, same as tmlint/tmcheck: saving
    masks the current findings; one MORE identical-shaped site still
    fails the gate."""
    report = _fixture_report("unguarded_pkg")
    assert report.violations
    path = str(tmp_path / "race_baseline.json")
    save_baseline(report.violations, path)
    assert new_violations(report.violations, load_baseline(path)) == []
    extra = report.violations + [
        Violation(
            rule="race-unguarded-global",
            path="mod.py",
            line=99,
            col=0,
            message="seeded",
            source="OTHER = 1",
        )
    ]
    assert len(new_violations(extra, load_baseline(path))) == 1


# ---------------------------------------------------------------------------
# the RANK_EDGES contract: lockwatch's table cannot drift from source


def test_rank_edges_static_all_derived(head_report):
    derived = ranked_edges(head_report.edges)
    for edge, cls in lockwatch.RANK_EDGES.items():
        assert cls in ("static", "runtime-only"), edge
        if cls == "static":
            assert edge in derived, (
                f"RANK_EDGES declares {edge} static but tmrace cannot "
                "derive it — update the table or mark it runtime-only"
            )


def test_every_derived_edge_is_declared(head_report):
    """The inverse direction: a NEW statically derived edge between
    ranked locks must be added to RANK_EDGES — the table is the
    reviewed inventory of the lock graph."""
    for edge in ranked_edges(head_report.edges):
        assert edge in lockwatch.RANK_EDGES, (
            f"statically derived edge {edge} missing from "
            "lockwatch.RANK_EDGES"
        )


def test_static_rank_names_round_trip():
    """STATIC_RANK_NAMES maps lockset identities onto lockwatch's RANK
    namespace; every target must actually be ranked, and every edge in
    RANK_EDGES must stay inside that namespace."""
    for static_name, rank_name in STATIC_RANK_NAMES.items():
        assert rank_name in lockwatch.RANK, (static_name, rank_name)
    for a, b in lockwatch.RANK_EDGES:
        assert a in lockwatch.RANK and b in lockwatch.RANK, (a, b)


def test_rank_declared_edges_respect_rank_order():
    for (a, b), _cls in lockwatch.RANK_EDGES.items():
        assert lockwatch.RANK[a] < lockwatch.RANK[b], (
            f"RANK_EDGES entry {(a, b)} contradicts RANK itself"
        )


# ---------------------------------------------------------------------------
# CLI contract (scripts/lint.py --race)


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), *args],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def _load_lint_module():
    spec = importlib.util.spec_from_file_location(
        "lint_cli", os.path.join(REPO, "scripts", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_cli_race_clean_exit_zero():
    r = _run_cli("--race", "--stats")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[race]" in r.stdout


def test_cli_race_seeded_violation_exit_one(monkeypatch):
    """The exit contract end to end: a race finding beyond the (empty)
    baseline exits 1 through the real main()."""
    lint = _load_lint_module()
    seeded = [
        Violation(
            rule="race-unguarded-global",
            path="crypto/fake.py",
            line=1,
            col=0,
            message="seeded unguarded shared write",
            source="X = 1",
        )
    ]
    monkeypatch.setattr(
        lint.tmrace, "race_violations", lambda pkg=None, **kw: seeded
    )
    monkeypatch.setattr(
        lint.tmcheck, "build_package", lambda root=None: None
    )
    assert lint.main(["--race"]) == 1
    # rank-contradiction findings ride the same rule set / exit path
    seeded[0] = Violation(
        rule="race-lock-order",
        path="crypto/fake.py",
        line=1,
        col=0,
        message="seeded RANK-contradicting edge",
        source="with b_lock:",
    )
    assert lint.main(["--race"]) == 1


def test_cli_race_baseline_update_refuses_filtered_runs():
    """Same hazard the PR-5 fix closed for --schema-update: a filtered
    scan would overwrite the whole-file baseline with its subset."""
    r = _run_cli("--race", "--baseline-update", "--rule", "det-float")
    assert r.returncode == 2
    assert "full-package" in r.stderr
    r = _run_cli(
        "--race", "--baseline-update", "tendermint_tpu/crypto/faults.py"
    )
    assert r.returncode == 2


def test_cli_race_and_schema_combine():
    # section flags compose pairwise, same as --taint --schema and
    # --taint --race: both requested sections run, the others don't
    r = _run_cli("--race", "--schema", "--stats")
    assert r.returncode == 0
    assert "[schema+race]" in r.stdout


def test_cli_list_rules_includes_race():
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rid, _title in tmrace.RULES:
        assert rid in r.stdout


# ---------------------------------------------------------------------------
# regression tests for the true positives the first full run surfaced


def test_faults_env_latch_never_answers_before_rules_load(monkeypatch):
    """tmrace finding #1 (crypto/faults.py): armed() used to set
    _ENV_LOADED BEFORE parsing TM_TPU_FAULT, so a second thread could
    see the latch up and answer False while the first was still
    parsing — a fault rule armed via env could be silently skipped
    exactly once. The latch now rises under _LOCK after _ARMED is
    refreshed."""
    from tendermint_tpu.crypto import faults

    monkeypatch.setenv("TM_TPU_FAULT", "tpu.dispatch:raise")
    faults.reset()
    faults._ENV_LOADED = False

    entered = threading.Event()
    proceed = threading.Event()
    real_parse = faults._parse_rule

    def slow_parse(spec):
        entered.set()
        assert proceed.wait(5), "test deadlock"
        return real_parse(spec)

    monkeypatch.setattr(faults, "_parse_rule", slow_parse)
    results = {}
    t = threading.Thread(target=lambda: results.setdefault(
        "first", faults.armed()
    ), daemon=True)
    t.start()
    assert entered.wait(5)
    # release the parser shortly AFTER this thread is blocked on _LOCK
    threading.Timer(0.05, proceed.set).start()
    # old code: returns False here (latch already up, rules not loaded)
    assert faults.armed() is True
    t.join(5)
    assert results["first"] is True
    monkeypatch.delenv("TM_TPU_FAULT")
    faults.reset()
    faults.load_env()  # re-sync armed state with the cleared env


@pytest.mark.parametrize(
    "module_name, class_name",
    [
        ("tendermint_tpu.ops.ed25519_kernel", "Ed25519Verifier"),
        ("tendermint_tpu.ops.sr25519_kernel", "Sr25519Verifier"),
    ],
)
def test_default_verifier_single_construction_under_hammer(
    module_name, class_name, monkeypatch
):
    """tmrace finding #2 (ops kernels): concurrent first calls to
    default_verifier() — the asyncio loop and the breaker probe thread
    — could each construct a verifier, and the loser's compiled-program
    cache was silently discarded. Now double-checked under
    _DEFAULT_LOCK: exactly one construction, everyone gets it."""
    mod = importlib.import_module(module_name)
    built = []
    barrier = threading.Barrier(8)

    class Counting:
        def __init__(self):
            built.append(self)
            time.sleep(0.05)  # widen the old race window

    monkeypatch.setattr(mod, class_name, Counting)
    monkeypatch.setattr(mod, "_DEFAULT", None)

    got = []

    def hammer():
        barrier.wait(5)
        got.append(mod.default_verifier())

    threads = [
        threading.Thread(target=hammer, daemon=True) for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert len(built) == 1, f"{len(built)} constructions under contention"
    assert all(g is built[0] for g in got)
