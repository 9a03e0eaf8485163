"""tmcost: the whole-program per-request cost-bound gate.

Six jobs: (1) run tmcost over the whole package on every tier-1
invocation, failing on anything beyond the (empty) cost baseline and
on ANY budget drift — the static form of "no request may cost more
than its reviewed budget"; (2) pin the budget table's coverage: every
RPC route handler and p2p recv handler has a reviewed entry; (3)
prove the gate non-vacuous by seeding violations into a COPY of the
REAL package (strip the serving cache from light_blocks, drop the
page clamp) and watching the exact rule turn red naming the handler;
(4) unit-test the engine against the seeded mini-packages in
tests/data/cost/ (each turning exactly its rule red, with
clamped/cached/guarded/suppressed twins green); (5) pin the engine
decisions this PR's own development surfaced (lin factors don't fire
superlinear, stability never crosses parameters, the pagination-slice
idiom, guard-then-raise re-classing); (6) the CLI exit contract and
the --cost-update refusal matrix.
"""

import json
import os
import shutil
import time

import pytest

from tendermint_tpu.analysis import tmcost
from tendermint_tpu.analysis.tmcheck.callgraph import build_package
from tendermint_tpu.analysis.tmcost import boundflow, roots as roots_mod
from tendermint_tpu.analysis.tmlint import load_baseline, new_violations

FIXTURES = os.path.join(os.path.dirname(__file__), "data", "cost")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_ROOT = os.path.join(REPO, "tendermint_tpu")


def _rule_hits(rep, rule):
    return [v for v in rep.violations if v.rule == rule]


def _fixture_report(name: str):
    pkg = build_package(os.path.join(FIXTURES, name))
    return tmcost.analyze(pkg)


# ---------------------------------------------------------------------------
# THE gate: whole package, empty baseline, zero budget drift


@pytest.fixture(scope="module")
def head_pkg():
    return build_package()


@pytest.fixture(scope="module")
def head_report(head_pkg):
    t0 = time.thread_time()  # the analyzer's CPU, not the machine's load
    rep = tmcost.analyze(head_pkg)
    rep.elapsed_s = time.thread_time() - t0
    return rep


def test_package_clean_against_baseline_and_budgets(head_report):
    """tmcost over the whole package: nothing beyond the (empty)
    counted baseline, and zero cost-budget findings — every serving
    root matches its reviewed budget exactly."""
    base, gated = tmcost.split_baselineable(head_report.violations)
    new = new_violations(
        base, load_baseline(tmcost.COST_BASELINE_PATH)
    )
    assert not new, "new tmcost violations:\n" + "\n".join(
        v.render() for v in new
    )
    assert not gated, "cost-budget drift:\n" + "\n".join(
        v.render() for v in gated
    )


def test_cost_baseline_is_checked_in_and_empty():
    """Every first-run finding was FIXED (the light_block/light_blocks
    serving cache, the evidence per-message clamp) or suppressed with
    an in-file rationale — none grandfathered, so the baseline must
    stay empty."""
    assert os.path.exists(tmcost.COST_BASELINE_PATH)
    with open(tmcost.COST_BASELINE_PATH) as f:
        data = json.load(f)
    assert data["entries"] == {}


def test_full_package_run_under_budget(head_report):
    """Runtime budget: the cost pass runs on every tier-1 invocation
    and must stay under 10 s for the whole package (measured ~3 s
    including the call-graph build). Times the module fixture's run
    rather than paying a second analyze."""
    assert head_report.elapsed_s < 10.0, (
        f"tmcost full-package run took {head_report.elapsed_s:.1f}s"
    )


def test_budgets_cover_every_rpc_route_and_p2p_recv_handler(
    head_report,
):
    """The head-catalog pin: cost_budgets.json covers EVERY discovered
    serving root — all RPC route handlers, all p2p recv handlers
    (Envelope-annotated + inline envelope loops), and the reviewed
    consensus entry points — with no stale extras."""
    budgets = tmcost.load_budgets()
    computed = set(head_report.costs)
    assert set(budgets) == computed
    fams = {}
    for rec in budgets.values():
        fams[rec["family"]] = fams.get(rec["family"], 0) + 1
    # every routes() entry in rpc/core.py is RPCRequest-annotated, so
    # the rpc family must be at least that big (+ the jsonrpc dispatch
    # chokepoint); the p2p family covers the reactor handlers
    assert fams["rpc"] >= 37, fams
    assert fams["p2p"] >= 13, fams
    assert fams["consensus"] == len(roots_mod.CONSENSUS_ROOTS)
    for rid in (
        "rpc/core.py:Environment.light_blocks",
        "rpc/core.py:Environment.tx_proofs",
        "rpc/core.py:Environment.broadcast_tx_commit",
        "consensus/reactor.py:ConsensusReactor._handle_vote_msg",
        "evidence/reactor.py:EvidenceReactor._recv_routine",
        "mempool/reactor.py:MempoolReactor._recv_routine",
        "statesync/reactor.py:StatesyncReactor._on_light_msg",
        "types/validation.py:verify_commit",
    ):
        assert rid in budgets, f"missing budget for {rid}"


def test_consensus_roots_all_resolve(head_pkg):
    """Adding a CONSENSUS_ROOTS entry is a reviewed change; a key that
    no longer resolves is a silently weakened gate."""
    for key in roots_mod.CONSENSUS_ROOTS:
        assert key in head_pkg.functions, key


def test_serving_cache_cost_is_visible_in_budgets(head_report):
    """The cached light_blocks budget records the CLAMPED page plus
    the cache's cold-miss per-block encode — the pre-fix per-request
    re-assembly shape (vset with no clamp factor) must be gone."""
    rec = head_report.costs["rpc/core.py:Environment.light_blocks"]
    assert "clamped" in rec["cost"]
    assert all("attacker" not in t and "store" not in t
               for t in rec["cost"]), rec
    # the single-block route is a pure cache lookup on the warm path
    lb = head_report.costs["rpc/core.py:Environment.light_block"]
    assert all("attacker" not in t for t in lb["cost"]), lb


def test_head_suppression_catalog_is_exactly_the_reviewed_sites(
    head_report,
):
    """The accepted-by-rationale sites are exactly: the three
    block_results encode() loops (generic-encoder summary imprecision,
    the real cost is block-linear) and the statesync ConsensusParams
    encode (a fixed handful of ints). Every other first-run finding
    got a real fix — the serving cache for light_block/light_blocks,
    the per-message evidence clamp. A new entry here means someone
    added a `# tmcost: <rule>-ok` — review it, then extend this pin
    deliberately."""
    by_site = {(rule, path) for rule, path, _ln in head_report.suppressed}
    assert by_site == {
        ("cost-superlinear", "rpc/core.py"),
        ("cost-recompute", "statesync/reactor.py"),
    }
    assert len(head_report.suppressed) == 4


# ---------------------------------------------------------------------------
# budget gate semantics (tmp golden files)


def _write_budgets(tmp_path, roots):
    p = tmp_path / "budgets.json"
    p.write_text(json.dumps({"note": "", "roots": roots}))
    return str(p)


def test_budget_missing_root_is_red(head_pkg, tmp_path):
    rep = tmcost.analyze(head_pkg, budgets_path=_write_budgets(
        tmp_path, {}
    ))
    hits = _rule_hits(rep, "cost-budget")
    assert len(hits) == len(rep.costs)
    assert any("no reviewed cost budget" in v.message for v in hits)


def test_budget_drift_both_directions_and_stale_are_red(
    head_pkg, tmp_path
):
    good = {rid: dict(rec) for rid, rec in tmcost.analyze(
        head_pkg
    ).costs.items()}
    # cheaper-than-budgeted is ALSO drift: a budget raise or cut is a
    # reviewed change either way
    rid = "rpc/core.py:Environment.light_blocks"
    good[rid] = {"family": "rpc", "cost": ["attacker"]}
    good["rpc/core.py:Environment.gone_route"] = {
        "family": "rpc", "cost": ["const"],
    }
    rep = tmcost.analyze(
        head_pkg, budgets_path=_write_budgets(tmp_path, good)
    )
    msgs = [v.message for v in _rule_hits(rep, "cost-budget")]
    assert len(msgs) == 2
    assert any("cost drift" in m and "light_blocks" in m for m in msgs)
    assert any("stale budget entry" in m for m in msgs)


def test_budget_findings_never_absorbed_by_baseline(
    head_pkg, tmp_path
):
    """cost-budget is golden-gated: new_cost_violations reports it
    even though the counted baseline is consulted for the dataflow
    rules (the tmtrace laundering class)."""
    new = tmcost.new_cost_violations(
        head_pkg, baseline_path=tmcost.COST_BASELINE_PATH
    )
    assert not new  # clean head
    rep_new = tmcost.analyze(
        head_pkg, budgets_path=_write_budgets(tmp_path, {})
    )
    base, gated = tmcost.split_baselineable(rep_new.violations)
    assert gated and not base


# ---------------------------------------------------------------------------
# seeded violations into a COPY of the REAL package (non-vacuousness)


@pytest.fixture()
def pkg_copy(tmp_path):
    dst = tmp_path / "tendermint_tpu"
    shutil.copytree(
        PKG_ROOT, dst, ignore=shutil.ignore_patterns("__pycache__")
    )
    return dst


def _analyze_copy(dst):
    from tendermint_tpu.analysis.tmcheck import callgraph

    p = callgraph.Package(str(dst), "tendermint_tpu")
    p.build()
    return tmcost.analyze(p)


def test_stripping_the_serving_cache_turns_recompute_red(pkg_copy):
    """Acceptance A/B, direction one: restore the pre-fix light_blocks
    shape (per-request re-assembly + re-encode) and the cost-recompute
    rule comes back red NAMING THE HANDLER."""
    core = pkg_copy / "rpc" / "core.py"
    src = core.read_text()
    old = (
        "blob = self.serving_cache.encoded_light_block(\n"
        "                    min_h + off\n"
        "                )\n"
        "                if blob is None:\n"
        "                    break\n"
        "                w.message(1, blob)"
    )
    new = (
        "lb = self.serving_cache.light_block_at(min_h + off)\n"
        "                if lb is None:\n"
        "                    break\n"
        "                w.message(1, lb.to_proto())"
    )
    assert old in src, "light_blocks serving loop moved; update test"
    core.write_text(src.replace(old, new))
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in _rule_hits(rep, "cost-recompute")
        if v.path == "rpc/core.py"
    ]
    assert hits, "uncached per-request re-encode not flagged"
    assert any(
        "Environment.light_blocks" in v.message for v in hits
    ), [v.message for v in hits]


def test_dropping_the_page_clamp_turns_superlinear_and_budget_red(
    pkg_copy,
):
    """Acceptance A/B, direction two: removing the light_blocks page
    clamp makes the loop store-range-sized — cost-superlinear fires
    (store x per-block vset encode) AND the budget gate reports the
    drift."""
    core = pkg_copy / "rpc" / "core.py"
    src = core.read_text()
    old = "for off in range(min(max_h - min_h + 1, cap)):"
    new = "for off in range(max_h - min_h + 1):"
    # `cap` only appears in light_blocks (blockchain clamps with a
    # literal) — exactly one site to strip
    assert src.count(old) == 1, "light_blocks page loop moved"
    core.write_text(src.replace(old, new))
    rep = _analyze_copy(pkg_copy)
    sl = [
        v for v in _rule_hits(rep, "cost-superlinear")
        if v.path == "rpc/core.py"
        and "Environment.light_blocks" in v.message
    ]
    assert sl, "unclamped store-range page loop not flagged"
    drift = [
        v for v in _rule_hits(rep, "cost-budget")
        if "light_blocks" in v.message and "cost drift" in v.message
    ]
    assert drift, "budget gate missed the cost change"


# ---------------------------------------------------------------------------
# fixture mini-packages: each rule red exactly once per seeded site,
# twins green


def test_fixture_superlinear_red_and_twins_green():
    rep = _fixture_report("superlinear_pkg")
    hits = _rule_hits(rep, "cost-superlinear")
    assert {(v.path, v.line) for v in hits} == {
        ("handlers.py", 17),  # nested loops
        ("handlers.py", 54),  # helper fold at the call site
    }
    assert all("attacker*vset" in v.message for v in hits)
    # witness names the serving root
    assert all("scan" in v.message for v in hits)
    assert ("cost-superlinear", "handlers.py", 37) in rep.suppressed


def test_fixture_recompute_red_and_twins_green():
    rep = _fixture_report("recompute_pkg")
    hits = _rule_hits(rep, "cost-recompute")
    assert [(v.path, v.line) for v in hits] == [("handlers.py", 17)]
    assert "Env.header_raw" in hits[0].message
    assert ("cost-recompute", "handlers.py", 29) in rep.suppressed


def test_fixture_alloc_red_and_twins_green():
    rep = _fixture_report("alloc_pkg")
    hits = _rule_hits(rep, "cost-unclamped-alloc")
    assert {(v.path, v.line) for v in hits} == {
        ("handlers.py", 17),  # bytes(store-height)
        ("handlers.py", 27),  # b"\x00" * attacker
    }
    assert (
        "cost-unclamped-alloc", "handlers.py", 41
    ) in rep.suppressed


# ---------------------------------------------------------------------------
# engine decision units (the development-surfaced pins)


def _one_fn_report(tmp_path, body: str):
    pkg_dir = tmp_path / "mini"
    pkg_dir.mkdir(parents=True)
    (pkg_dir / "__init__.py").write_text("")
    (pkg_dir / "m.py").write_text(
        "class RPCRequest:\n    params: dict = {}\n\n" + body
    )
    pkg = build_package(str(pkg_dir))
    return tmcost.analyze(pkg)


def test_lin_factors_do_not_fire_superlinear(tmp_path):
    """Nested unknown-provenance (lin) collections stay findable via
    budget drift but don't fire the red rule — the first development
    run drowned in 50+ label-tuple micro-iterations."""
    rep = _one_fn_report(
        tmp_path,
        "async def h(req: RPCRequest, groups, sinks):\n"
        "    for g in groups.values():\n"
        "        for s in sinks:\n"
        "            g(s)\n",
    )
    assert not _rule_hits(rep, "cost-superlinear")
    assert rep.costs["m.py:h"]["cost"] == ["lin*lin", "lin"] or (
        "lin*lin" in rep.costs["m.py:h"]["cost"]
    )


def test_stability_never_crosses_parameters(tmp_path):
    """A helper that encodes its PARAMETER is not a recompute site —
    only locally store-derived receivers count (the cross-caller
    contamination class: store content in one caller, request content
    in another)."""
    rep = _one_fn_report(
        tmp_path,
        "def enc(meta):\n"
        "    return meta.header.to_proto()\n\n"
        "async def h(req: RPCRequest, block_store):\n"
        "    meta = block_store.load_block_meta(1)\n"
        "    return enc(meta)\n",
    )
    assert not _rule_hits(rep, "cost-recompute")


def test_local_store_derivation_is_flagged(tmp_path):
    rep = _one_fn_report(
        tmp_path,
        "async def h(req: RPCRequest, block_store):\n"
        "    meta = block_store.load_block_meta(1)\n"
        "    return meta.header.to_proto()\n",
    )
    assert len(_rule_hits(rep, "cost-recompute")) == 1


def test_pagination_slice_idiom_is_clamped(tmp_path):
    """`x[start : start + per_page]` with a clamped per_page bounds
    the slice LENGTH even when start is attacker-chosen (the
    validators/tx_search page shape)."""
    rep = _one_fn_report(
        tmp_path,
        "async def h(req: RPCRequest, vals):\n"
        "    page = int(req.params.get('page', 1))\n"
        "    per_page = min(int(req.params.get('per_page', 30)), 100)\n"
        "    start = (page - 1) * per_page\n"
        "    sel = vals.validators[start : start + per_page]\n"
        "    out = 0\n"
        "    for v in sel:\n"
        "        for w in vals.validators:\n"
        "            out += 1\n"
        "    return out\n",
    )
    # clamped page x vset = NOT superlinear (one clamp is enough)
    assert not _rule_hits(rep, "cost-superlinear")


def test_guard_then_raise_reclasses_the_bound(tmp_path):
    """`if height > top: raise` pins an attacker int into the store
    range; comparing against a constant clamps it."""
    rep = _one_fn_report(
        tmp_path,
        "MAX_N = 100\n\n"
        "async def h(req: RPCRequest, block_store):\n"
        "    n = int(req.params.get('n'))\n"
        "    if n > MAX_N:\n"
        "        raise ValueError('too big')\n"
        "    return bytes(n)\n",
    )
    assert not _rule_hits(rep, "cost-unclamped-alloc")
    # the unguarded twin is alloc_pkg's attacker_repeat fixture


def test_envelope_loop_is_the_request_boundary(tmp_path):
    """A p2p root's own `async for envelope in channel` loop is the
    per-request boundary, not a cost factor — but a loop over the
    MESSAGE's content still counts."""
    rep = _one_fn_report(
        tmp_path,
        "class Envelope:\n    message = None\n\n"
        "async def recv(channel, vals):\n"
        "    async for envelope in channel:\n"
        "        for item in envelope.message.items_list:\n"
        "            for v in vals.validators:\n"
        "                item(v)\n",
    )
    rec = rep.costs["m.py:recv"]
    # attacker*vset from the message-content nesting fires, and the
    # envelope loop itself contributed no third factor to any term
    assert _rule_hits(rep, "cost-superlinear")
    assert not any(
        t.count("*") >= 2 for t in rec["cost"]
    ), rec


def test_store_height_range_classifies_store(tmp_path):
    """`range(store.height() - store.base())`-shaped walks are
    store-class: unbounded over the chain's life."""
    rep = _one_fn_report(
        tmp_path,
        "async def h(req: RPCRequest, block_store, vals):\n"
        "    top = block_store.height()\n"
        "    base = block_store.base()\n"
        "    for hh in range(top - base + 1):\n"
        "        for v in vals.validators:\n"
        "            v(hh)\n",
    )
    hits = _rule_hits(rep, "cost-superlinear")
    assert hits and "store" in hits[0].message


def test_while_event_loops_are_not_cost_factors(tmp_path):
    """`while not closed.is_set()` pump loops don't contribute terms;
    a while whose COMPARISON reads an attacker counter does."""
    rep = _one_fn_report(
        tmp_path,
        "async def pump(req: RPCRequest, ws, sub):\n"
        "    while not ws.closed.is_set():\n"
        "        await sub.next()\n",
    )
    assert rep.costs["m.py:pump"]["cost"] == ["const"]
    rep2 = _one_fn_report(
        tmp_path / "w2",
        "async def count(req: RPCRequest, vals):\n"
        "    n = int(req.params.get('n'))\n"
        "    i = 0\n"
        "    while i < n:\n"
        "        for v in vals.validators:\n"
        "            v(i)\n"
        "        i += 1\n",
    )
    assert _rule_hits(rep2, "cost-superlinear")


# ---------------------------------------------------------------------------
# CLI contract


def _lint_main(argv):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lintcli", os.path.join(REPO, "scripts", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(argv)


def test_cli_cost_section_clean_head():
    assert _lint_main(["--cost"]) == 0


def test_cli_cost_update_refusal_matrix():
    # --cost-update refuses combined/filtered runs
    assert _lint_main(["--cost-update", "--adv"]) == 2
    assert _lint_main(["--cost-update", "--rule", "det-float"]) == 2
    assert _lint_main(["--cost-update", "--baseline-update"]) == 2
    assert _lint_main(["--cost-update", "--schema-update"]) == 2
    # the other update modes refuse --cost
    assert _lint_main(["--schema-update", "--cost"]) == 2
    assert _lint_main(["--signatures-update", "--cost"]) == 2


def test_cli_list_rules_includes_cost(capsys):
    assert _lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid, _ in tmcost.RULES:
        assert rid in out
