"""tmsafe: the whole-program adversarial-input safety gate.

Five jobs: (1) run tmsafe over the whole package on every tier-1
invocation, failing on anything beyond the (empty) safe baseline —
the static form of "no wire message can buy asymmetric decode-time
work"; (2) prove the gate is not vacuous by seeding violations into a
COPY of the REAL package (strip the from_words clamp, strip a
handler's validate_basic) and watching the exact rule turn red;
(3) unit-test the engine against the seeded mini-packages in
tests/data/safe/ (each proven to turn exactly its own rule red, with
clamped/validated/suppressed twins green); (4) pin the taint-engine
regressions this PR's own development surfaced (`is None` must not
sanitize, constructor calls must return the tainted instance,
enumerate indexes are LEN); (5) the CLI exit contract and the
update-refusal matrix for --adv.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from tendermint_tpu.analysis import tmsafe
from tendermint_tpu.analysis.tmcheck.callgraph import build_package
from tendermint_tpu.analysis.tmcheck.schema import extract_package
from tendermint_tpu.analysis.tmlint import (
    Violation,
    load_baseline,
    new_violations,
    save_baseline,
)
from tendermint_tpu.analysis.tmsafe import taintflow, validate
from tendermint_tpu.analysis.tmsafe.sources import derive_entries
from tendermint_tpu.analysis.tmsafe.taintflow import LEN, VAL, TaintEngine

FIXTURES = os.path.join(os.path.dirname(__file__), "data", "safe")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_ROOT = os.path.join(REPO, "tendermint_tpu")


def _fixture_report(name: str):
    pkg = build_package(os.path.join(FIXTURES, name))
    return tmsafe.analyze(pkg)


# ---------------------------------------------------------------------------
# THE gate: whole package against the checked-in (empty) baseline


@pytest.fixture(scope="module")
def head_pkg():
    return build_package()


@pytest.fixture(scope="module")
def head_report(head_pkg):
    t0 = time.thread_time()  # the analyzer's CPU, not the machine's load
    rep = tmsafe.analyze(head_pkg)
    rep.elapsed_s = time.thread_time() - t0
    return rep


def test_package_clean_against_baseline(head_report):
    """tmsafe over the whole package; anything beyond
    tmsafe/safe_baseline.json fails tier-1 — fix it, suppress it with
    a justified `# tmsafe: <rule>-ok`, or consciously re-baseline
    (docs/static_analysis.md)."""
    new = new_violations(
        head_report.violations, load_baseline(tmsafe.SAFE_BASELINE_PATH)
    )
    assert not new, "new tmsafe violations:\n" + "\n".join(
        v.render() for v in new
    )


def test_safe_baseline_is_checked_in_and_empty():
    """Every first-run true positive was FIXED in-tree (the BitArray
    from_words clamp + packed elems encoding, the blockchain page-count
    clamp), none merely grandfathered, so the baseline must stay
    empty — new findings fail loudly."""
    assert os.path.exists(tmsafe.SAFE_BASELINE_PATH)
    with open(tmsafe.SAFE_BASELINE_PATH) as f:
        data = json.load(f)
    assert data["entries"] == {}


def test_full_package_run_under_budget(head_report):
    """Runtime budget: the adv pass runs on every tier-1 invocation
    and must stay under 10 s for the whole package (measured ~2.5 s
    including the call-graph build). Times the module fixture's run
    rather than paying a second analyze."""
    assert head_report.elapsed_s < 10.0, (
        f"tmsafe full-package run took {head_report.elapsed_s:.1f}s"
    )


def test_head_suppression_catalog_is_exactly_the_opaque_tx_sites(
    head_report,
):
    """The head catalog of accepted-by-rationale sites is exactly the
    two mempool-admission calls: a tx is opaque app bytes with no
    validate_basic of its own — CheckTx IS its validation. The batch
    ingest on the gossip receive loop (check_tx_batch, now a cataloged
    sink itself) plus the single serial-admission chokepoint all three
    RPC broadcast routes resolve to (Environment._admit_tx — the
    coalescing-batcher refactor collapsed the two per-route
    suppressions into one). Every other first-run finding got a real
    fix (BitArray clamp + packed elems, blockchain page clamp,
    evidence validate-before-add ×2), not a comment. A new entry here
    means someone added a `# tmsafe: <rule>-ok` — review the
    rationale, then extend this pin deliberately."""
    by_site = {(rule, path) for rule, path, _ln in head_report.suppressed}
    assert by_site == {
        ("safe-unvalidated-use", "mempool/reactor.py"),
        ("safe-unvalidated-use", "rpc/core.py"),
    }
    assert len(head_report.suppressed) == 2


# ---------------------------------------------------------------------------
# the machine-derived source catalog


def test_entries_cover_every_schema_decoder(head_pkg):
    """The decoder entry family IS the schema extraction's decoder
    set: every message with a dec_func resolves to an entry, so the
    source catalog cannot drift from the golden wire schema."""
    entries = {e.key for e in derive_entries(head_pkg)}
    messages, _ = extract_package(head_pkg.root, pkg=head_pkg)
    decoders = 0
    for mkey, msg in messages.items():
        if not msg.dec_func:
            continue
        path, _, tail = mkey.partition("::")
        cands = [(path, f"{tail}.{msg.dec_func}"), (path, msg.dec_func)]
        resolved = [k for k in cands if k in head_pkg.functions]
        if resolved:
            decoders += 1
            assert resolved[0] in entries, f"decoder {resolved[0]} not an entry"
    assert decoders >= 80  # 90+ messages, most with decoders


def test_entry_families_present(head_report):
    fams = {}
    for e in head_report.entries:
        fams[e.family] = fams.get(e.family, 0) + 1
    assert fams.get("decoder", 0) >= 80
    assert fams.get("rpc", 0) >= 30  # every RPCRequest route handler
    assert fams.get("rpc-parse", 0) == 3
    assert fams.get("wal", 0) == 2
    assert fams.get("p2p-framing", 0) >= 2
    assert fams.get("validate", 0) >= 20  # quadratic-rule scope


def test_region_reaches_the_delicate_helpers(head_pkg):
    """The taint region must include the helpers the first run's true
    positives lived in — BitArray.from_words (reached from
    decode_bit_array with VAL size) and FieldReader.__init__ (every
    decoder's receiver)."""
    eng = TaintEngine(head_pkg, derive_entries(head_pkg))
    eng.run()
    fw = ("libs/bits.py", "BitArray.from_words")
    assert fw in eng.states and eng.states[fw].analyzed
    assert eng.states[fw].param_taint.get("size") == VAL
    fr = ("encoding/proto.py", "FieldReader.__init__")
    assert fr in eng.states and eng.states[fr].analyzed


def test_mutation_sink_catalog_resolves(head_pkg):
    """Every MUTATION_SINKS key names a real function — the catalog
    cannot silently rot when a sink is moved or renamed."""
    for key in validate.MUTATION_SINKS:
        assert key in head_pkg.functions, f"stale sink catalog entry {key}"


# ---------------------------------------------------------------------------
# seeded violations against a copy of the REAL package


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
    return tmsafe.analyze(p)


def test_seeded_unclamped_from_words_turns_alloc_red(pkg_copy):
    """Acceptance: stripping the from_words MAX_BIT_ARRAY_SIZE clamp
    re-opens the real first-run finding — `1 << size` with a
    wire-chosen size — and the witness names the decode entry."""
    bits = pkg_copy / "libs" / "bits.py"
    src = bits.read_text()
    assert "MAX_BIT_ARRAY_SIZE:" in src
    start = src.index("        if size > MAX_BIT_ARRAY_SIZE:")
    end = src.index("        out = cls(size)")
    bits.write_text(src[:start] + src[end:])
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in rep.violations
        if v.rule == "safe-alloc-unbounded" and v.path == "libs/bits.py"
    ]
    assert hits, "unclamped 1 << size not flagged"
    assert "decode_bit_array" in hits[0].message


def test_seeded_unclamped_light_blocks_page_turns_alloc_red(pkg_copy):
    """ISSUE 11 satellite: stripping the bulk light_blocks route's
    page clamp re-opens the exact class PR 10's blockchain fix pinned
    — a range() bound built from attacker-chosen heights instead of a
    clamp expression — and the witness names the route handler."""
    core = pkg_copy / "rpc" / "core.py"
    src = core.read_text()
    needle = "for off in range(min(max_h - min_h + 1, cap)):"
    assert needle in src
    core.write_text(
        src.replace(needle, "for off in range(max_h - min_h + 1):")
    )
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in rep.violations
        if v.rule == "safe-alloc-unbounded" and v.path == "rpc/core.py"
    ]
    assert hits, "unclamped light_blocks page not flagged"
    assert "light_blocks" in hits[0].message


def test_seeded_dropped_validate_turns_unvalidated_red(pkg_copy):
    """Acceptance: deleting the vote handler's validate_basic() call
    makes the path to VoteSet.set_has_vote-family state unvalidated —
    the 25-site convention is a checked catalog now."""
    reactor = pkg_copy / "consensus" / "reactor.py"
    src = reactor.read_text()
    needle = (
        "        msg.validate_basic()\n"
        "        vote = msg.vote\n"
    )
    assert needle in src
    reactor.write_text(src.replace(needle, "        vote = msg.vote\n"))
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in rep.violations
        if v.rule == "safe-unvalidated-use"
        and v.path == "consensus/reactor.py"
    ]
    assert hits, "dropped validate_basic not flagged"
    assert "_handle_vote_msg" in hits[0].message


# ---------------------------------------------------------------------------
# seeded mini-packages: each turns exactly its own rule red


def test_fixture_alloc_unbounded():
    rep = _fixture_report("alloc_pkg")
    assert {v.rule for v in rep.violations} == {"safe-alloc-unbounded"}
    lines = {(v.path, v.line) for v in rep.violations}
    # bytes(n), range(count), b"\x00"*n, 1<<size, readexactly(length)
    assert len(lines) == 5
    assert any(p == "p2p/conn.py" for p, _ in lines)
    # clamped / len-guarded / min-clamped twins are green: no finding
    # may sit inside them
    bad_lines = {ln for p, ln in lines if p == "types/mod.py"}
    src = open(
        os.path.join(FIXTURES, "alloc_pkg", "types", "mod.py")
    ).read().splitlines()
    for ln in bad_lines:
        fn_region = "\n".join(src[max(0, ln - 8): ln])
        assert "decode_clamped" not in fn_region
        assert "decode_len_guarded" not in fn_region
        assert "decode_min_clamped" not in fn_region
    # the suppressed twin was exercised
    assert rep.stats["suppressed"] == 1


def test_fixture_index_unchecked():
    rep = _fixture_report("index_pkg")
    assert {v.rule for v in rep.violations} == {"safe-index-unchecked"}
    assert len(rep.violations) == 1  # checked/guarded/suppressed green
    assert rep.violations[0].line == 13
    assert rep.stats["suppressed"] == 1


def test_fixture_unvalidated_use():
    rep = _fixture_report("unval_pkg")
    assert {v.rule for v in rep.violations} == {"safe-unvalidated-use"}
    assert len(rep.violations) == 1
    v = rep.violations[0]
    assert "handle_bad" in v.message
    assert "VoteSet.add_vote" in v.message
    # validated + transitively-validated twins green, suppressed twin
    # counted
    assert rep.stats["suppressed"] == 1


def test_fixture_quadratic_decode():
    rep = _fixture_report("quad_pkg")
    assert {v.rule for v in rep.violations} == {"safe-quadratic-decode"}
    lines = sorted(v.line for v in rep.violations)
    # nested-loop decoder, list-membership scan, validate_basic nest
    assert len(lines) == 3
    # clamped-slice twin and set-membership twin are green
    msgs = " ".join(v.message for v in rep.violations)
    assert "O(n^2)" in msgs


def test_fixture_baseline_round_trip(tmp_path):
    """save_baseline over fixture findings -> zero new; a duplicated
    offending line overflows its counted fingerprint."""
    rep = _fixture_report("alloc_pkg")
    path = tmp_path / "safe_baseline.json"
    save_baseline(rep.violations, str(path), note=tmsafe.SAFE_BASELINE_NOTE)
    assert new_violations(rep.violations, load_baseline(str(path))) == []
    extra = rep.violations + [rep.violations[0]]
    over = new_violations(extra, load_baseline(str(path)))
    assert over and "baseline allows" in over[0].message


# ---------------------------------------------------------------------------
# engine regressions (tiny synthetic packages)


def _mini_pkg(tmp_path, source: str):
    d = tmp_path / "mini"
    (d / "types").mkdir(parents=True)
    (d / "types" / "mod.py").write_text(source)
    return build_package(str(d))


def test_is_none_check_does_not_sanitize(tmp_path):
    """Regression: `if data is None: return None` is an identity test,
    not a bound — the engine once sanitized `data` on it and went
    vacuously clean (the tmtrace is-exemption lesson, re-learned)."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    if data is None:\n"
        "        return None\n"
        "    r = FieldReader(data)\n"
        "    n = r.uint(1)\n"
        "    return bytes(n)\n"
    )))
    assert [v.rule for v in rep.violations] == ["safe-alloc-unbounded"]


def test_enumerate_index_is_len_bounded(tmp_path):
    """`for i, w in enumerate(parsed)`: the index is bounded by the
    collection's length — only the element keeps VAL."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    out = 0\n"
        "    for i, w in enumerate(r.get_all(1)):\n"
        "        out |= 1 << (64 * i)\n"  # index: LEN, no finding
        "    return out\n"
    )))
    assert rep.violations == []
    rep = tmsafe.analyze(_mini_pkg(tmp_path / "b", (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    out = 0\n"
        "    for i, w in enumerate(r.get_all(1)):\n"
        "        out |= 1 << w\n"  # element: VAL, flagged
        "    return out\n"
    )))
    assert [v.rule for v in rep.violations] == ["safe-alloc-unbounded"]


def test_slices_are_exempt_but_plain_index_is_not(tmp_path):
    """Python slices clamp (bounded by the source) — only plain
    subscripts are the aliasing hazard."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    n = r.uint(1)\n"
        "    return data[n : n + 4]\n"  # slice: exempt
    )))
    assert rep.violations == []


def test_except_valueerror_does_not_guard_index_sinks(tmp_path):
    """Review finding (this PR): `except ValueError` does NOT catch
    IndexError — and a NEGATIVE wire index raises nothing at all — so
    it must not sanitize an index sink the way `except IndexError`
    does."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "LOOKUP = ['a', 'b']\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    i = r.int64(1)\n"
        "    try:\n"
        "        return LOOKUP[i]\n"
        "    except ValueError:\n"
        "        raise ValueError('bad') from None\n"
    )))
    assert [v.rule for v in rep.violations] == ["safe-index-unchecked"]


def test_kwonly_param_taint_is_not_dropped(tmp_path):
    """Review finding (this PR): taint passed as `count=parsed` into a
    keyword-only parameter must reach the callee."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def _alloc(data, *, count):\n"
        "    return bytes(count)\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    n = r.uint(1)\n"
        "    return _alloc(data, count=n)\n"
    )))
    assert [v.rule for v in rep.violations] == ["safe-alloc-unbounded"]
    assert "_alloc" in rep.violations[0].message


def test_modulo_by_untainted_sanitizes(tmp_path):
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "TABLE = ['a', 'b', 'c']\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    n = r.uint(1)\n"
        "    return TABLE[n % len(TABLE)]\n"
    )))
    assert rep.violations == []


def test_fixed_literal_membership_sanitizes_but_accumulator_does_not(
    tmp_path,
):
    """`f in names` against a literal dispatch table sanitizes the tag
    (the abci _dec_pub_key idiom); `x in seen` against a growing
    accumulator must NOT — it is the quadratic scan itself."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    names = {1: 'ed', 2: 'secp'}\n"
        "    r = FieldReader(data)\n"
        "    f = r.uint(1)\n"
        "    if f in names:\n"
        "        return names[f]\n"
        "    raise ValueError('unknown')\n"
    )))
    assert rep.violations == []


def test_recursion_on_parsed_int_flagged_structural_descent_not(
    tmp_path,
):
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    depth = r.uint(1)\n"
        "    return decode_thing(depth)\n"  # VAL-driven: flagged
    )))
    assert [v.rule for v in rep.violations] == ["safe-alloc-unbounded"]
    rep = tmsafe.analyze(_mini_pkg(tmp_path / "b", (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    sub = r.bytes(1)\n"
        "    if sub:\n"
        "        return decode_thing(sub)\n"  # LEN-driven: bytes per
        "    return ()\n"                     # level, transport-capped
    )))
    assert rep.violations == []


def test_interprocedural_summary_returns_val(tmp_path):
    """A helper that PARSES (LEN in, VAL out) must poison its caller's
    range() — the return-summary fixpoint, not just arg joining."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def _count_of(data):\n"
        "    r = FieldReader(data)\n"
        "    return r.uint(1)\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    ver = r.uint(2)\n"
        "    if ver > 3:\n"
        "        raise ValueError('bad version')\n"
        "    out = []\n"
        "    for _ in range(_count_of(data)):\n"
        "        out.append(0)\n"
        "    return out\n"
    )))
    assert [
        (v.rule, v.line) for v in rep.violations
    ] == [("safe-alloc-unbounded", 11)]


def test_suppression_comment_block_above(tmp_path):
    """The comment-block-above form (shared family convention) covers
    the first code line below the block."""
    rep = tmsafe.analyze(_mini_pkg(tmp_path, (
        "from tendermint_tpu.encoding.proto import FieldReader\n"
        "def decode_thing(data):\n"
        "    r = FieldReader(data)\n"
        "    n = r.uint(1)\n"
        "    # tmsafe: safe-alloc-unbounded-ok — reviewed: fixture\n"
        "    # rationale spanning the block above the code line\n"
        "    return bytes(n)\n"
    )))
    assert rep.violations == []
    assert rep.stats["suppressed"] == 1


# ---------------------------------------------------------------------------
# CLI contract


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), *args],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def _load_lint_module():
    spec = importlib.util.spec_from_file_location(
        "lint_cli_safe", os.path.join(REPO, "scripts", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_cli_adv_clean_exit_zero():
    r = _run_cli("--adv", "--stats")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[adv]" in r.stdout


def test_cli_adv_seeded_violation_exit_one(monkeypatch):
    """The exit contract end to end: a safe finding beyond the (empty)
    baseline exits 1 through the real main()."""
    lint = _load_lint_module()
    seeded = [
        Violation(
            rule="safe-alloc-unbounded",
            path="types/fake.py",
            line=1,
            col=0,
            message="seeded unclamped allocation",
            source="return bytes(n)",
        )
    ]
    monkeypatch.setattr(
        lint.tmsafe, "safe_violations", lambda pkg=None, **kw: seeded
    )
    monkeypatch.setattr(
        lint.tmcheck, "build_package", lambda root=None: None
    )
    assert lint.main(["--adv"]) == 1


def test_cli_adv_baseline_update_refuses_filtered_runs():
    r = _run_cli("--adv", "--baseline-update", "--rule", "det-float")
    assert r.returncode == 2
    assert "full-package" in r.stderr


def test_cli_update_modes_refuse_adv():
    """--schema-update / --signatures-update combined with --adv would
    silently skip the adv gate while exiting 0 — the laundering class
    every section must refuse."""
    r = _run_cli("--schema-update", "--adv")
    assert r.returncode == 2 and "full-package" in r.stderr
    r = _run_cli("--signatures-update", "--adv")
    assert r.returncode == 2 and "full-package" in r.stderr


def test_cli_list_rules_includes_safe():
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rid, _ in tmsafe.RULES:
        assert rid in r.stdout
