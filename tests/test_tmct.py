"""tmct: the secret-flow / constant-time gate over the crypto plane.

Five jobs, mirroring the tmsafe harness: (1) run tmct over the whole
package on every tier-1 invocation, failing on anything beyond the
(empty) ct baseline — the static form of "no secret modulates trace
shape or reaches rendered/shared state"; (2) prove the gate is not
vacuous by seeding violations into a COPY of the REAL package (strip a
reviewed `# tmct: ct-ok` rationale, strip the FilePVKey repr=False
fix, plant a module-global nonce memo in the secp256k1 sign path) and
watching the exact rule turn red; (3) unit-test the two-level
CLEAN < CARRIER < SECRET engine against tiny synthetic crypto-plane
packages — every rule red on its minimal trigger, every
declassification boundary green on its twin; (4) pin the head
suppression catalog (the reviewed accepted-by-rationale sites) and the
true-positive fixes this PR's own first run surfaced; (5) the CLI exit
contract and the update-refusal matrix for --ct.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from tendermint_tpu.analysis import tmct
from tendermint_tpu.analysis.tmcheck.callgraph import build_package
from tendermint_tpu.analysis.tmct.secretflow import (
    CARRIER,
    CLEAN,
    SECRET,
    SecretEngine,
)
from tendermint_tpu.analysis.tmct.sources import derive_catalog
from tendermint_tpu.analysis.tmlint import (
    Violation,
    load_baseline,
    new_violations,
    save_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_ROOT = os.path.join(REPO, "tendermint_tpu")


# ---------------------------------------------------------------------------
# THE gate: whole package against the checked-in (empty) baseline


@pytest.fixture(scope="module")
def head_pkg():
    return build_package()


@pytest.fixture(scope="module")
def head_report(head_pkg):
    t0 = time.thread_time()  # the analyzer's CPU, not the machine's load
    rep = tmct.analyze(head_pkg)
    rep.elapsed_s = time.thread_time() - t0
    return rep


def test_package_clean_against_baseline(head_report):
    """tmct over the whole package; anything beyond
    tmct/ct_baseline.json fails tier-1 — fix it or suppress it in-file
    with a justified `# tmct: ct-ok — why` (docs/static_analysis.md);
    re-baselining is NOT the sanctioned path for this section."""
    new = new_violations(
        head_report.violations, load_baseline(tmct.CT_BASELINE_PATH)
    )
    assert not new, "new tmct violations:\n" + "\n".join(
        v.render() for v in new
    )


def test_ct_baseline_is_checked_in_and_empty():
    """The crypto plane starts clean and stays clean: every first-run
    true positive was FIXED in-tree (NodeKey/FilePVKey repr=False,
    PrivKey.__repr__ redaction), every reviewed residual suppressed
    in-file with a written reason — nothing was grandfathered, so the
    counted baseline must stay empty forever."""
    assert os.path.exists(tmct.CT_BASELINE_PATH)
    with open(tmct.CT_BASELINE_PATH) as f:
        data = json.load(f)
    assert data["entries"] == {}


def test_full_package_run_under_budget(head_report):
    """Runtime budget: the ct pass runs on every tier-1 invocation and
    must stay under 10 s for the whole package (measured ~1.5 s for
    the three-pass polymorphic engine on ~3000 functions). Times the
    module fixture's run rather than paying a second analyze."""
    assert head_report.elapsed_s < 10.0, (
        f"tmct full-package run took {head_report.elapsed_s:.1f}s"
    )


def test_head_suppression_catalog_is_exactly_the_reviewed_sites(
    head_report,
):
    """The head catalog of accepted-by-rationale sites, by (rule,
    file): rejection sampling + published-signature zero tests in the
    secp256k1 sign path, native verify verdict compares (sr25519 /
    ed25519 batch / ristretto basemul FFI status), gen_validator's
    documented key-JSON emission, and the model checker's deterministic
    fixture keygen cache. Every other first-run finding got a real fix
    (field(repr=False) ×2, PrivKey.__repr__ redaction), not a comment.
    A new entry here means someone added a `# tmct: ct-ok — ...` —
    review the rationale, then extend this pin deliberately."""
    by_site = {(rule, path) for rule, path, _ln in head_report.suppressed}
    assert by_site == {
        ("ct-leak-lifetime", "analysis/tmmc/harness.py"),
        ("ct-leak-telemetry", "cmd/commands.py"),
        ("ct-secret-compare", "crypto/ed25519.py"),
        ("ct-secret-branch", "crypto/secp256k1.py"),
        ("ct-secret-compare", "crypto/secp256k1.py"),
        ("ct-secret-compare", "crypto/sr25519.py"),
        ("ct-secret-compare", "native/__init__.py"),
    }
    assert len(head_report.suppressed) == 11


# ---------------------------------------------------------------------------
# the machine-derived source catalog at head


def test_privkey_closure_is_the_four_key_classes(head_report):
    """The source catalog derives the PrivKey hierarchy, never a hand
    list — a fifth key class joins the gate the moment it subclasses
    PrivKey."""
    assert head_report.catalog.privkey_class_names == {
        "PrivKey",
        "PrivKeyEd25519",
        "PrivKeySr25519",
        "PrivKeySecp256k1",
    }
    assert "PubKey" in head_report.catalog.pubkey_class_names
    assert "PubKeySecp256k1" in head_report.catalog.pubkey_class_names


def test_secret_attr_carriers_include_the_key_records(head_report):
    """PrivKey-annotated fields (FilePVKey.priv_key, NodeKey.priv_key)
    are carriers package-wide, and the raw-material union covers the
    concrete classes' scalar/seed attrs."""
    assert "priv_key" in head_report.catalog.secret_attr_names
    raw = head_report.catalog.raw_attr_union()
    assert "_secret" in raw  # secp256k1 seed bytes + sr25519
    assert "_d" in raw       # secp256k1 scalar


def test_head_has_no_dataclass_repr_leaks(head_report):
    """The two first-run repr leaks (NodeKey.priv_key,
    FilePVKey.priv_key) are fixed with field(repr=False); the catalog
    scan must find zero remaining."""
    assert head_report.catalog.repr_leaks == []


def test_findings_all_zero_at_head(head_report):
    for rid, _ in tmct.RULES:
        assert head_report.stats[f"findings[{rid}]"] == 0
    assert head_report.stats["privkey_classes"] == 4
    assert head_report.stats["region"] > 2000  # whole-program, not crypto/-only


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
    return tmct.analyze(p)


def test_seeded_stripped_rationale_turns_branch_red(pkg_copy):
    """Acceptance: the rejection-sampling suppression in _rfc6979_k is
    load-bearing — deleting the reviewed rationale re-opens the real
    first-run ct-secret-branch finding on the nonce-range test."""
    mod = pkg_copy / "crypto" / "secp256k1.py"
    src = mod.read_text()
    needle = (
        "  # tmct: ct-ok — rejection sampling per RFC 6979 §3.2: the "
        "retry event has probability ~2^-128 independent of long-term "
        "key bits"
    )
    assert needle in src
    mod.write_text(src.replace(needle, ""))
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in rep.violations
        if v.rule == "ct-secret-branch" and v.path == "crypto/secp256k1.py"
    ]
    assert hits, "unsuppressed nonce-range branch not flagged"
    assert any("_ORDER" in v.source for v in hits)


def test_seeded_stripped_sign_zero_test_turns_compare_red(pkg_copy):
    """Acceptance: the r/s zero-test rationale in sign() is
    load-bearing — the engine still sees r and s as nonce-derived
    secrets at that point (publication happens at return)."""
    mod = pkg_copy / "crypto" / "secp256k1.py"
    src = mod.read_text()
    needle = (
        "  # tmct: ct-ok — r and s ARE the published signature; the "
        "zero test gates output validity (probability ~2^-256) and "
        "reveals nothing beyond the signature itself"
    )
    assert needle in src
    mod.write_text(src.replace(needle, ""))
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in rep.violations
        if v.rule == "ct-secret-compare"
        and v.path == "crypto/secp256k1.py"
    ]
    assert hits, "unsuppressed r/s zero test not flagged"


def test_seeded_dropped_repr_false_turns_telemetry_red(pkg_copy):
    """Acceptance: stripping field(repr=False) from FilePVKey.priv_key
    re-opens the real first-run finding — the generated __repr__ would
    embed the key object in every log/crash rendering."""
    mod = pkg_copy / "privval" / "file.py"
    src = mod.read_text()
    needle = "priv_key: PrivKey = field(repr=False)"
    assert needle in src
    mod.write_text(src.replace(needle, "priv_key: PrivKey = None"))
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in rep.violations
        if v.rule == "ct-leak-telemetry" and v.path == "privval/file.py"
    ]
    assert hits, "dropped repr=False not flagged"
    assert "repr" in hits[0].message


def test_seeded_nonce_memo_turns_lifetime_red(pkg_copy):
    """ISSUE satellite: the PR-9 shared-container lifetime rule catches
    a planted secret-keyed cache — memoizing the RFC 6979 nonce in a
    module global (the classic 'cache the expensive scalar' mistake
    that turns a local secret into process-lifetime state)."""
    mod = pkg_copy / "crypto" / "secp256k1.py"
    src = mod.read_text()
    needle = "def _rfc6979_k(secret: bytes, h1: bytes) -> int:"
    assert needle in src
    src = src.replace(needle, "_K_MEMO: dict = {}\n\n\n" + needle)
    needle = "            x, _y = _ct_to_affine(_ct_mul_base(k))"
    assert needle in src
    mod.write_text(
        src.replace(needle, "            _K_MEMO[h1] = k\n" + needle)
    )
    rep = _analyze_copy(pkg_copy)
    hits = [
        v for v in rep.violations
        if v.rule == "ct-leak-lifetime" and v.path == "crypto/secp256k1.py"
    ]
    assert hits, "planted module-global nonce memo not flagged"
    assert "_K_MEMO" in hits[0].message


# ---------------------------------------------------------------------------
# engine unit tests: tiny synthetic crypto-plane packages


def _mini_pkg(tmp_path, source: str, path: str = "crypto/mod.py"):
    d = tmp_path / "mini"
    full = d / path
    full.parent.mkdir(parents=True, exist_ok=True)
    full.write_text(source)
    return build_package(str(d))


_KEY_PREAMBLE = (
    "class PrivKey:\n"
    "    pass\n"
    "class PubKey:\n"
    "    pass\n"
    "class PrivKeyMini(PrivKey):\n"
    "    def __init__(self, seed):\n"
    "        self._key = seed\n"
    "        self._pub = b'public-bytes'\n"
)


def _rules(rep):
    return sorted(v.rule for v in rep.violations)


def test_branch_on_secret_flagged_public_twin_clean(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def bad(self):\n"
        "        if self._key[0]:\n"
        "            return 1\n"
        "        return 0\n"
        "    def ok(self):\n"
        "        if self._pub[0]:\n"
        "            return 1\n"
        "        return 0\n"
    )))
    assert _rules(rep) == ["ct-secret-branch"]
    assert rep.violations[0].source == "if self._key[0]:"


def test_range_bound_flagged_byte_iteration_clean(tmp_path):
    """`range(secret)` is a secret trip count; `for b in key` iterates
    the public length — only the bound is the finding."""
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def bad(self):\n"
        "        acc = 0\n"
        "        for i in range(self._key[0]):\n"
        "            acc += i\n"
        "        return acc\n"
        "    def ok(self):\n"
        "        acc = 0\n"
        "        for b in self._key:\n"
        "            acc += 1\n"
        "        return acc\n"
    )))
    assert _rules(rep) == ["ct-secret-branch"]
    assert "range" in rep.violations[0].source


def test_eq_on_secret_flagged_bytes_eq_clean(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def bad(self, other):\n"
        "        return self._key == other\n"
        "    def ok(self, other):\n"
        "        return bytes_eq(self._key, other)\n"
    )))
    assert _rules(rep) == ["ct-secret-compare"]
    assert "bytes_eq" in rep.violations[0].message


def test_two_arg_pow_flagged_three_arg_clean(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def bad(self):\n"
        "        return pow(3, self._key)\n"
        "    def ok(self):\n"
        "        return pow(3, self._key, 97)\n"
    )))
    assert _rules(rep) == ["ct-vartime-pow"]


def test_table_index_by_secret_flagged(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, (
        "TABLE = (0, 1, 2, 3)\n"
    ) + _KEY_PREAMBLE + (
        "    def bad(self):\n"
        "        return TABLE[self._key[0] & 3]\n"
        "    def ok(self, i):\n"
        "        return TABLE[i & 3]\n"
    )))
    assert _rules(rep) == ["ct-secret-index"]


def test_telemetry_sinks_fstring_exception_print(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def bad_f(self):\n"
        "        return f'key={self._key}'\n"
        "    def bad_exc(self):\n"
        "        raise ValueError(self._key)\n"
        "    def bad_print(self):\n"
        "        print(self._key)\n"
        "    def ok(self):\n"
        "        return f'key={len(self._key)} bytes'\n"
    )))
    assert _rules(rep) == ["ct-leak-telemetry"] * 3


def test_lifetime_sinks_module_global_and_container(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, (
        "_CACHE = {}\n"
        "_RING = []\n"
    ) + _KEY_PREAMBLE + (
        "    def bad_store(self):\n"
        "        _CACHE[b'k'] = self._key\n"
        "    def bad_push(self):\n"
        "        _RING.append(self._key)\n"
        "    def ok_local(self):\n"
        "        local = {}\n"
        "        local[b'k'] = self._key\n"
        "        return local\n"
    )))
    assert _rules(rep) == ["ct-leak-lifetime"] * 2


def test_carrier_object_fires_lifetime_but_not_timing(tmp_path):
    """The two-level lattice: a PrivKey *object* parked in a module
    global is a lifetime leak, but branching on it (presence checks,
    dispatch) is not a timing finding — only raw material is."""
    rep = tmct.analyze(_mini_pkg(tmp_path, (
        "_KEYS = {}\n"
    ) + _KEY_PREAMBLE + (
        "def use(pk: PrivKeyMini, name):\n"
        "    if pk is None:\n"
        "        return None\n"
        "    if name:\n"
        "        _KEYS[name] = pk\n"
        "    return pk\n"
    )))
    assert _rules(rep) == ["ct-leak-lifetime"]


def test_raw_attr_read_off_carrier_reenters_secret(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "def bad(pk: PrivKeyMini):\n"
        "    if pk._key[0]:\n"
        "        return 1\n"
        "    return 0\n"
    )))
    assert _rules(rep) == ["ct-secret-branch"]


def test_declassified_methods_are_public(tmp_path):
    """sign/pub_key/address results are published output by design —
    branching on them is not a finding (their internals still are
    analyzed, as the other tests prove)."""
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def sign(self, msg):\n"
        "        return bytes(32)\n"
        "    def pub_key(self):\n"
        "        return PubKey()\n"
        "def ok(pk: PrivKeyMini, msg):\n"
        "    sig = pk.sign(msg)\n"
        "    if sig[0]:\n"
        "        return sig\n"
        "    return pk.pub_key()\n"
    )))
    assert rep.violations == []


def test_urandom_births_secret_only_in_crypto_plane(tmp_path):
    src = (
        "import os\n"
        "def gen():\n"
        "    nonce = os.urandom(32)\n"
        "    if nonce[0] & 1:\n"
        "        return 1\n"
        "    return 0\n"
    )
    rep = tmct.analyze(_mini_pkg(tmp_path, src, "crypto/mod.py"))
    assert _rules(rep) == ["ct-secret-branch"]
    rep = tmct.analyze(_mini_pkg(tmp_path / "b", src, "rpc/mod.py"))
    assert rep.violations == []


def test_polymorphic_helper_summary_no_public_poisoning(tmp_path):
    """The caller-sensitivity regression this PR's own development
    surfaced: shared arithmetic called with secrets from the sign path
    must NOT make its return secret for public callers (precompute
    tables, verify paths) — the ret_base/param_dep summary split."""
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "def dbl(x):\n"
        "    return x + x\n"
        "class Signer(PrivKeyMini):\n"
        "    def bad(self):\n"
        "        t = dbl(self._key[0])\n"
        "        if t & 1:\n"
        "            return 1\n"
        "        return 0\n"
        "def public_precompute():\n"
        "    n = dbl(3)\n"
        "    if n > 4:\n"
        "        return 1\n"
        "    return 0\n"
    )))
    assert [(v.rule, v.source) for v in rep.violations] == [
        ("ct-secret-branch", "if t & 1:")
    ]


def test_internal_secret_birth_propagates_to_caller(tmp_path):
    """ret_base: a function that mints a secret internally (urandom in
    the crypto plane) taints every caller even with clean args."""
    rep = tmct.analyze(_mini_pkg(tmp_path, (
        "import os\n"
        "def fresh_scalar():\n"
        "    return os.urandom(32)\n"
        "def caller():\n"
        "    k = fresh_scalar()\n"
        "    if k[0]:\n"
        "        return 1\n"
        "    return 0\n"
    )))
    assert _rules(rep) == ["ct-secret-branch"]


def test_structural_reads_are_clean(tmp_path):
    """len() / type() / isinstance() / `is None` read structure, not
    content — the public-length contract."""
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def ok(self):\n"
        "        if self._key is None:\n"
        "            return 0\n"
        "        if len(self._key) != 32:\n"
        "            return 1\n"
        "        if isinstance(self._key, bytearray):\n"
        "            return 2\n"
        "        return 3\n"
    )))
    assert rep.violations == []


def test_suppression_requires_reason(tmp_path):
    """`# tmct: ct-ok — why` suppresses; a bare `# tmct: ct-ok` does
    not parse — every sanctioned site is a written, reviewable claim."""
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def ok(self):\n"
        "        if self._key[0]:  # tmct: ct-ok — fixture: reviewed reason\n"
        "            return 1\n"
        "        return 0\n"
        "    def still_bad(self):\n"
        "        if self._key[0]:  # tmct: ct-ok\n"
        "            return 1\n"
        "        return 0\n"
    )))
    assert _rules(rep) == ["ct-secret-branch"]
    assert rep.stats["suppressed"] == 1


def test_suppression_comment_block_above(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def ok(self):\n"
        "        # tmct: ct-ok — fixture: rejection sampling twin,\n"
        "        # rationale spanning the block above the code line\n"
        "        if self._key[0]:\n"
        "            return 1\n"
        "        return 0\n"
    )))
    assert rep.violations == []
    assert rep.stats["suppressed"] == 1


def test_dataclass_repr_leak_and_repr_false_twin(tmp_path):
    rep = tmct.analyze(_mini_pkg(tmp_path, (
        "from dataclasses import dataclass, field\n"
        "class PrivKey:\n"
        "    pass\n"
        "@dataclass\n"
        "class BadRec:\n"
        "    priv_key: PrivKey\n"
        "@dataclass\n"
        "class OkRec:\n"
        "    priv_key: PrivKey = field(repr=False)\n"
    )))
    assert _rules(rep) == ["ct-leak-telemetry"]
    assert "BadRec" in rep.violations[0].message


def test_witness_chain_names_the_source_function(tmp_path):
    """Findings carry an interprocedural witness so the operator can
    see how the secret reached the sink."""
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def bad(self):\n"
        "        if self._key[0]:\n"
        "            return 1\n"
        "        return 0\n"
    )))
    assert len(rep.violations) == 1
    assert "witness" in rep.violations[0].message


def test_lattice_constants():
    assert CLEAN < CARRIER < SECRET


def test_engine_seeds_init_params_secret(tmp_path):
    pkg = _mini_pkg(tmp_path, _KEY_PREAMBLE)
    cat = derive_catalog(pkg)
    assert cat.seed_params == {
        ("crypto/mod.py", "PrivKeyMini.__init__"): {"seed"}
    }
    eng = SecretEngine(pkg, cat)
    eng.run()
    st = eng.states[("crypto/mod.py", "PrivKeyMini.__init__")]
    assert st.param_taint["seed"] == SECRET


def test_baseline_round_trip(tmp_path):
    """save_baseline over synthetic findings -> zero new; a duplicated
    offending line overflows its counted fingerprint."""
    rep = tmct.analyze(_mini_pkg(tmp_path, _KEY_PREAMBLE + (
        "    def bad(self):\n"
        "        if self._key[0]:\n"
        "            return 1\n"
        "        return 0\n"
    )))
    assert rep.violations
    path = tmp_path / "ct_baseline.json"
    save_baseline(rep.violations, str(path), note=tmct.CT_BASELINE_NOTE)
    assert new_violations(rep.violations, load_baseline(str(path))) == []
    extra = rep.violations + [rep.violations[0]]
    over = new_violations(extra, load_baseline(str(path)))
    assert over and "baseline allows" in over[0].message


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
        "lint_cli_ct", os.path.join(REPO, "scripts", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_cli_ct_clean_exit_zero():
    r = _run_cli("--ct", "--stats")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[ct]" in r.stdout
    assert "tmct gate:" in r.stdout


def test_cli_ct_seeded_violation_exit_one(monkeypatch):
    """The exit contract end to end: a ct finding beyond the (empty)
    baseline exits 1 through the real main()."""
    lint = _load_lint_module()
    seeded = tmct.CtReport()
    seeded.violations = [
        Violation(
            rule="ct-secret-branch",
            path="crypto/fake.py",
            line=1,
            col=0,
            message="seeded secret-dependent branch",
            source="if key[0]:",
        )
    ]
    monkeypatch.setattr(lint.tmct, "analyze", lambda pkg=None: seeded)
    monkeypatch.setattr(
        lint.tmcheck, "build_package", lambda root=None: None
    )
    assert lint.main(["--ct"]) == 1


def test_cli_ct_baseline_update_refuses_filtered_runs():
    r = _run_cli("--ct", "--baseline-update", "--rule", "det-float")
    assert r.returncode == 2
    assert "full-package" in r.stderr


def test_cli_update_modes_refuse_ct():
    """--schema-update / --signatures-update / --cost-update combined
    with --ct would silently skip the ct gate while exiting 0 — the
    laundering class every section must refuse."""
    r = _run_cli("--schema-update", "--ct")
    assert r.returncode == 2 and "full-package" in r.stderr
    r = _run_cli("--signatures-update", "--ct")
    assert r.returncode == 2 and "full-package" in r.stderr
    r = _run_cli("--cost-update", "--ct")
    assert r.returncode == 2 and "full-package" in r.stderr


def test_cli_list_rules_includes_ct():
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rid, _ in tmct.RULES:
        assert rid in r.stdout
