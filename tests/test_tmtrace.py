"""tmtrace: the whole-program device-dispatch proof gate.

Four jobs: (1) run tmtrace (static passes + fast-tier compile gate)
over the whole package on every tier-1 invocation, failing on
anything beyond the (empty) trace baseline; (2) pin the golden
jit-signature contract — every jit root in ops//parallel/ appears,
drift in any direction turns the gate red; (3) unit-test each
seeded-violation class against the mini-packages in
tests/data/trace/ (dynamic shape, tracer leak, mesh-axis mismatch,
use-after-donate, indivisible bucket, trace failure, unknown root);
(4) the CLI exit contract incl. the --signatures-update refusal
matrix, plus the fixture corpus for the two rules migrated out of
tmlint (dev-host-sync / dev-shape-leak).
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from tendermint_tpu.analysis import tmtrace
from tendermint_tpu.analysis.tmcheck.callgraph import build_package
from tendermint_tpu.analysis.tmlint import (
    load_baseline,
    new_violations,
    save_baseline,
)
from tendermint_tpu.analysis.tmtrace import (
    jitroots,
    shapeflow,
    shapemodel,
    shardcheck,
    tracegate,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "data", "trace")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXPECTED_ROOT_IDS = {
    "ops/ed25519_kernel.py:_verify_tile",
    "ops/ed25519_kernel.py:sha512_fixed",
    "ops/merkle_kernel.py:S.inner_hash_batch",
    "ops/merkle_kernel.py:_verify_program",
    "ops/sr25519_kernel.py:_verify_tile_sr",
    "ops/sr25519_kernel.py:merlin_challenge",
    "ops/verifier.py:per_chip",
}


@pytest.fixture(scope="module")
def pkg():
    return build_package()


@pytest.fixture(scope="module")
def head_report(pkg):
    return tmtrace.analyze(pkg)


def _fixture_pkg(name):
    return build_package(os.path.join(FIXTURES, name))


def _fixture_report(name, **kwargs):
    kwargs.setdefault("signatures", False)
    kwargs.setdefault("live", False)
    return tmtrace.analyze(_fixture_pkg(name), **kwargs)


# ---------------------------------------------------------------------------
# THE gate: whole package against the checked-in (empty) baseline


def test_package_clean_against_baseline(head_report):
    """tmtrace over the whole package (static + fast-tier live);
    anything beyond tmtrace/trace_baseline.json fails tier-1 — fix
    it, suppress it with a justified `# tmtrace: trace-ok`, or
    consciously re-baseline (docs/static_analysis.md)."""
    new = new_violations(
        head_report.violations,
        load_baseline(tmtrace.TRACE_BASELINE_PATH),
    )
    assert new == [], "\n".join(v.render() for v in new)


def test_trace_baseline_pinned_empty():
    """The shipped baseline is EMPTY: tmtrace launched with zero
    accepted debt and must stay that way — new findings are fixed or
    suppressed in-file with justification, never grandfathered."""
    assert load_baseline(tmtrace.TRACE_BASELINE_PATH) == {}


def test_gate_budget_under_10s():
    """The acceptance budget: the full tmtrace gate (call graph +
    static passes + fast-tier eval_shape) in under 10 s on CPU."""
    t0 = time.monotonic()
    tmtrace.analyze()
    elapsed = time.monotonic() - t0
    assert elapsed < 10.0, f"tmtrace gate took {elapsed:.1f}s"


def test_fast_tier_records_skipped_heavy(head_report):
    """The default tier skips the heavy crypto tiles BY NAME, never
    silently — the full sweep (--trace-full / bench trace_all_buckets)
    is where they trace."""
    st = head_report.stats
    assert st["tier"] == "fast"
    assert st["traced"] >= 4
    assert not st["skipped_budget"]
    assert "ops/ed25519_kernel.py:_verify_tile" in st["skipped_heavy"]
    assert (
        "ops/sr25519_kernel.py:_verify_tile_sr" in st["skipped_heavy"]
    )


def test_fast_tier_traces_the_merlin_program(head_report):
    """sr25519's merlin challenge traces in the fast tier, as SHA-512
    does, at the narrowest bucket the verifier launches it at and the
    widest."""
    cases = head_report.stats["per_case_ms"]
    m, top = shapemodel.REP_MSG_LEN, max(shapemodel._buckets())
    assert {f"merlin@M{m}x512", f"merlin@M{m}x{top}"} <= set(cases)


# ---------------------------------------------------------------------------
# golden jit-signature contract


def test_every_device_jit_root_in_golden(pkg):
    """Acceptance criterion: every jit root in ops/, parallel/, and
    crypto/tpu_verifier.py appears in jit_signatures.json — and
    discovery found exactly the known set (a new root shows up here
    first, by design)."""
    roots = jitroots.discover(pkg)
    rids = {r.rid for r in roots}
    assert rids == EXPECTED_ROOT_IDS
    golden = shapemodel.load_golden()
    assert golden is not None
    gold_rids = set(golden["roots"])
    device_rids = {
        r.rid
        for r in roots
        if r.path.startswith(("ops/", "parallel/"))
        or r.path == "crypto/tpu_verifier.py"
    }
    assert device_rids <= gold_rids
    assert gold_rids == rids


def test_golden_records_static_args_and_buckets():
    golden = shapemodel.load_golden()
    tile = golden["roots"]["ops/ed25519_kernel.py:_verify_tile"]
    assert tile["static_argnames"] == [] and tile["static_argnums"] == []
    from tendermint_tpu.config import DEFAULT_BUCKET_SIZES

    for b in DEFAULT_BUCKET_SIZES:
        assert any(f"[32,{b}]" in s for s in tile["signatures"]), b


def test_new_bucket_is_signature_drift(pkg, monkeypatch):
    """An accidental new pad bucket (= a silent recompilation on the
    hot path) must turn the gate red until --signatures-update."""
    from tendermint_tpu import config

    monkeypatch.setattr(
        config,
        "DEFAULT_BUCKET_SIZES",
        tuple(config.DEFAULT_BUCKET_SIZES) + (24576,),
    )
    roots = jitroots.discover(pkg)
    drift = shapemodel.drift_violations(
        roots, shapemodel.load_golden(), pkg
    )
    assert any(v.rule == "trace-signature-drift" for v in drift)
    assert any("24576" in v.message for v in drift)


def test_removed_root_is_signature_drift(pkg):
    roots = jitroots.discover(pkg)
    golden = shapemodel.load_golden()
    pruned = [
        r for r in roots if r.rid != "ops/ed25519_kernel.py:_verify_tile"
    ]
    drift = shapemodel.drift_violations(pruned, golden, pkg)
    assert any(
        v.rule == "trace-signature-drift"
        and "no longer exists" in v.message
        for v in drift
    )


def test_golden_extra_entry_is_signature_drift(pkg):
    roots = jitroots.discover(pkg)
    golden = json.loads(json.dumps(shapemodel.load_golden()))
    del golden["roots"]["ops/ed25519_kernel.py:_verify_tile"]
    drift = shapemodel.drift_violations(roots, golden, pkg)
    assert any(
        v.rule == "trace-signature-drift"
        and "not in the golden" in v.message
        for v in drift
    )


def test_unknown_root_fails_gate_on_fixture():
    """A brand-new jax.jit root with no shapemodel entry (the fixture
    package's) must fail as trace-unknown-root — the author declares
    the shape family before the gate passes."""
    rep = _fixture_report("leak_pkg", signatures=True)
    rules = {v.rule for v in rep.violations}
    assert "trace-unknown-root" in rules
    assert "trace-signature-drift" in rules


# ---------------------------------------------------------------------------
# seeded-violation fixtures


def test_fixture_tracer_leak_flags_bad_and_passes_clean():
    rep = _fixture_report("leak_pkg")
    leaks = [
        v for v in rep.violations if v.rule == "trace-tracer-leak"
    ]
    assert len(leaks) == 2, [v.render() for v in rep.violations]
    # the interprocedural leak (float() inside helper) and the branch
    assert any("float" in v.message for v in leaks)
    assert any("branch" in v.message for v in leaks)
    # the clean twin (jnp.where / shape reads / is-None config check)
    # produced nothing
    clean_lines = {
        v.line for v in leaks if "tile_clean" in v.message
    }
    assert not clean_lines


def test_fixture_dynamic_shape_flags_bad_and_passes_clean():
    rep = _fixture_report("dynshape_pkg")
    shapes = [v for v in rep.violations if v.rule == "dev-shape-leak"]
    assert len(shapes) == 1, [v.render() for v in rep.violations]
    assert "(32, n)" in shapes[0].message
    assert "dynamic" in shapes[0].message


def test_fixture_mesh_axis_flags_bad_and_passes_clean():
    rep = _fixture_report("mesh_pkg")
    mesh = [v for v in rep.violations if v.rule == "trace-mesh-axis"]
    assert len(mesh) == 1, [v.render() for v in rep.violations]
    assert "'model'" in mesh[0].message
    assert "'sig'" not in mesh[0].message.split("declared")[0]


def test_fixture_donated_reuse_flags_bad_and_passes_clean():
    rep = _fixture_report("donate_pkg")
    don = [
        v for v in rep.violations if v.rule == "trace-donated-reuse"
    ]
    assert len(don) == 1, [v.render() for v in rep.violations]
    assert "`buf`" in don[0].message and "_step" in don[0].message


def test_fixture_suppressions_silence_every_form():
    rep = _fixture_report("suppressed_pkg")
    assert rep.violations == [], [
        v.render() for v in rep.violations
    ]


def test_divisibility_real_classes_pass():
    """The production ops/verifier.py rounding keeps every bucket
    divisible by every virtual mesh width."""
    assert shardcheck.divisibility_violations() == []


def test_divisibility_seeded_bad_class_fails():
    spec = importlib.util.spec_from_file_location(
        "divis_bad", os.path.join(FIXTURES, "divis_bad.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    v = shardcheck.divisibility_violations(
        [mod.BadSharded], mesh_sizes=(8,)
    )
    assert v and all(
        x.rule == "trace-bucket-indivisible" for x in v
    )
    assert any("12" in x.message for x in v)


@pytest.mark.parametrize(
    "mesh, ragged", [(3, "2049"), (1, "20000"), (4, "20000")]
)
def test_divisibility_seeded_ragged_share_fails(mesh, ragged):
    """A verifier that rounds to the mesh alone (the rule before the
    fused walk) hands a chip of three 683 lanes, and any placement an
    oversized batch as it comes: widths the kernel's grid cannot tile.
    The CPU suite traces the scan and would never see it."""
    from tendermint_tpu.parallel import sharding as sh

    class MeshOnly(sh.ShardedEd25519Verifier):
        def _round(self, b):
            return -(-b // self._devices) * self._devices

    v = shardcheck.divisibility_violations([MeshOnly], mesh_sizes=(mesh,))
    assert v and all(x.rule == "trace-bucket-indivisible" for x in v)
    assert any(ragged in x.message and "fused" in x.message for x in v)


def test_trace_compile_fail_seeded():
    """A root that cannot trace (Python branch on an abstract value)
    must turn the compile gate red with the trace error; a clean root
    passes."""

    def build_bad():
        import jax
        import jax.numpy as jnp

        def bad(x):
            if x.sum() > 0:  # concretization error under eval_shape
                return x
            return -x

        return bad, (jax.ShapeDtypeStruct((8,), jnp.int32),)

    def build_ok():
        import jax
        import jax.numpy as jnp

        def ok(x):
            return jnp.where(x > 0, x, -x)

        return ok, (jax.ShapeDtypeStruct((8,), jnp.int32),)

    cases = [
        shapemodel.TraceCase("ops/fake.py:bad", "bad@8", "fast", build_bad),
        shapemodel.TraceCase("ops/fake.py:ok", "ok@8", "fast", build_ok),
    ]
    violations, stats = tracegate.run_cases(cases)
    assert len(violations) == 1
    assert violations[0].rule == "trace-compile-fail"
    assert "bad@8" in violations[0].message
    assert stats["traced"] == 2


def test_trace_budget_stops_sweep_late_and_records():
    def build_ok():
        import jax
        import jax.numpy as jnp

        return (lambda x: x + 1), (
            jax.ShapeDtypeStruct((8,), jnp.int32),
        )

    cases = [
        shapemodel.TraceCase("ops/fake.py:f", f"f@{i}", "fast", build_ok)
        for i in range(3)
    ]
    violations, stats = tracegate.run_cases(cases, budget_s=0.0)
    assert violations == []
    assert stats["traced"] == 0
    assert len(stats["skipped_budget"]) == 3


# ---------------------------------------------------------------------------
# migrated rules: the tmlint fixture corpus now runs through tmtrace


def _mini_pkg(tmp_path, relpath, src_name):
    src = open(
        os.path.join(
            os.path.dirname(__file__), "data", "lint", src_name
        )
    ).read()
    dest = tmp_path / relpath
    dest.parent.mkdir(parents=True, exist_ok=True)
    dest.write_text(src)
    return build_package(str(tmp_path))


def test_migrated_host_sync_flags_bad_fixture(tmp_path):
    pkg = _mini_pkg(tmp_path, "parallel/fixture.py", "dev_host_sync_bad.py")
    v = shapeflow.host_sync_violations(pkg)
    assert v and all(x.rule == "dev-host-sync" for x in v)
    assert len(v) == 3  # .item(), float(), np.asarray


def test_migrated_host_sync_passes_clean_fixture(tmp_path):
    pkg = _mini_pkg(
        tmp_path, "parallel/fixture.py", "dev_host_sync_clean.py"
    )
    assert shapeflow.host_sync_violations(pkg) == []


def test_migrated_shape_leak_flags_bad_fixture(tmp_path):
    pkg = _mini_pkg(tmp_path, "crypto/batch.py", "dev_shape_leak_bad.py")
    v = shapeflow.shape_leak_violations(pkg)
    assert v and all(x.rule == "dev-shape-leak" for x in v)
    assert len(v) == 2  # jnp.zeros(n), jnp.arange(len(batch))


def test_migrated_shape_leak_passes_clean_fixture(tmp_path):
    pkg = _mini_pkg(
        tmp_path, "crypto/batch.py", "dev_shape_leak_clean.py"
    )
    assert shapeflow.shape_leak_violations(pkg) == []


def test_head_host_sync_clean_with_legacy_suppressions(pkg):
    """The in-tree justified `# tmlint: disable=dev-host-sync` sites
    (tpu_verifier env parse, sharding mesh topology) keep working
    through tmtrace."""
    assert shapeflow.host_sync_violations(pkg) == []


def test_taint_propagates_through_traced_region(pkg):
    """Regression: the taint pass must actually REACH the field/curve
    layer from the jit targets — a stack-order AST walk read uses
    before defs (empty env ⇒ nothing tainted ⇒ no edges), and a
    short-circuiting `or` skipped call operands once the result was
    known (`x + helper(y)` never analyzed helper); both produced a
    vacuously-clean gate. leak_pkg's interprocedural float() pins the
    short-circuit shape; this pins the depth."""
    roots = jitroots.discover(pkg)
    rep = shapeflow._Findings()
    tp = shapeflow._TaintPass(pkg, rep)
    for root in roots:
        if root.target_key is None:
            continue
        fi = pkg.functions.get(root.target_key)
        if fi is None:
            continue
        params = shapeflow._array_params(fi, root)
        if params:
            tp.seed(root.target_key, params)
    tp.run()
    fns = {k for k, _mask in tp.done}
    assert ("ops/field25519.py", "mul") in fns
    assert ("ops/field25519.py", "sqr") in fns
    assert ("ops/edwards.py", "point_double") in fns
    assert ("ops/sha512_kernel.py", "_compress") in fns
    assert len(fns) >= 30, len(fns)


def test_traced_region_reaches_field_ops(pkg):
    roots = jitroots.discover(pkg)
    region = jitroots.traced_region(pkg, roots)
    assert ("ops/field25519.py", "mul") in region
    assert ("ops/edwards.py", "point_double") in region
    # dispatch wrappers are NOT traced-region: they are host code
    assert (
        "crypto/tpu_verifier.py",
        "_TpuBatchVerifier.verify",
    ) not in region


# ---------------------------------------------------------------------------
# suppression map + baseline round-trip


def test_suppression_map_forms():
    lines = [
        "x = 1  # tmtrace: trace-ok — why",
        "# tmtrace: trace-ok=dev-shape-leak — reason",
        "y = jnp.zeros(n)",
        "z = 2",
    ]
    m = tmtrace.suppression_map(lines)
    assert m[1] == {"all"}
    assert m[2] == {"dev-shape-leak"}
    assert m[3] == {"dev-shape-leak"}  # comment-block-above form
    assert 4 not in m


def test_golden_gated_rules_cannot_be_baselined(tmp_path):
    """trace-signature-drift / trace-unknown-root / trace-compile-fail
    can never be absorbed by --baseline-update: their accepted state
    is jit_signatures.json, and letting the counted baseline eat them
    would be the same laundering class the PR-5 '--schema
    --baseline-update refused' fix closed."""
    fpkg = _fixture_pkg("leak_pkg")
    path = str(tmp_path / "trace_baseline.json")
    counts = tmtrace.update_trace_baseline(
        fpkg, baseline_path=path, signatures=True, live=False
    )
    # only the dataflow findings were fingerprinted...
    assert counts
    new = tmtrace.new_trace_violations(
        fpkg, baseline_path=path, signatures=True, live=False
    )
    # ...so the unknown-root/drift findings are STILL new
    assert new
    assert {v.rule for v in new} <= tmtrace.NON_BASELINE_RULES


def test_baseline_roundtrip(tmp_path):
    rep = _fixture_report("leak_pkg")
    assert rep.violations
    path = str(tmp_path / "trace_baseline.json")
    counts = save_baseline(
        rep.violations, path, note=tmtrace.TRACE_BASELINE_NOTE
    )
    assert counts
    data = json.load(open(path))
    assert "tmtrace" in data["note"]
    assert new_violations(rep.violations, load_baseline(path)) == []
    # one extra identical-fingerprint finding still fails
    extra = rep.violations + [rep.violations[0]]
    assert new_violations(extra, load_baseline(path))


# ---------------------------------------------------------------------------
# CLI contract (scripts/lint.py --trace)


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), *args],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


@pytest.mark.slow
def test_cli_trace_clean_exit_zero():
    r = _run_cli("--trace", "--stats")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[trace]" in r.stdout
    assert "tmtrace live tier=fast" in r.stdout


def test_cli_trace_baseline_update_refuses_filtered_runs():
    r = _run_cli("--trace", "--baseline-update", "--rule", "det-float")
    assert r.returncode == 2
    assert "full-package" in r.stderr


def test_cli_signatures_update_refusal_matrix():
    for combo in (
        ("--signatures-update", "--taint"),
        ("--signatures-update", "--race"),
        ("--signatures-update", "--trace"),
        ("--signatures-update", "--schema-update"),
        ("--signatures-update", "--baseline-update"),
        ("--signatures-update", "--rule", "det-float"),
        ("--signatures-update", "tendermint_tpu/ops/merkle_kernel.py"),
    ):
        r = _run_cli(*combo)
        assert r.returncode == 2, combo
        assert "full-package" in r.stderr, combo


def test_cli_schema_update_refuses_trace():
    r = _run_cli("--schema-update", "--trace")
    assert r.returncode == 2
    assert "--trace" in r.stderr


def test_cli_list_rules_includes_trace():
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rid, _title in tmtrace.RULES:
        assert rid in r.stdout
