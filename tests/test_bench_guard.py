"""The bench's banked-line emission machinery.

The round-end driver parses exactly one JSON line from bench.py; these
pin the guarantees that line survives the observed failure modes (a
device that stops answering mid-stage, an unserializable extra, a
device that hangs at backend init) without paying for a full bench run.
"""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

import bench  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_emit():
    bench._EMIT.clear()
    bench._EMIT.update({"done": False, "line": None})
    yield
    bench._EMIT.clear()
    bench._EMIT.update({"done": False, "line": None})


def _line(extra=None):
    return {
        "metric": "m",
        "value": 1.5,
        "unit": "sigs/s/cpu",
        "vs_baseline": 2.0,
        "extra": extra if extra is not None else {},
    }


def test_emit_line_prints_exactly_once(capsys):
    bench._EMIT["line"] = _line()
    bench._emit_line()
    bench._emit_line()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    assert json.loads(out[0])["value"] == 1.5


def test_emit_line_noop_without_banked_line(capsys):
    bench._emit_line()
    assert capsys.readouterr().out == ""
    assert not bench._EMIT["done"]


def test_emit_line_stall_tag(capsys):
    bench._EMIT["line"] = _line()
    bench._emit_line(stall="stage 'x' exceeded its budget")
    d = json.loads(capsys.readouterr().out)
    assert "exceeded" in d["extra"]["stall"]


def test_emit_line_minimal_fallback_on_unserializable_extra(capsys):
    bench._EMIT["line"] = _line(extra={"bad": object()})
    bench._emit_line(stall="why")
    d = json.loads(capsys.readouterr().out)
    # scalar headline fields survive; the poisoned extra is replaced
    assert d["value"] == 1.5 and d["unit"] == "sigs/s/cpu"
    assert "stall" in d["extra"]
    assert bench._EMIT["done"]


def test_emit_line_moves_cpu_alias_keys_to_side_file(
    capsys, tmp_path, monkeypatch
):
    """VERDICT weak #6 / next #7: the r5 line carried every key twice
    (plain + `_cpu` alias) and overflowed the driver's tail window
    (`parsed: null`). Aliases whose plain twin exists must leave the
    line for the side file; cpu-only primaries (no twin) stay."""
    side = tmp_path / "side.json"
    monkeypatch.setattr(bench, "_CPU_SIDE_FILE", str(side))
    bench._EMIT["line"] = _line(
        extra={
            "verify_commit_10k_p50_ms": 3.1,
            "verify_commit_10k_p50_cpu_ms": 24.2,
            "verify_commit_10k_breakdown_ms": {"host": 1},
            "verify_commit_10k_breakdown_cpu_ms": {"host": 9},
            "cpu_single_verify_sigs_per_s": 1000.0,  # primary, no twin
            "backend": "device",
        }
    )
    bench._emit_line()
    d = json.loads(capsys.readouterr().out)
    extra = d["extra"]
    assert "verify_commit_10k_p50_cpu_ms" not in extra
    assert "verify_commit_10k_breakdown_cpu_ms" not in extra
    assert extra["verify_commit_10k_p50_ms"] == 3.1
    assert extra["cpu_single_verify_sigs_per_s"] == 1000.0
    moved = json.loads(side.read_text())
    assert moved == {
        "verify_commit_10k_p50_cpu_ms": 24.2,
        "verify_commit_10k_breakdown_cpu_ms": {"host": 9},
    }
    # the live banked dict is untouched (stall-guard concurrency)
    assert "verify_commit_10k_p50_cpu_ms" in bench._EMIT["line"]["extra"]


def test_emit_line_keeps_cpu_alias_when_twin_is_placeholder(
    capsys, tmp_path, monkeypatch
):
    """Mid-device-run stall: the plain keys still hold the pre-seeded
    {'skipped': 'device stage not reached'} stubs (bench.py seeds them
    before the device stages) or an {'error': ...} from a failed
    stage — the `_cpu` alias is then the run's ONLY real measurement
    and must stay in the line, not be evicted to the side file."""
    side = tmp_path / "side.json"
    monkeypatch.setattr(bench, "_CPU_SIDE_FILE", str(side))
    bench._EMIT["line"] = _line(
        extra={
            "verify_commit_10k_p50_ms": {
                "skipped": "device stage not reached"
            },
            "verify_commit_10k_p50_cpu_ms": 24.2,
            "verify_commit_10k_warm": {"error": "DeviceTimeout(...)"},
            "verify_commit_10k_warm_cpu": {"p50_ms": 30.0},
        }
    )
    bench._emit_line(stall="stage 'device:commit_10k' exceeded its budget")
    d = json.loads(capsys.readouterr().out)
    assert d["extra"]["verify_commit_10k_p50_cpu_ms"] == 24.2
    assert d["extra"]["verify_commit_10k_warm_cpu"] == {"p50_ms": 30.0}
    assert not side.exists()


def test_emit_line_keeps_cpu_keys_without_twin(capsys, tmp_path, monkeypatch):
    """A fallback run where canonicalization did NOT happen (or a
    cpu-only stage) must not lose its only copy of a number."""
    side = tmp_path / "side.json"
    monkeypatch.setattr(bench, "_CPU_SIDE_FILE", str(side))
    bench._EMIT["line"] = _line(
        extra={"merkle_proof_batch_per_s_cpu": 42.0}
    )
    bench._emit_line()
    d = json.loads(capsys.readouterr().out)
    assert d["extra"]["merkle_proof_batch_per_s_cpu"] == 42.0
    assert not side.exists()


def test_emit_line_restores_aliases_when_side_file_unwritable(
    capsys, tmp_path, monkeypatch
):
    """Read-only checkout / full disk: if the side file can't be
    written, the evicted rows must go BACK into the line (data over
    line size) with an error marker — never silently vanish."""
    side = tmp_path / "no-such-dir" / "side.json"
    monkeypatch.setattr(bench, "_CPU_SIDE_FILE", str(side))
    bench._EMIT["line"] = _line(
        extra={
            "verify_commit_10k_p50_ms": 3.1,
            "verify_commit_10k_p50_cpu_ms": 24.2,
        }
    )
    bench._emit_line()
    d = json.loads(capsys.readouterr().out)
    assert d["extra"]["verify_commit_10k_p50_cpu_ms"] == 24.2
    assert "cpu_side_file_error" in d["extra"]


def test_probe_device_subprocess_honors_cpu_fallback_env(monkeypatch):
    monkeypatch.setenv("TM_BENCH_CPU_FALLBACK", "1")
    assert bench._probe_device_subprocess(5.0) is False


def test_stall_guard_emits_banked_line_and_exits_3():
    """End-to-end guard firing: a subprocess banks a line, arms the
    guard with a tiny budget, then blocks — the watcher must print the
    banked line with the stall tag and exit 3. (Subprocess because the
    guard exits via os._exit.)"""
    script = r"""
import sys, time
sys.path.insert(0, %r)
import bench
bench._EMIT["line"] = {"metric": "m", "value": 7, "unit": "u",
                       "vs_baseline": 1, "extra": {}}
g = bench._StallGuard(1.0)
g.tick("wedged-stage", 1.0)
time.sleep(60)
print("guard never fired")
sys.exit(0)
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=50,
        env={**os.environ, "PYTHONPATH": "", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 3, (r.returncode, r.stdout, r.stderr)
    d = json.loads(r.stdout.strip().splitlines()[-1])
    assert d["value"] == 7
    assert "wedged-stage" in d["extra"]["stall"]


def test_stall_guard_disarm_prevents_firing():
    script = r"""
import sys, time
sys.path.insert(0, %r)
import bench
bench._EMIT["line"] = {"metric": "m", "value": 7, "unit": "u",
                       "vs_baseline": 1, "extra": {}}
g = bench._StallGuard(1.0)
g.tick("s", 1.0)
g.disarm()
time.sleep(12)
bench._emit_line()
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=40,
        env={**os.environ, "PYTHONPATH": "", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    d = json.loads(r.stdout.strip())
    assert "stall" not in d["extra"]


def test_tmlive_gate_row_never_initializes_jax():
    """The tmlive_gate row lives in the banked CPU block BEFORE the
    device probe: running it must never import jax (a hung device
    hangs backend init — the whole reason the CPU block is banked
    first). Run in a clean subprocess so this file's own imports don't
    mask a violation."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row = bench.bench_tmlive_gate()
assert row["wall_s"] > 0 and "findings" in row and "suppressed" in row
assert set(row["findings"]) == {
    "live-block-under-lock", "live-block-in-main-loop",
    "live-unbounded-blocking", "live-grow-unbounded",
}
assert "jax" not in sys.modules, "tmlive_gate dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_tmsafe_gate_row_never_initializes_jax():
    """Same contract for the tmsafe_gate row: banked CPU block, pure
    stdlib AST, jax must never load."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row = bench.bench_tmsafe_gate()
assert row["wall_s"] > 0 and "findings" in row and "suppressed" in row
assert set(row["findings"]) == {
    "safe-alloc-unbounded", "safe-index-unchecked",
    "safe-unvalidated-use", "safe-quadratic-decode",
}
assert row["entries"] >= 100 and row["sinks_cataloged"] >= 10
assert "jax" not in sys.modules, "tmsafe_gate dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_tmcost_gate_row_never_initializes_jax():
    """Same contract for the ISSUE-14 tmcost_gate row: banked CPU
    block, pure stdlib AST, jax must never load — and the row reads
    the gate's own stats (findings, suppressions, budget coverage)."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row = bench.bench_tmcost_gate()
assert row["wall_s"] > 0 and "findings" in row and "suppressed" in row
assert set(row["findings"]) == {
    "cost-superlinear", "cost-recompute",
    "cost-unclamped-alloc", "cost-budget",
}
assert row["roots"] >= 50 and row["budgeted"] == row["roots"]
assert "jax" not in sys.modules, "tmcost_gate dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_tmct_gate_row_never_initializes_jax():
    """Same contract for the ISSUE-20 tmct_gate row: banked CPU
    block, pure stdlib AST over the crypto plane, jax must never
    load — and the row reads the gate's own stats (per-rule findings,
    suppressions, the machine-derived source-catalog sizes) so it can
    never diverge from `scripts/lint.py --ct`."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row = bench.bench_tmct_gate()
assert row["wall_s"] > 0 and "findings" in row and "suppressed" in row
assert set(row["findings"]) == {
    "ct-secret-branch", "ct-secret-index", "ct-secret-compare",
    "ct-vartime-pow", "ct-leak-telemetry", "ct-leak-lifetime",
}
assert sum(row["findings"].values()) == 0, "head crypto plane is red"
assert row["privkey_classes"] >= 4 and row["secret_attrs"] >= 1
assert "jax" not in sys.modules, "tmct_gate dragged jax in"
# the secp commit rows ride the same banked CPU block: the
# pure-Python backend must never drag jax in either (small n so the
# guard stays cheap; the banked BENCH_SECP.json comes from full runs)
p50, p95 = bench.bench_commit_latency(
    12, reps=2, light=False, use_device=False, key_type="secp256k1"
)
assert p50 > 0 and p95 >= p50
assert "jax" not in sys.modules, "secp commit row dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_tmmc_gate_row_never_initializes_jax():
    """Same contract for the ISSUE-19 tmmc_gate row: the model
    harness drives the REAL consensus implementation with in-memory
    stores — pure-CPU protocol execution, jax must never load.
    TM_TPU_MC_BENCH_FAST shrinks the reduction horizon so this guard
    stays cheap; the banked full-run record (and its persist) is only
    written by real bench runs."""
    import json as _json

    script = """
import json, sys
sys.path.insert(0, %r)
import bench
row = bench.bench_tmmc_gate()
assert row["gate_wall_s"] > 0 and row["gate_states"] > 0
assert row["gate_violations"] == 0
assert row["reduction_x"] >= 1.0
assert "jax" not in sys.modules, "tmmc_gate dragged jax in"
print("ROW=" + json.dumps(row))
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": "", "TM_TPU_MC_BENCH_FAST": "1"},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout
    row = _json.loads(
        r.stdout.split("ROW=", 1)[1].splitlines()[0]
    )
    # fast mode must not have clobbered the banked full-run artifact
    banked = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_MC.json",
    )
    with open(banked) as f:
        full = _json.load(f)
    assert full["horizon_depth"] > row["horizon_depth"]


def test_serving_cache_row_never_initializes_jax():
    """The ISSUE-14 serving-cache A/B row drives the REAL light_blocks
    handler against proto-backed stub stores — pure codec + cache
    work, jax must never load. Tiny shape; the full-size medians land
    in BENCH_STATELESS.json on real runs."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row = bench.bench_serving_cache_page(
    n_vals=4, page=5, reps=1, rounds=1
)
assert row["page"] == 5 and row["cache_hits"] >= 5
for key in ("warm_serve_ms", "uncached_serve_ms", "speedup_warm"):
    assert row[key] > 0, key
assert "jax" not in sys.modules, "serving-cache row dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_load_smoke_row_never_initializes_jax():
    """The ISSUE-12 load row boots a live multi-node localnet and
    drives real HTTP/websocket traffic — all of it must stay off the
    jax backend (loadgen/localnet.py pins tpu.enable=false): the row
    lives in the banked CPU block BEFORE the device probe, where a
    hung device would hang backend init. Tiny shape here; the real
    BENCH_LOAD.json run uses the defaults."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row, report = bench.bench_load_smoke(
    n_nodes=2, duration_s=1.5, rate=40, subscribers=2, warmup_s=0.0
)
assert row["nodes"] == 2 and row["wall_s"] > 0
for key in ("requests_per_s", "sustained_txs_per_s",
            "committed_txs_per_s", "errors_total", "timeouts_total",
            "subscribers_held", "routes_p99_ms", "mempool_size_max"):
    assert key in row, key
assert row["subscribers_held"] == 2
assert report["schema"] == "bench_load/v1"
assert report["scenario"]["seed"] == 2026
for op, d in report["routes"].items():
    assert d["count"] > 0 and d["p999_ms"] >= d["p50_ms"] > 0, op
assert "jax" not in sys.modules, "load smoke dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_chaos_smoke_row_never_initializes_jax():
    """The ISSUE-13 chaos row boots live localnets, partitions and
    heals them, and reads the safety/recovery verdicts — all in the
    banked CPU block BEFORE the device probe, so none of it may touch
    the jax backend (loadgen/localnet.py pins tpu.enable=false; the
    fault plane is pure stdlib). One tiny 3-node minority-partition
    scenario here; the real BENCH_CHAOS.json run uses the shipped
    catalog."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
from tendermint_tpu.loadgen import ChaosScenario
cs = ChaosScenario(
    name="minority_partition", kind="partition",
    spec={"isolate": [2]}, fault_s=1.0, baseline_s=0.5,
    recovery_slo_s=20.0,
)
row, report = bench.bench_chaos_smoke(
    n_nodes=3, seed=11, rate=25.0, scenarios=[cs]
)
assert row["scenarios"] == 1
assert report["schema"] == "bench_chaos/v1"
r = report["scenarios"][0]
assert r["safety_ok"] and r["heights_checked"] >= 1, r
assert r["recovered_within_slo"] and r["passed"], r
assert r["net_faults_applied"], "partition applied no faults"
assert "jax" not in sys.modules, "chaos smoke dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_byz_smoke_row_never_initializes_jax():
    """The ISSUE-18 byzantine row boots live localnets with the
    adversary plane armed, drives equivocation, and reads the
    safety/accountability verdicts — all in the banked CPU block
    BEFORE the device probe, so none of it may touch the jax backend
    (consensus/byzantine.py is pure stdlib; loadgen/localnet.py pins
    tpu.enable=false). One equivocation scenario here; the real
    BENCH_BYZ.json run uses the shipped catalog."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
from tendermint_tpu.loadgen import ByzScenario
sc = ByzScenario(
    name="equivocate_prevote",
    spec="equivocate:h=4..5:step=prevote:seed={seed}",
    h_lo=4, h_hi=5, evidence_slo_s=20.0, baseline_s=0.5,
)
row, report = bench.bench_byz_smoke(
    n_nodes=4, seed=11, rate=25.0, scenarios=[sc]
)
assert row["scenarios"] == 1
assert report["schema"] == "bench_byz/v1"
r = report["scenarios"][0]
assert r["safety_ok"] and r["heights_checked"] >= 1, r
assert r["fired"] >= 1 and r["accountable"], r
assert r["evidence_committed"] >= 1 and r["passed"], r
assert row["evidence_committed_total"] >= 1
assert report["summary"]["tte_evidence_commit_s"], report["summary"]
from tendermint_tpu.consensus import byzantine
assert not byzantine.armed(), "the arc left the plane armed"
assert "jax" not in sys.modules, "byz smoke dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_profiler_rows_never_initialize_jax():
    """The ISSUE-16 rows (profiler_overhead, fanout_publish) live in
    the banked CPU block BEFORE the device probe: the sampler is pure
    threading/sys stdlib and the fan-out row is pure pubsub — jax must
    never load. Tiny shapes; the real numbers land in the banked line
    on full runs."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row = bench.bench_profiler_overhead(reps=20_000, window_s=0.1)
for key in ("disabled_label_ns", "armed_label_ns",
            "sampling_overhead_pct_97hz", "samples_in_window",
            "flood_stacks", "flood_collapsed_samples"):
    assert key in row, key
assert row["bounded"], row
from tendermint_tpu.libs import profiler
assert not profiler.is_enabled() and not profiler.labels_armed()
assert profiler.stats()["samples_total"] == 0  # row cleans up
row = bench.bench_fanout_publish(subs=32, publishes=200)
assert row["subs"] == 32 and row["deliveries_per_publish"] == 32
assert row["same_query_us"] > 0 and row["distinct_query_us"] > 0
assert "jax" not in sys.modules, "profiler rows dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout


def test_stateless_bulk_rows_never_initialize_jax():
    """The ISSUE-11 rows (merkle_multiproof_10k,
    light_sync_bulk_150vals) live in the banked CPU block BEFORE the
    device probe: pure hashlib/numpy + the CPU light client, jax must
    never load. Tiny shapes — the full-size A/B medians land in
    BENCH_STATELESS.json on real runs."""
    script = """
import sys
sys.path.insert(0, %r)
import bench
row = bench.bench_merkle_multiproof(n=200, k=16, reps=1, rounds=1)
assert row["leaves"] == 200 and row["k"] == 16
for key in ("per_proof_build_ms", "vector_build_ms", "vector_serve_ms",
            "speedup_cold", "speedup_serving", "verify_speedup"):
    assert key in row, key
row = bench.bench_light_sync_bulk(
    n_vals=4, n_headers=6, reps=1, rounds=1
)
assert row["headers"] == 6 and row["commit_memo_hits"] >= 1
for key in ("warm_client_headers_per_s", "warm_bulk_headers_per_s",
            "speedup_warm", "cold_bulk_headers_per_s"):
    assert row[key] > 0, key
assert "jax" not in sys.modules, "stateless bulk rows dragged jax in"
print("OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": ""},
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "OK" in r.stdout
