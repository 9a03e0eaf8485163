"""Device-fault containment: the fault plane, the circuit breakers,
and the _TpuBatchVerifier recovery paths.

The invariants under test are the acceptance criteria of the
containment layer (docs/resilience.md):

- every injected fault mode (raise / hang / mis-shape / bit-flip) is
  contained inside BatchVerifier.verify(): callers always get the
  (all_ok, bitmap) answer a healthy CPU run would give, with the SAME
  wrong-signature index attribution;
- nothing learned from a faulted batch reaches the verified-signature
  cache;
- a tripped breaker routes new work to CPU with zero device touches,
  re-arms through a single-flight probe, and never admits traffic onto
  a possibly-hung device before its backoff (the probe-delay policy
  the old trip_sr_singles machinery implemented by hand);
- fault-path metrics count only work the device actually completed.
"""

import threading
import time

import pytest

from tendermint_tpu.crypto import breaker as B
from tendermint_tpu.crypto import faults, sigcache
from tendermint_tpu.crypto import tpu_verifier as T
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.types import InvalidCommitError, verify_commit

from .test_types import CHAIN_ID
from .test_validation import make_commit


def _triples(n, tag=b"fault", seed0=41):
    out = []
    for i in range(n):
        priv = PrivKeyEd25519.from_seed(bytes([seed0 + i]) * 32)
        msg = tag + b"-%d" % i
        out.append((priv.pub_key(), msg, priv.sign(msg)))
    return out


def _fill(v, triples):
    for pk, msg, sig in triples:
        v.add(pk, msg, sig)
    return v


# -- the fault plane ---------------------------------------------------


def test_rules_are_seed_reproducible():
    """Whether consult k fires is a pure function of (seed, k): two
    rules with the same seed fire on identical consult indexes."""

    def pattern(seed):
        fired = []
        with faults.inject("p", mode="raise", p=0.5, seed=seed) as rule:
            for i in range(50):
                try:
                    faults.fire("p")
                except faults.DeviceFault:
                    fired.append(i)
            assert rule.fired == len(fired)
        return fired

    a, b, c = pattern(7), pattern(7), pattern(8)
    assert a == b
    assert a != c  # different seed, different schedule
    assert a  # p=0.5 over 50 consults fires at least once


def test_inject_scope_and_times_budget():
    with faults.inject("p", mode="raise", times=2) as rule:
        for _ in range(2):
            with pytest.raises(faults.DeviceFault):
                faults.fire("p")
        faults.fire("p")  # budget spent: no fault
        assert rule.fired == 2
    faults.fire("p")  # scope exited: disarmed
    assert not faults.armed()


def test_key_filter_scopes_rule():
    with faults.inject("p", mode="raise", key="sr25519"):
        faults.fire("p", key="ed25519")  # filtered out
        with pytest.raises(faults.DeviceFault):
            faults.fire("p", key="sr25519")


def test_env_spec_parses_and_arms(monkeypatch):
    monkeypatch.setenv(
        "TM_TPU_FAULT", "tpu.dispatch:raise:p=0.25:seed=9;wal.fsync:io_error"
    )
    faults.load_env()
    armed = {(r.point, r.mode) for r in faults.rules()}
    assert ("tpu.dispatch", "raise") in armed
    assert ("wal.fsync", "io_error") in armed
    with pytest.raises(OSError):
        faults.fire("wal.fsync")
    monkeypatch.setenv("TM_TPU_FAULT", "")
    faults.load_env()
    assert not faults.armed()


def test_malformed_env_spec_raises_once_then_disarmed(monkeypatch):
    """A bad TM_TPU_FAULT must surface ONCE, not turn every hot-path
    armed() check into a re-parse + re-raise: the latch rises (and
    _ARMED refreshes) even when the parse fails, all-or-nothing so a
    spec that dies mid-list arms none of its rules."""
    monkeypatch.setenv(
        "TM_TPU_FAULT", "tpu.dispatch:raise;tpu.gather:bogus-mode"
    )
    monkeypatch.setattr(faults, "_ENV_LOADED", False)
    with pytest.raises(ValueError):
        faults.armed()
    # second call: latched, disarmed, no re-raise
    assert not faults.armed()
    assert all(
        not getattr(r, "_from_env", False) for r in faults.rules()
    )
    # a corrected spec re-arms via the explicit reload path
    monkeypatch.setenv("TM_TPU_FAULT", "tpu.dispatch:raise")
    faults.load_env()
    assert faults.armed()
    monkeypatch.setenv("TM_TPU_FAULT", "")
    faults.load_env()


def test_mangle_and_clip_modes():
    bits = [True, True, True, True]
    with faults.inject("g", mode="misshape"):
        assert len(faults.mangle("g", bits)) == 3
    with faults.inject("g", mode="bitflip", seed=3):
        flipped = faults.mangle("g", bits)
        assert len(flipped) == 4 and flipped != bits
    data = bytes(range(64))
    with faults.inject("w", mode="short_write", seed=5):
        prefix = faults.clip("w", data)
        assert len(prefix) < 64 and data.startswith(prefix)


# -- the circuit breaker ----------------------------------------------


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


def test_breaker_trips_and_backs_off_exponentially():
    clk = FakeClock()
    b = B.CircuitBreaker("t1", backoff_base_s=10.0, clock=clk)
    assert b.state() == B.CLOSED and b.allow()
    b.record_failure()
    assert b.state() == B.OPEN
    assert not b.allow()  # inside the backoff window: nobody admitted
    clk.now += 9.9
    assert not b.allow()  # the probe-delay policy: never pile on early
    clk.now += 0.2  # past the base backoff
    assert b.allow()  # probe-less breaker: ONE half-open ticket
    assert not b.allow()  # ...and only one
    b.record_failure()  # the ticket-holder failed -> backoff doubles
    assert b.stats()["retry_in_s"] == pytest.approx(20.0, abs=0.1)
    clk.now += 20.1
    assert b.allow()
    b.record_success()  # healed: closed, exponent reset
    assert b.state() == B.CLOSED
    b.record_failure()
    assert b.stats()["retry_in_s"] == pytest.approx(10.0, abs=0.1)


def test_breaker_backoff_is_capped():
    clk = FakeClock()
    b = B.CircuitBreaker(
        "t2", backoff_base_s=10.0, backoff_max_s=60.0, clock=clk
    )
    for _ in range(10):
        b.record_failure()
    assert b.stats()["retry_in_s"] <= 60.0


def test_breaker_probe_is_single_flight():
    """With a probe fn armed, traffic is NEVER admitted while open or
    half-open — exactly one background probe decides, and concurrent
    allow() storms cannot start a second one."""
    gate = threading.Event()
    in_flight = []
    peak = []

    def probe():
        in_flight.append(1)
        peak.append(len(in_flight))
        gate.wait(5.0)
        in_flight.pop()
        return True

    b = B.CircuitBreaker("t3", backoff_base_s=0.01, probe=probe)
    b.record_failure()
    time.sleep(0.1)  # timer fires, probe starts and parks on the gate
    assert b.state() == B.HALF_OPEN
    for _ in range(50):
        assert not b.allow()  # traffic stays off the device meanwhile
    assert b.stats()["probes"] == 1  # the storm started no new probes
    gate.set()
    deadline = time.monotonic() + 5.0
    while b.state() != B.CLOSED and time.monotonic() < deadline:
        time.sleep(0.01)
    assert b.state() == B.CLOSED  # probe success re-armed the route
    assert max(peak) == 1  # <= 1 probe in flight at all times
    assert b.allow()


def test_breaker_failed_probe_reopens_with_backoff():
    b = B.CircuitBreaker("t4", backoff_base_s=0.02, probe=lambda: False)
    b.record_failure()
    time.sleep(0.1)
    # the probe failed; the breaker is open again with a doubled window
    assert b.state() == B.OPEN
    assert b.stats()["trips"] >= 2
    assert not b.allow()
    # bounded probing: backoff doubling means a dead device sees a
    # logarithmic number of probes, not one per caller
    time.sleep(0.3)
    assert b.stats()["probes"] <= 6


def test_start_open_breaker_closes_via_probe():
    """The sr25519-single warm gate re-expressed: cold == OPEN, a
    successful probe (install's warm-up) closes it."""
    b = B.CircuitBreaker("t5", backoff_base_s=5.0, start_open=True,
                         probe=lambda: True)
    assert not b.allow()  # cold: no device routing, no blocking
    b.probe_now()
    deadline = time.monotonic() + 5.0
    while b.state() != B.CLOSED and time.monotonic() < deadline:
        time.sleep(0.01)
    assert b.state() == B.CLOSED


# -- verifier containment ---------------------------------------------


def test_dispatch_raise_contained_and_uncacheable():
    triples = _triples(5)
    with faults.inject("tpu.dispatch", mode="raise"):
        v = _fill(T.TpuEd25519BatchVerifier(), triples)
        from tendermint_tpu.crypto.batch import drain_and_cache

        keys = [
            sigcache.key_for(pk.bytes(), m, s) for pk, m, s in triples
        ]
        ok, bits = drain_and_cache(v, keys)
    assert (ok, bits) == (True, [True] * 5)
    assert v.faulted
    # the CPU re-verify was correct, but nothing a faulted batch
    # touched may enter the cache
    assert sigcache.entries() == 0
    assert B.breaker_for("ed25519").state() == B.OPEN


def test_gather_hang_surfaces_as_timeout_and_falls_back(monkeypatch):
    monkeypatch.setenv("TM_TPU_GATHER_DEADLINE_S", "0.2")
    triples = _triples(4)
    # warm the kernel program first: the XLA compile happens inside
    # dispatch() and must not be charged to the hang-containment wall
    assert _fill(T.TpuEd25519BatchVerifier(), triples).verify()[0]
    t0 = time.perf_counter()
    with faults.inject("tpu.gather", mode="hang", hang_s=5.0):
        v = _fill(T.TpuEd25519BatchVerifier(), triples)
        ok, bits = v.verify()
    wall = time.perf_counter() - t0
    assert (ok, bits) == (True, [True] * 4)
    assert v.faulted
    assert wall < 3.0  # the 5 s hang never reached the caller
    assert T.stats()["faults"] >= 1


def test_misshaped_gather_contained():
    triples = _triples(4)
    with faults.inject("tpu.gather", mode="misshape"):
        v = _fill(T.TpuEd25519BatchVerifier(), triples)
        ok, bits = v.verify()
    assert (ok, bits) == (True, [True] * 4)
    assert v.faulted


def test_bitflipped_lane_disproven_and_contained():
    """A device that silently invalidates a good lane is caught by the
    CPU disprover and treated as a faulted device, not a bad vote."""
    triples = _triples(6)
    with faults.inject("tpu.gather", mode="bitflip", seed=3):
        v = _fill(T.TpuEd25519BatchVerifier(), triples)
        ok, bits = v.verify()
    assert (ok, bits) == (True, [True] * 6)
    assert v.faulted


def test_genuinely_bad_signature_not_a_device_fault():
    """The disprover must not cry wolf: a real wrong signature keeps
    its per-index attribution and trips nothing."""
    triples = _triples(5)
    pk, msg, sig = triples[3]
    triples[3] = (pk, msg, sig[:6] + bytes([sig[6] ^ 1]) + sig[7:])
    v = _fill(T.TpuEd25519BatchVerifier(), triples)
    ok, bits = v.verify()
    assert not ok and bits == [True, True, True, False, True]
    assert not v.faulted
    assert B.breaker_for("ed25519").state() == B.CLOSED


def test_open_breaker_routes_silently_without_device_touch():
    touched = []

    class SpyBacking:
        def dispatch(self, pks, msgs, sigs):  # pragma: no cover - guard
            touched.append(len(pks))
            raise AssertionError("device touched through open breaker")

        def gather(self, handle):  # pragma: no cover - guard
            raise AssertionError("device touched through open breaker")

    B.breaker_for("ed25519").open_now()
    triples = _triples(4)
    v = _fill(T.TpuEd25519BatchVerifier(SpyBacking()), triples)
    ok, bits = v.verify()
    assert (ok, bits) == (True, [True] * 4)
    assert not touched
    assert not v.faulted  # a quiet reroute is not a fault
    # ...and the factory declines outright, so new batches are born CPU
    assert T._factory(64) is None


def test_streaming_dispatch_fault_does_not_raise_from_add(monkeypatch):
    """add() may only raise on malformed input; a fault in the async
    chunk launch is deferred to verify()'s CPU fallback."""
    monkeypatch.setattr(T, "_STREAMING", True)
    monkeypatch.setattr(T._TpuBatchVerifier, "STREAM_CHUNK", 2)
    triples = _triples(5)
    with faults.inject("tpu.dispatch", mode="raise"):
        v = T.TpuEd25519BatchVerifier()
        for pk, msg, sig in triples:
            v.add(pk, msg, sig)  # chunk launches fault silently here
            assert len(v) <= 5
        ok, bits = v.verify()
    assert (ok, bits) == (True, [True] * 5)
    assert v.faulted


def test_midloop_gather_fault_counts_only_completed_work(monkeypatch):
    """Three streamed chunks in flight; the gather of the SECOND one
    faults. tpu_verify_sigs_total must advance by exactly the one
    chunk the device completed — the old code left the counters
    claiming work the device never finished — and the verifier must
    still answer the full batch correctly from CPU."""
    monkeypatch.setattr(T, "_STREAMING", True)
    monkeypatch.setattr(T._TpuBatchVerifier, "STREAM_CHUNK", 2)

    class FlakyBacking:
        """dispatch/gather pair whose SECOND gather raises — the
        mid-flight device death shape."""

        def __init__(self):
            self.gathers = 0

        def dispatch(self, pks, msgs, sigs):
            from tendermint_tpu.crypto.ed25519 import Ed25519BatchVerifier
            from tendermint_tpu.crypto.keys import pubkey_from_type_and_bytes

            bv = Ed25519BatchVerifier()
            for pk, m, s in zip(pks, msgs, sigs):
                bv.add(pubkey_from_type_and_bytes("ed25519", pk), m, s)
            return bv.verify()[1]

        def gather(self, handle):
            self.gathers += 1
            if self.gathers == 2:
                raise T.DeviceFault("device died mid-flight")
            return handle

    triples = _triples(6)
    sigs0 = T.stats()["sigs"]
    faults0 = T.stats()["faults"]
    v = T.TpuEd25519BatchVerifier(FlakyBacking())
    for pk, msg, sig in triples:
        v.add(pk, msg, sig)  # streams three 2-sig chunks
    ok, bits = v.verify()
    assert (ok, bits) == (True, [True] * 6)
    assert v.faulted
    # only the ONE gathered chunk (2 sigs) counts as device work
    assert T.stats()["sigs"] == sigs0 + 2
    assert T.stats()["faults"] == faults0 + 1
    assert len(v) == 0 and v.verify() == (False, [])


def test_verify_commit_error_parity_across_fault_paths():
    """The acceptance criterion: the wrong-signature index and message
    are byte-identical on the device path, the pure CPU path, and the
    mid-batch-fault-then-fallback path — and no path leaks sigcache
    entries from a faulted batch."""
    from tendermint_tpu.crypto.batch import (
        register_device_factory,
        unregister_device_factory,
    )

    def run():
        vals, bid, commit = make_commit(4)
        forged = bytearray(commit.signatures[2].signature)
        forged[5] ^= 0x40
        commit.signatures[2].signature = bytes(forged)
        with pytest.raises(InvalidCommitError) as ei:
            verify_commit(CHAIN_ID, vals, bid, 1, commit)
        return str(ei.value)

    register_device_factory(
        "ed25519", lambda hint: T.TpuEd25519BatchVerifier()
    )
    try:
        device = run()
        sigcache.reset()
        with faults.inject("tpu.dispatch", mode="raise"):
            mid_fault = run()
        # a faulted batch never populates the cache — not even its
        # three good signatures
        assert sigcache.entries() == 0
        B.reset_all()
    finally:
        unregister_device_factory("ed25519")
    cpu = run()
    assert device == mid_fault == cpu
    assert "wrong signature (#2)" in cpu


def test_probe_rearms_route_after_faults_clear():
    """install()-style wiring: fault trips the breaker, the fault
    clears, the timer-scheduled probe closes it again — open ->
    half-open -> closed, with no traffic required."""
    b = B.fresh("ed25519", backoff_base_s=0.05)
    b.set_probe(
        lambda: T._device_probe("ed25519", T._ed_backing)
    )
    triples = _triples(3)
    with faults.inject("tpu.dispatch", mode="raise"):
        v = _fill(T.TpuEd25519BatchVerifier(), triples)
        assert v.verify() == (True, [True] * 3)
        assert b.state() == B.OPEN
    # fault plane disarmed: the next probe finds a healthy device
    deadline = time.monotonic() + 10.0
    while b.state() != B.CLOSED and time.monotonic() < deadline:
        time.sleep(0.01)
    assert b.state() == B.CLOSED
    # and the route serves the device again
    v = _fill(T.TpuEd25519BatchVerifier(), triples)
    assert v.verify() == (True, [True] * 3)
    assert not v.faulted


def test_half_open_ticket_expires_and_reissues():
    """A probe-less breaker whose half-open ticket holder never
    reports back (its work was rerouted, its caller died) must re-admit
    a caller after the backoff window — half-open may stall the route,
    never wedge it permanently (review finding)."""
    clk = FakeClock()
    b = B.CircuitBreaker("t6", backoff_base_s=10.0, clock=clk)
    b.record_failure()
    clk.now += 10.1
    assert b.allow()  # ticket out; holder silently vanishes
    assert not b.allow()
    clk.now += 10.1  # a full backoff with no report
    assert b.allow()  # fresh ticket
    b.record_success()
    assert b.state() == B.CLOSED


def test_factory_admission_pays_back_the_ticket():
    """The double-consult wedge (review finding): _factory's allow()
    takes the half-open ticket, and verify() must then ATTEMPT the
    device and report the outcome — not consult allow() again, reroute
    to CPU, and leave the breaker half-open forever."""
    b = B.fresh("ed25519", backoff_base_s=0.0)  # probe-less
    b.record_failure()
    assert b.state() == B.OPEN
    # backoff 0: the next factory consult transitions to HALF_OPEN and
    # admits ONE verifier
    v = T._factory(8)
    assert v is not None and b.state() == B.HALF_OPEN
    triples = _triples(3)
    for pk, msg, sig in triples:
        v.add(pk, msg, sig)
    ok, bits = v.verify()  # the admitted verifier IS the probe
    assert (ok, bits) == (True, [True] * 3)
    assert not v.faulted
    assert b.state() == B.CLOSED  # ticket paid back, route re-armed


def test_open_now_wins_over_inflight_probe():
    """Operator kill switch vs a racing probe (review finding): a probe
    launched before open_now() must not close the breaker the operator
    just ordered open, even if it succeeds against the device."""
    release = threading.Event()

    def probe():
        release.wait(5.0)
        return True  # the device looks healthy to the stale probe

    b = B.CircuitBreaker("t7", backoff_base_s=0.01, probe=probe)
    b.record_failure()
    deadline = time.monotonic() + 5.0
    while not b.probe_in_flight() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert b.probe_in_flight()
    b.open_now()  # operator override while the probe is parked
    release.set()
    time.sleep(0.2)  # give the stale probe time to (try to) publish
    assert b.state() == B.OPEN  # the override held
