"""Batch-verifier dispatch — the offload decision point.

Mirrors crypto/batch/batch.go:11-33 (CreateBatchVerifier /
SupportsBatchVerifier switching on key type) and extends it with the
device registry: when a TPU/accelerator backend has been registered (see
tendermint_tpu.crypto.tpu_verifier.install) and the caller hints a large
enough batch, the returned verifier runs on device. CPU remains the
default, exactly like the reference keeps pure-Go as the default.
"""

from __future__ import annotations

import threading
from typing import Callable, NamedTuple, Optional, Sequence

from ..libs import heap, trace
from .keys import BatchVerifier, PubKey

__all__ = [
    "Columns",
    "create_batch_verifier",
    "cpu_factory",
    "drain_and_cache",
    "drain_classes",
    "supports_batch_verifier",
    "register_device_factory",
    "device_factory_installed",
]

# key type -> CPU batch verifier factory
_CPU_FACTORIES: dict[str, Callable[[], BatchVerifier]] = {}
# key type -> device batch verifier factory (size_hint -> verifier or None)
_DEVICE_FACTORIES: dict[
    str, Callable[[int], Optional[BatchVerifier]]
] = {}


def register_cpu_factory(
    key_type: str, factory: Callable[[], BatchVerifier]
) -> None:
    # tmlint: disable=lock-global-mutation — single GIL-atomic dict
    # write from import-time defaults / main-thread embedder setup
    _CPU_FACTORIES[key_type] = factory


def register_device_factory(
    key_type: str, factory: Callable[[int], Optional[BatchVerifier]]
) -> None:
    # tmlint: disable=lock-global-mutation — single GIL-atomic dict
    # write from install(), a main-thread seam (nodes install at
    # construction, never from worker threads)
    _DEVICE_FACTORIES[key_type] = factory


def unregister_device_factory(key_type: str) -> None:
    """Remove a device factory (tpu_verifier.uninstall's half)."""
    # tmlint: disable=lock-global-mutation — single GIL-atomic pop
    # from uninstall(), a main-thread test/embedder seam
    _DEVICE_FACTORIES.pop(key_type, None)


def device_factory_installed(key_type: str) -> bool:
    return key_type in _DEVICE_FACTORIES


def cpu_factory(key_type: str) -> Optional[Callable[[], BatchVerifier]]:
    """The registered CPU factory for a key type, or None. This is the
    mandatory software fallback of the device-fault containment layer:
    crypto/tpu_verifier.py re-verifies a faulted device batch through
    it with the identical (all_ok, bitmap) contract."""
    return _CPU_FACTORIES.get(key_type)


# How many independent commits' signatures callers should merge into
# one batch verifier when they have several available (the light
# client's sequential window, statesync backfill). 1 = verify each
# commit separately. The device install raises it when an accelerator
# backend is live: merged batches amortize dispatch and fill buckets,
# but on a CPU-backed kernel the padding waste inverts the win.
# The value may be provided lazily (set_group_affinity_fn): deciding
# it can require jax backend initialization, which must not happen at
# install() time — a device that hangs there would hang node startup.
_GROUP_AFFINITY: Optional[int] = 1
_GROUP_AFFINITY_FN: Optional[Callable[[], int]] = None
_GROUP_AFFINITY_EXPLICIT = False
# guards the affinity triple: group_affinity()'s lazy init is a
# check-then-act on module state, and verify paths on probe threads
# race the first consensus caller (tmlint: lock-global-mutation)
_affinity_lock = threading.Lock()


def set_group_affinity(n: int) -> None:
    """Operator override — wins over any install-provided default
    (set_group_affinity_fn will not replace it)."""
    global _GROUP_AFFINITY, _GROUP_AFFINITY_FN, _GROUP_AFFINITY_EXPLICIT
    with _affinity_lock:
        _GROUP_AFFINITY = max(1, int(n))
        _GROUP_AFFINITY_FN = None
        _GROUP_AFFINITY_EXPLICIT = True


def set_group_affinity_fn(fn: Callable[[], int]) -> None:
    """Defer the affinity decision until the first caller needs it.
    A no-op if an operator already pinned a value explicitly."""
    global _GROUP_AFFINITY, _GROUP_AFFINITY_FN
    with _affinity_lock:
        if _GROUP_AFFINITY_EXPLICIT:
            return
        _GROUP_AFFINITY = None
        _GROUP_AFFINITY_FN = fn


def group_affinity() -> int:
    global _GROUP_AFFINITY
    while True:
        # consistent (value, fn) snapshot: all writers hold the lock
        with _affinity_lock:
            value = _GROUP_AFFINITY
            fn = _GROUP_AFFINITY_FN
        if value is not None:
            return value
        # resolve the deferred fn OUTSIDE the lock: it may initialize
        # the jax backend (slow, possibly hung) and must never park
        # every verify path behind it
        computed = max(1, int(fn())) if fn is not None else 1
        with _affinity_lock:
            if _GROUP_AFFINITY is not None:
                return _GROUP_AFFINITY
            if _GROUP_AFFINITY_FN is fn:
                _GROUP_AFFINITY = computed
                return computed
            # the fn changed while we computed (install landed mid-
            # flight) — loop and resolve the new one


def group_affinity_state() -> tuple:
    """Snapshot for restore_group_affinity — the save/restore idiom
    for tests and embedders. Restoring via set_group_affinity(old)
    would pin the explicit-override flag forever and silently disable
    any later install()'s affinity fn."""
    return (_GROUP_AFFINITY, _GROUP_AFFINITY_FN, _GROUP_AFFINITY_EXPLICIT)


def restore_group_affinity(state: tuple) -> None:
    global _GROUP_AFFINITY, _GROUP_AFFINITY_FN, _GROUP_AFFINITY_EXPLICIT
    with _affinity_lock:
        _GROUP_AFFINITY, _GROUP_AFFINITY_FN, _GROUP_AFFINITY_EXPLICIT = state


def supports_batch_verifier(pk: Optional[PubKey]) -> bool:
    return pk is not None and pk.type() in _CPU_FACTORIES


def create_batch_verifier(
    pk: PubKey, size_hint: int = 0
) -> BatchVerifier:
    """Return the best available batch verifier for this key type.

    size_hint is the expected number of add() calls (a Commit's signature
    count); device backends use it to pick a padded bucket shape and may
    decline small batches (returning None → CPU fallback).
    """
    key_type = pk.type()
    dev = _DEVICE_FACTORIES.get(key_type)
    if dev is not None:
        verifier = dev(size_hint)
        if verifier is not None:
            return verifier
    cpu = _CPU_FACTORIES.get(key_type)
    if cpu is None:
        raise ValueError(f"key type {key_type!r} does not support batching")
    return cpu()


def drain_and_cache(verifier: BatchVerifier, cache_keys) -> tuple:
    """Drain a batch verifier, populating the verified-signature cache
    (crypto.sigcache) for every triple whose bitmap bit is True — the
    drain half of the cross-stage cache: whatever a batch proves here,
    no later stage re-proves. cache_keys aligns with add() order; None
    entries (cache disabled at assembly time) are skipped. Returns
    verify()'s (all_ok, bitmap) unchanged.

    A batch the device faulted under (verifier.faulted — see
    crypto/tpu_verifier.py) never populates the cache, even though its
    CPU re-verify answered correctly: nothing learned while a device
    was misbehaving is allowed to outlive the batch."""
    from . import sigcache

    ok, bits = verifier.verify()
    if getattr(verifier, "faulted", False):
        return ok, bits
    with trace.span("sigcache_populate") as span:
        if ok:
            proven = [key for key in cache_keys if key is not None]
        else:
            proven = [
                key
                for key, bit in zip(cache_keys, bits)
                if bit and key is not None
            ]
        sigcache.add_keys_bulk(proven)
        span.set(keys=len(proven))
    return ok, bits


class Columns(NamedTuple):
    """One key class's cache misses awaiting batch verification, as
    parallel columns: row i is the triple (pub_keys[i], messages[i],
    signatures[i]) with key_bytes[i] == pub_keys[i].bytes(), its place
    in the caller's own numbering (a commit index, a place in a merged
    triple list) and its verified-signature cache key, None where the
    cache was off at assembly. Rows are in ascending `positions`. A
    producer with the misses in hand builds the columns whole (slices
    of what its plan and its cache probe already hold); one that walks
    vote by vote starts from new() and append()s."""

    pub_keys: Sequence
    key_bytes: Sequence
    messages: Sequence
    signatures: Sequence
    positions: Sequence
    cache_keys: Sequence

    @classmethod
    def new(cls) -> "Columns":
        return cls([], [], [], [], [], [])

    def append(
        self, pub_key, key_bytes, message, signature, position, cache_key
    ) -> None:
        self.pub_keys.append(pub_key)
        self.key_bytes.append(key_bytes)
        self.messages.append(message)
        self.signatures.append(signature)
        self.positions.append(position)
        self.cache_keys.append(cache_key)


def drain_classes(pending: dict) -> dict:
    """Drain the per-key-class miss batches of one verification in two
    phases, so that every class's device work is in flight before the
    host blocks on any of it. `pending` maps a key type to its Columns.

    Phase 1, a class at a time: one add_many() hands its columns to its
    verifier (a device verifier streams full chunks from inside it) and
    launch() sends its remainder. A class whose launches cost the host
    byte rows alone goes before one that makes an operand on the host
    at the width it launches (`host_operand`: sr25519's merlin below
    its device width), so that the device starts soonest and the
    costlier host work runs under device time; among equals, in
    `pending`'s order. Phase 2, in the same order:
    drain_and_cache() each, so a class's cache is populated under the
    next one's tiles. Every class is verified whatever an earlier one
    answered, and the heap settles once, after the last gather. Returns
    key type -> (all_ok, bitmap aligned with the columns).

    One `batch_drain` span a call: `classes`, the verifiers drained,
    and `overlapped`, those whose every launch was enqueued before the
    first gather began. One `batch_add` span a class: `sigs`, its rows,
    and `bulk`, those of them that entered through a verifier's own
    add_many() (0 for one that inherits the loop over add()). If
    anything raises, what was launched is abandoned and nothing of it
    reaches the cache. With nothing pending (every triple a cache hit)
    there is no drain and no span."""
    if not pending:
        return {}
    with trace.span(
        "batch_drain", classes=len(pending)
    ) as span, heap.deferred():
        batches = [
            (
                key_type,
                cols,
                create_batch_verifier(
                    cols.pub_keys[0], size_hint=len(cols.positions)
                ),
            )
            for key_type, cols in pending.items()
        ]
        batches.sort(
            key=lambda batch: batch[2].host_operand(len(batch[1].positions))
        )
        try:
            overlapped = 0
            for key_type, cols, bv in batches:
                sigs = len(cols.positions)
                takes_columns = (
                    type(bv).add_many is not BatchVerifier.add_many
                )
                with trace.span(
                    "batch_add",
                    key=key_type,
                    sigs=sigs,
                    bulk=sigs if takes_columns else 0,
                ):
                    bv.add_many(
                        cols.pub_keys,
                        cols.messages,
                        cols.signatures,
                        cols.key_bytes,
                    )
                    if bv.launch():
                        overlapped += 1
            span.set(overlapped=overlapped)
            return {
                key_type: drain_and_cache(bv, cols.cache_keys)
                for key_type, cols, bv in batches
            }
        except BaseException:
            for _key_type, _cols, bv in batches:
                bv.abandon()
            raise


def native_cpu_affinity() -> int:
    """Merged-window size when only CPU kernels serve batches. The
    native RLC batch equation is exact-size (no bucket padding) and
    its per-signature cost keeps falling through ~8k terms (PERF.md
    batch curve: 24 us @64 -> 10.4 us @8192), so merging a light
    client's sequential window into one call wins on CPU too. Without
    the native kernel the OpenSSL-sequential fallback gains nothing
    from merging — stay at 1."""
    try:
        from .ed25519 import _native_batch_fn

        return 32 if _native_batch_fn() is not None else 1
    except Exception:  # pragma: no cover - native probing must not raise
        return 1


def _register_defaults() -> None:
    from .ed25519 import KEY_TYPE as ED, Ed25519BatchVerifier

    register_cpu_factory(ED, Ed25519BatchVerifier)
    try:
        from .sr25519 import KEY_TYPE as SR, Sr25519BatchVerifier

        register_cpu_factory(SR, Sr25519BatchVerifier)
    except ImportError:  # sr25519 backend optional
        pass
    from .secp256k1 import KEY_TYPE as SECP, Secp256k1BatchVerifier

    register_cpu_factory(SECP, Secp256k1BatchVerifier)


_register_defaults()
set_group_affinity_fn(native_cpu_affinity)
