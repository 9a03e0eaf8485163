"""Light client — verify headers without executing the chain.

reference: light/client.go (1175 LoC): TrustOptions, initialization
from an operator trust root, sequential + skipping (bisection)
verification, backwards verification, witness cross-checking via the
detector, primary replacement, store pruning.

Every hop bottoms out in batched commit verification, so a long header
sync streams thousands of signature batches through the device seam
(BASELINE config 4: 10k headers @ 150 validators).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional

from ..libs import trace
from ..libs.log import get_logger
from ..types.evidence import LightClientAttackEvidence
from ..types.light import LightBlock
from ..types.validation import Fraction
from .errors import (
    DivergenceError,
    InvalidHeaderError,
    LightClientError,
    NewValSetCantBeTrustedError,
    NoWitnessesError,
)
from .provider import Provider
from .store import LightStore
from .verifier import (
    DEFAULT_TRUST_LEVEL,
    MAX_CLOCK_DRIFT_NS,
    header_expired,
    verify,
    verify_adjacent_batch,
    verify_backwards,
)

__all__ = ["Client", "TrustOptions"]

_DEFAULT_PRUNING_SIZE = 1000  # reference: client.go defaultPruningSize

# Cap on hops per merged device batch in sequential sync. 32 hops x
# 150 validators ~ 4.8k signatures — around half a device bucket, big
# enough to amortize dispatch, small enough that one window's fetch
# doesn't stall verification. The effective window is
# min(this, crypto.batch.group_affinity()): affinity is 1 unless an
# accelerator-backed verifier is installed, so CPU-only deployments
# keep the reference's one-hop loop shape.
SEQUENTIAL_BATCH_HOPS = 32


@dataclass
class TrustOptions:
    """Operator-supplied trust root (reference: light/client.go:59-98).
    `period_ns` should be well below the chain's unbonding period."""

    period_ns: int
    height: int
    hash: bytes

    def validate(self) -> None:
        if self.period_ns <= 0:
            raise ValueError("trusting period must be positive")
        if self.height <= 0:
            raise ValueError("trust height must be positive")
        if len(self.hash) != 32:
            raise ValueError("trust hash must be 32 bytes")


class Client:
    """reference: light/client.go Client."""

    def __init__(
        self,
        chain_id: str,
        trust_options: TrustOptions,
        primary: Provider,
        witnesses: List[Provider],
        store: LightStore,
        sequential: bool = False,
        trust_level: Fraction = DEFAULT_TRUST_LEVEL,
        max_clock_drift_ns: int = MAX_CLOCK_DRIFT_NS,
        pruning_size: int = _DEFAULT_PRUNING_SIZE,
    ) -> None:
        trust_options.validate()
        self.chain_id = chain_id
        self.trust_options = trust_options
        self.primary = primary
        self.witnesses = list(witnesses)
        self.store = store
        self.sequential = sequential
        self.trust_level = trust_level
        self.max_clock_drift_ns = max_clock_drift_ns
        self.pruning_size = pruning_size
        self.logger = get_logger("light")
        self._initialized = False

    # ------------------------------------------------------------------
    # setup

    async def initialize(self, now_ns: Optional[int] = None) -> None:
        """Fetch + pin the trust-root light block
        (reference: client.go initializeWithTrustOptions :268-330)."""
        if self._initialized:
            return
        now_ns = now_ns if now_ns is not None else time.time_ns()
        # resume from an existing trusted store when compatible
        existing = self.store.light_block(self.trust_options.height)
        if existing is not None:
            if existing.signed_header.hash() != self.trust_options.hash:
                raise LightClientError(
                    "stored light block at trust height does not match "
                    "the configured trust hash"
                )
            self._initialized = True
            return
        lb = await self._from_primary(self.trust_options.height)
        lb.validate_basic(self.chain_id)
        if lb.signed_header.hash() != self.trust_options.hash:
            raise LightClientError(
                f"trusted header hash mismatch at height "
                f"{self.trust_options.height}: got "
                f"{lb.signed_header.hash().hex()[:16]}, want "
                f"{self.trust_options.hash.hex()[:16]}"
            )
        if header_expired(
            lb.signed_header, self.trust_options.period_ns, now_ns
        ):
            raise LightClientError("trust-root header is already expired")
        self.store.save_light_block(lb)
        self._initialized = True

    # ------------------------------------------------------------------
    # public verification API

    async def verify_light_block_at_height(
        self, height: int, now_ns: Optional[int] = None
    ) -> LightBlock:
        """reference: client.go VerifyLightBlockAtHeight :451-486."""
        await self.initialize(now_ns)
        now_ns = now_ns if now_ns is not None else time.time_ns()
        stored = self.store.light_block(height) if height > 0 else None
        if stored is not None:
            return stored
        latest = self.store.latest_light_block()
        if height == 0 or (latest is not None and height > latest.height):
            return await self._verify_forwards(height, now_ns)
        first = self.store.first_light_block()
        if first is not None and height < first.height:
            return await self._verify_backwards_to(height)
        # between stored blocks: verify forwards from the closest lower
        return await self._verify_forwards(height, now_ns)

    async def update(self, now_ns: Optional[int] = None) -> Optional[LightBlock]:
        """Verify the primary's latest header
        (reference: client.go Update :413-446)."""
        await self.initialize(now_ns)
        now_ns = now_ns if now_ns is not None else time.time_ns()
        latest_primary = await self._from_primary(0)
        latest_trusted = self.store.latest_light_block()
        if (
            latest_trusted is not None
            and latest_primary.height <= latest_trusted.height
        ):
            return None
        return await self._verify_forwards(
            latest_primary.height, now_ns, target=latest_primary
        )

    def trusted_light_block(self, height: int) -> Optional[LightBlock]:
        return self.store.light_block(height)

    # ------------------------------------------------------------------
    # forwards (sequential or skipping)

    async def _verify_forwards(
        self,
        height: int,
        now_ns: int,
        target: Optional[LightBlock] = None,
    ) -> LightBlock:
        trusted = self._closest_trusted_below(height)
        if trusted is None:
            raise LightClientError("no trusted state to verify from")
        if header_expired(
            trusted.signed_header, self.trust_options.period_ns, now_ns
        ):
            raise LightClientError(
                "closest trusted header is outside the trusting period"
            )
        with trace.span(
            "light_sync",
            from_height=trusted.height,
            to_height=height,
            mode="sequential" if self.sequential else "skipping",
        ):
            if target is None:
                target = await self._from_primary(height)
                target.validate_basic(self.chain_id)
            if self.sequential:
                verified = await self._verify_sequential(
                    trusted, target, now_ns
                )
            else:
                verified = await self._verify_skipping(
                    trusted, target, now_ns
                )
            with trace.span("light_divergence", witnesses=len(self.witnesses)):
                await self._detect_divergence(verified, now_ns)
            with trace.span("light_store_save", blocks=1):
                self.store.save_light_block(verified)
                self.store.prune(self.pruning_size)
        return verified

    def _closest_trusted_below(self, height: int) -> Optional[LightBlock]:
        lb = self.store.light_block_before(height + 1)
        return lb

    async def _verify_sequential(
        self, trusted: LightBlock, target: LightBlock, now_ns: int
    ) -> LightBlock:
        """Verify every header between trusted and target
        (reference: client.go verifySequential :488-542), in windows of
        SEQUENTIAL_BATCH_HOPS hops: interim blocks of a window are
        fetched concurrently, all header-chain checks run in hop order
        on host, then every commit's signatures go to the device as ONE
        merged batch (the hop-per-device-call form pays a dispatch per
        header — at 10k headers that is 10k round-trips for work the
        chip finishes in milliseconds). A wrong signature comes back
        from the merged batch with its hop named
        (verify_adjacent_batch): the hops before it are saved and the
        hop's own error raised, with no height fetched or verified
        twice. Any other window failure (a header-chain check, which
        runs before any signature of the window is checked) falls back
        to the reference's one-hop-at-a-time loop for the exact error
        and store state."""
        from ..crypto.batch import group_affinity

        window = max(1, min(SEQUENTIAL_BATCH_HOPS, group_affinity()))
        if window == 1:
            # no accelerator-backed verifier installed: the reference's
            # one-hop loop, no window machinery, no double-fetch on a
            # verification failure
            cur = trusted
            for h in range(trusted.height + 1, target.height):
                interim = await self._from_primary(h)
                interim.validate_basic(self.chain_id)
                self._verify_hop(cur, interim, now_ns)
                self.store.save_light_block(interim)
                cur = interim
            self._verify_hop(cur, target, now_ns)
            return target
        cur = trusted
        while cur.height < target.height:
            first = cur.height + 1
            last = min(first + window - 1, target.height)
            chunk: List[LightBlock] = []
            try:
                chunk = await self._fetch_range(
                    first, min(last, target.height - 1)
                )
                if last == target.height:
                    chunk.append(target)
                with trace.span(
                    "light_window", hops=len(chunk), first=first, last=last
                ):
                    with trace.span("light_header_checks", hops=len(chunk)):
                        for b in chunk:
                            if b.height < target.height:
                                b.validate_basic(self.chain_id)
                    # all header-chain checks in hop order, then every
                    # commit through ONE sigcache-aware bulk
                    # verification (merged probe + grouped batch cold,
                    # M memo probes warm —
                    # types/validation.verify_commit_light_bulk)
                    verify_adjacent_batch(
                        self.chain_id,
                        cur.signed_header,
                        chunk,
                        self.trust_options.period_ns,
                        now_ns,
                        self.max_clock_drift_ns,
                    )
            except Exception as e:
                hop = getattr(e, "hop", None)
                if hop is not None:
                    # the merged batch named the hop: the hops before
                    # it are verified, and the error is that hop's own
                    self._save_interim(chunk[:hop], target)
                    raise
                # reference-exact fallback: refetch and verify one hop
                # at a time so the first failing height raises its own
                # error with every prior hop verified and saved. Logged
                # so a systematic batch-path defect (every window
                # falling back, doubling provider load) is visible.
                self.logger.info(
                    "sequential window fell back to per-hop verify",
                    first=first,
                    last=last,
                    err=repr(e),
                )
                with trace.span(
                    "light_fallback",
                    hops=last - first + 1,
                    reason=type(e).__name__,
                ):
                    for h in range(first, last + 1):
                        if h == target.height:
                            interim = target
                        else:
                            interim = await self._from_primary(h)
                            interim.validate_basic(self.chain_id)
                        self._verify_hop(cur, interim, now_ns)
                        if h < target.height:
                            self.store.save_light_block(interim)
                        cur = interim
                continue
            self._save_interim(chunk, target)
            cur = chunk[-1]
        return target

    def _save_interim(
        self, blocks: List[LightBlock], target: LightBlock
    ) -> None:
        """Save a window's verified blocks; the target is saved by
        _verify_forwards, after the witnesses have been asked."""
        interim = [b for b in blocks if b.height < target.height]
        with trace.span("light_store_save", blocks=len(interim)):
            for b in interim:
                self.store.save_light_block(b)

    async def _fetch_range(self, first: int, last: int) -> List[LightBlock]:
        """Fetch heights [first, last] ascending: ONE bulk
        `light_blocks` round-trip from the primary when it serves the
        range (Provider.light_blocks — the rpc bulk route for HTTP
        providers), else the per-height failover fetch with witness
        promotion. A bulk reply with wrong/missing heights is treated
        like a failed fetch, never trusted."""
        import asyncio

        if last < first:
            return []
        try:
            with trace.span("light_fetch", first=first, last=last, bulk=True):
                got = list(await self.primary.light_blocks(first, last))
            if [b.height for b in got] == list(range(first, last + 1)):
                return got
            self.logger.info(
                "bulk light_blocks returned wrong heights; refetching",
                primary=self.primary.id(), first=first, last=last,
            )
        except Exception as e:
            self.logger.info(
                "bulk light_blocks fetch failed; per-height fallback",
                primary=self.primary.id(), first=first, last=last,
                err=repr(e),
            )
        # return_exceptions so one failed fetch does not leave the
        # window's other in-flight fetches orphaned (gather would
        # otherwise raise immediately and abandon them)
        fetched = await asyncio.gather(
            *(self._from_primary(h) for h in range(first, last + 1)),
            return_exceptions=True,
        )
        for f in fetched:
            if isinstance(f, BaseException):
                raise f
        return list(fetched)

    async def _verify_skipping(
        self, trusted: LightBlock, target: LightBlock, now_ns: int
    ) -> LightBlock:
        """Bisection (reference: client.go verifySkipping :544-618):
        try the direct non-adjacent hop; when <1/3 of the trusted set
        signed the target, fetch the midpoint and recurse."""
        cache: List[LightBlock] = [target]
        cur = trusted
        while True:
            candidate = cache[-1]
            try:
                self._verify_hop(cur, candidate, now_ns)
            except NewValSetCantBeTrustedError:
                pivot = (cur.height + candidate.height) // 2
                if pivot in (cur.height, candidate.height):
                    raise InvalidHeaderError(
                        "bisection exhausted without trustable hop"
                    )
                pivot_block = await self._from_primary(pivot)
                pivot_block.validate_basic(self.chain_id)
                cache.append(pivot_block)
                continue
            # hop verified
            self.store.save_light_block(candidate)
            cur = candidate
            cache.pop()
            if not cache:
                return cur

    def _verify_hop(
        self, trusted: LightBlock, untrusted: LightBlock, now_ns: int
    ) -> None:
        verify(
            self.chain_id,
            trusted.signed_header,
            trusted.validator_set,
            untrusted.signed_header,
            untrusted.validator_set,
            self.trust_options.period_ns,
            now_ns,
            self.max_clock_drift_ns,
            self.trust_level,
        )

    # ------------------------------------------------------------------
    # backwards

    async def _verify_backwards_to(self, height: int) -> LightBlock:
        """Hash-chain back from the first trusted block
        (reference: client.go backwards :860-900)."""
        cur = self.store.first_light_block()
        assert cur is not None
        for h in range(cur.height - 1, height - 1, -1):
            interim = await self._from_primary(h)
            interim.validate_basic(self.chain_id)
            verify_backwards(
                self.chain_id, interim.signed_header, cur.signed_header
            )
            self.store.save_light_block(interim)
            cur = interim
        return cur

    # ------------------------------------------------------------------
    # detector (reference: light/detector.go)

    async def _detect_divergence(
        self, verified: LightBlock, now_ns: int
    ) -> None:
        """Cross-check the newly verified header against all witnesses.
        A witness that serves a DIFFERENT verifiable header at the same
        height is evidence of a light-client attack; a witness that
        serves garbage is dropped (reference: detector.go
        detectDivergence :28-100)."""
        if not self.witnesses:
            return
        remaining: List[Provider] = []
        evidence: List[LightClientAttackEvidence] = []
        for witness in self.witnesses:
            try:
                w_lb = await witness.light_block(verified.height)
            except Exception:
                # unresponsive witness: keep (transient) — reference
                # drops after repeated failures; we keep it simple
                remaining.append(witness)
                continue
            if (
                w_lb.signed_header.hash()
                == verified.signed_header.hash()
            ):
                remaining.append(witness)
                continue
            # conflicting header: is it *verifiable* from a trusted
            # block STRICTLY below the verified height? (the verified
            # block itself is already stored and must not anchor its
            # own cross-check)
            common = self.store.light_block_before(verified.height)
            try:
                w_lb.validate_basic(self.chain_id)
                self._verify_conflicting(common, w_lb, now_ns)
            except (LightClientError, ValueError):
                self.logger.info(
                    "witness sent invalid conflicting header; removing",
                    witness=witness.id(),
                )
                continue  # drop witness
            ev = LightClientAttackEvidence(
                conflicting_block=w_lb,
                common_height=common.height if common else 0,
                timestamp_ns=w_lb.signed_header.header.time_ns,
            )
            evidence.append(ev)
            remaining.append(witness)
        self.witnesses = remaining
        if not self.witnesses:
            raise NoWitnessesError(
                "all witnesses removed during divergence detection"
            )
        if evidence:
            for provider in [self.primary] + self.witnesses:
                for ev in evidence:
                    try:
                        await provider.report_evidence(ev)
                    except Exception:
                        pass
            raise DivergenceError(
                f"conflicting verifiable header at height "
                f"{verified.height}: possible light-client attack",
                evidence=evidence,
            )

    def _verify_conflicting(
        self, trusted: Optional[LightBlock], w_lb: LightBlock, now_ns: int
    ) -> None:
        if trusted is None:
            raise InvalidHeaderError("no trusted root for cross-check")
        if trusted.height == w_lb.height:
            if trusted.signed_header.hash() != w_lb.signed_header.hash():
                raise InvalidHeaderError("conflicts with trusted root")
            return
        self._verify_hop(trusted, w_lb, now_ns)

    # ------------------------------------------------------------------
    # providers

    async def _from_primary(self, height: int) -> LightBlock:
        """Fetch from the primary; on failure try witnesses and promote
        the first responsive one, demoting the old primary to the back
        of the witness list. The provider set is never shrunk by fetch
        failures — a height nobody can serve yet (e.g. the chain tip's
        commit) must not destroy the client (reference:
        client.go lightBlockFromPrimary + replacePrimaryProvider)."""
        last_err: Optional[Exception] = None
        for provider in [self.primary] + list(self.witnesses):
            try:
                with trace.span(
                    "light_fetch", first=height, last=height, bulk=False
                ):
                    lb = await provider.light_block(height)
            except Exception as e:
                last_err = e
                continue
            if height != 0 and lb.height != height:
                # lying/confused provider: treat as a failed fetch
                last_err = InvalidHeaderError(
                    f"provider {provider.id()} returned height "
                    f"{lb.height}, requested {height}"
                )
                continue
            if provider is not self.primary:
                self.logger.info(
                    "promoting witness to primary",
                    old=self.primary.id(), new=provider.id(),
                )
                self.witnesses = [
                    w for w in self.witnesses if w is not provider
                ] + [self.primary]
                self.primary = provider
            return lb
        raise NoWitnessesError(
            f"no provider could serve height {height}: {last_err}"
        )
