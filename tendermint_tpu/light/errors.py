"""Light client errors (reference: light/errors.go)."""

from __future__ import annotations

__all__ = [
    "LightClientError",
    "OldHeaderExpiredError",
    "NewValSetCantBeTrustedError",
    "InvalidHeaderError",
    "VerificationError",
    "LightBlockNotFoundError",
    "NoWitnessesError",
    "DivergenceError",
]


class LightClientError(Exception):
    pass


class OldHeaderExpiredError(LightClientError):
    """The trusted header is outside the trusting period
    (reference: light/errors.go ErrOldHeaderExpired)."""

    def __init__(self, at_ns: int, now_ns: int) -> None:
        super().__init__(
            f"old header has expired at {at_ns} (now: {now_ns})"
        )
        self.at_ns = at_ns
        self.now_ns = now_ns


class NewValSetCantBeTrustedError(LightClientError):
    """< trust-level of the trusted set signed the new header — the
    caller should bisect (reference: light/errors.go
    ErrNewValSetCantBeTrusted)."""


class InvalidHeaderError(LightClientError):
    """The header failed basic or signature validation — the provider
    is faulty (reference: light/errors.go ErrInvalidHeader). `hop` is
    the place of the failing block among verify_adjacent_batch's
    blocks, where the bulk verification could name it: every block
    before it is then verified. None otherwise."""

    hop = None


class VerificationError(LightClientError):
    pass


class LightBlockNotFoundError(LightClientError):
    """Provider has no block at the requested height
    (reference: light/provider/errors.go ErrLightBlockNotFound)."""


class NoWitnessesError(LightClientError):
    """All witnesses have been removed — the client cannot cross-check
    and must halt (reference: light/errors.go ErrNoWitnesses)."""


class DivergenceError(LightClientError):
    """A witness provided a conflicting, verifiable header — a possible
    light-client attack; evidence has been reported
    (reference: light/detector.go)."""

    def __init__(self, msg: str, evidence=None) -> None:
        super().__init__(msg)
        self.evidence = evidence or []
