"""Key and batch-verifier interfaces — the plugin boundary.

Mirrors the semantics of the reference's crypto.PubKey / crypto.PrivKey /
crypto.BatchVerifier interfaces (reference: crypto/crypto.go:23-61). The
BatchVerifier contract is the seam the whole TPU offload hangs on:

    add(pubkey, message, signature) -> None   (queue; may raise on bad input)
    verify() -> (all_ok: bool, per_item: list[bool])

`verify()` must report exactly which indices failed — consensus uses the
bitmap to attribute invalid signatures to validators
(reference: types/validation.go:240-249).
"""

from __future__ import annotations

import hashlib
from abc import ABC, abstractmethod
from typing import List, Tuple

__all__ = [
    "PubKey",
    "PrivKey",
    "BatchVerifier",
    "Address",
    "address_hash",
    "register_key_type",
    "pubkey_from_type_and_bytes",
    "pubkey_to_proto",
    "pubkey_from_proto",
]

ADDRESS_SIZE = 20  # tmhash truncated size (reference: crypto/crypto.go:11-19)

Address = bytes


def address_hash(data: bytes) -> Address:
    """sha256(data)[:20] (reference: crypto/crypto.go AddressHash)."""
    return hashlib.sha256(data).digest()[:ADDRESS_SIZE]


class PubKey(ABC):
    @abstractmethod
    def address(self) -> Address: ...

    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def verify_signature(self, msg: bytes, sig: bytes) -> bool: ...

    @abstractmethod
    def type(self) -> str: ...

    def equals(self, other: "PubKey") -> bool:
        return self.type() == other.type() and self.bytes() == other.bytes()

    def __eq__(self, other: object) -> bool:
        return isinstance(other, PubKey) and self.equals(other)

    def __hash__(self) -> int:
        return hash((self.type(), self.bytes()))

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.bytes().hex()[:16]}…)"


class PrivKey(ABC):
    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def sign(self, msg: bytes) -> bytes: ...

    @abstractmethod
    def pub_key(self) -> PubKey: ...

    @abstractmethod
    def type(self) -> str: ...

    def __repr__(self) -> str:
        # never render key material: reprs reach logs, tracebacks, and
        # debugger output (tmct ct-leak-telemetry lifetime contract)
        return f"<{type(self).__name__} redacted>"


class BatchVerifier(ABC):
    """Accumulate (pk, msg, sig) triples, verify all at once.

    Implementations: CPU per-curve batchers and the TPU-backed verifier in
    tendermint_tpu.crypto.tpu_verifier. Semantics of verify() follow
    reference crypto/crypto.go:53-61: returns (every sig valid, bitmap). The
    bitmap has one entry per add() in order. verify() is one-shot on every
    backend — it drains the queue, and a second call without new add()s
    returns (False, []) (a verifier is one batch, matching the reference's
    one-BatchVerifier-per-commit usage).
    """

    @abstractmethod
    def add(self, pub_key: PubKey, message: bytes, signature: bytes) -> None: ...

    @abstractmethod
    def verify(self) -> Tuple[bool, List[bool]]: ...

    def add_many(
        self, pub_keys, messages, signatures, key_bytes=None
    ) -> None:
        """add() for whole columns: the triples
        (pub_keys[i], messages[i], signatures[i]) in order, with add()'s
        checks and add()'s effects. `key_bytes`, where the caller holds
        it, is the column of pub_keys[i].bytes(); a verifier that
        overrides this to take a batch without per-triple Python
        (crypto/tpu_verifier.py) reads it and calls no method a key.
        crypto.batch.drain_classes hands every class over through here;
        add() stays for a caller with one triple."""
        for pub_key, message, signature in zip(
            pub_keys, messages, signatures
        ):
            self.add(pub_key, message, signature)

    def host_operand(self, n: int) -> bool:
        """True when launching `n` queued triples costs the host more
        than joining byte rows (a device verifier whose kernel takes an
        operand made on the host at that width).
        crypto.batch.drain_classes launches such a class last, so that
        its host work runs under the other classes' device time."""
        return False

    def launch(self) -> bool:
        """Start whatever is queued without waiting for it, so that the
        caller can fill another verifier before it blocks in verify().
        True when the whole batch is then in flight. A verifier that
        works on the host has nothing to start: verify() does it all."""
        return False

    def abandon(self) -> None:
        """Forget what launch() started: the caller is raising and will
        never call verify(). Nothing to forget on the host."""

    def __len__(self) -> int:  # number of queued items; override if cheap
        raise NotImplementedError


# -- key type registry (reference: crypto/encoding/codec.go + jsontypes) --

_KEY_TYPES: dict[str, type] = {}
_PROTO_FIELD: dict[str, int] = {}  # key type -> PublicKey oneof field number


def register_key_type(key_type: str, pubkey_cls: type, proto_field: int) -> None:
    _KEY_TYPES[key_type] = pubkey_cls
    _PROTO_FIELD[key_type] = proto_field


def pubkey_from_type_and_bytes(key_type: str, data: bytes) -> PubKey:
    cls = _KEY_TYPES.get(key_type)
    if cls is None:
        raise ValueError(f"unknown key type {key_type!r}")
    return cls(data)


# privval key types (reference: privval/file.go:188 GenFilePV's switch —
# ed25519 default, secp256k1 on request). One dispatch for the three
# consumers: FilePVKey.load, FilePV.generate, and the gen-validator CLI.


def _privval_priv_cls(key_type: str) -> type:
    if key_type in ("", "ed25519"):
        from .ed25519 import PrivKeyEd25519

        return PrivKeyEd25519
    if key_type == "secp256k1":
        from .secp256k1 import PrivKeySecp256k1

        return PrivKeySecp256k1
    raise ValueError(f"key type: {key_type} is not supported")


def generate_priv_key(key_type: str = "ed25519") -> PrivKey:
    return _privval_priv_cls(key_type).generate()


def privkey_from_type_and_bytes(key_type: str, data: bytes) -> PrivKey:
    return _privval_priv_cls(key_type)(data)


def pubkey_to_proto(pk: PubKey) -> bytes:
    """Encode as tendermint.crypto.PublicKey (oneof: ed25519=1,
    secp256k1=2, sr25519=3 — reference: proto/tendermint/crypto/keys.pb.go).
    Used verbatim in validator-set hashing (types/validator.go:130)."""
    from ..encoding.proto import ProtoWriter

    field = _PROTO_FIELD.get(pk.type())
    if field is None:
        raise ValueError(f"key type {pk.type()!r} has no proto mapping")
    w = ProtoWriter()
    w.bytes(field, pk.bytes())
    return w.finish()


def pubkey_from_proto(data: bytes) -> PubKey:
    from ..encoding.proto import iter_fields

    for field, _wt, value in iter_fields(data):
        for key_type, f in _PROTO_FIELD.items():
            if f == field:
                return pubkey_from_type_and_bytes(key_type, value)
    raise ValueError("PublicKey proto has no recognized key")
