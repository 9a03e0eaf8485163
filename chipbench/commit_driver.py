"""What the two commit-verification drivers share: the program-side
objects of a Ring (validator set, block ids), one request, what a
request should send the device, and the reference's verdict of it.

A request is what a node does with a block it was sent: decode the
commit from its wire bytes (a fresh `Commit`, so no per-object memo of
an earlier verification exists) and verify it against the validator
set. The outcome is the verdict as a short string in the reference's
vocabulary (chipbench/reference/commit_verify.py).
"""

from __future__ import annotations

import re
import time

from chipbench import gen
from chipbench.reference import commit_verify as R

_WRONG_SIG = re.compile(r"wrong signature \(#(\d+)\)")


class CommitDriver:
    def __init__(self, config: dict, traffic: dict, seed: int, light: bool) -> None:
        from tendermint_tpu.crypto.keys import pubkey_from_type_and_bytes
        from tendermint_tpu.types import validation as V
        from tendermint_tpu.types.block_id import BlockID, PartSetHeader
        from tendermint_tpu.types.commit import Commit
        from tendermint_tpu.types.validator import Validator, ValidatorSet

        self.light = light
        self.ring = ring = gen.Ring(config, traffic, seed, light)
        self.reference = R.Reference(ring.chain_id, ring.validators)
        self.vals = ValidatorSet(
            [
                Validator(
                    pub_key=pubkey_from_type_and_bytes(v["kind"], v["pub"]),
                    voting_power=v["power"],
                )
                for v in ring.validators
            ]
        )
        order = [v.pub_key.bytes() for v in self.vals.validators]
        if order != [v["pub"] for v in ring.validators]:
            raise RuntimeError("the program orders the validator set otherwise")
        self.block_ids = [
            BlockID(
                hash=c["block_hash"],
                part_set_header=PartSetHeader(
                    total=c["parts_total"], hash=c["parts_hash"]
                ),
            )
            for c in ring.commits
        ]
        self._decode = Commit.from_proto
        self._entry = V.verify_commit_light if light else V.verify_commit
        self._errors = (V.InvalidCommitError, V.NotEnoughVotingPowerError)
        self._not_enough = V.NotEnoughVotingPowerError
        self._expected: dict = {}  # request token -> the reference's verdict
        self.decode_s: list = []  # seconds of each request's decode, warm-up included
        # signatures of each key class among the votes the entry checks
        self.groups: dict = {}
        for v in ring.validators[: ring.checked]:
            self.groups[v["kind"]] = self.groups.get(v["kind"], 0) + 1

    def use_control(self) -> None:
        """Put the control in the program's place: the reference with
        the signature guarantee dropped (tally only). Nothing reaches
        the device; chipbench/control.py reads what the comparison
        makes of it."""
        self.run = lambda token, annotate=None: self.reference.verdict(
            self._commit(token), self.light, check_signatures=False
        )

    def _commit(self, token: tuple) -> dict:
        slot, bad = token
        return self.ring.bad_variant(slot)[0] if bad else self.ring.commits[slot]

    # -- the window's calls -------------------------------------------

    def warmup_requests(self) -> list:
        """Request tokens for set-up: every warm-up commit clean, then
        each corrupted, so every program and the CPU cross-examination
        of a bad lane have run before the window."""
        n = self.ring.n_ring
        slots = range(n, n + self.ring.n_warm)
        return [(s, False) for s in slots] + [(s, True) for s in slots]

    def window_request(self, i: int) -> tuple:
        return (self.ring.slot(i), self.ring.is_corrupted(i))

    def run(self, token: tuple, annotate=None) -> str:
        """Decode and verify one commit; the verdict as a string. The
        decode's seconds are kept for the `decode_host_ms` reader."""
        slot, bad = token
        wire = self.ring.bad_variant(slot)[1] if bad else self.ring.wire[slot]
        t0 = time.perf_counter()
        if annotate is None:
            commit = self._decode(wire)
            self.decode_s.append(time.perf_counter() - t0)
            return self._verify(slot, commit)
        with annotate("cb_decode"):
            commit = self._decode(wire)
        self.decode_s.append(time.perf_counter() - t0)
        with annotate("cb_entry"):
            return self._verify(slot, commit)

    def _verify(self, slot: int, commit) -> str:
        c = self.ring.commits[slot]
        try:
            self._entry(
                self.ring.chain_id, self.vals, self.block_ids[slot], c["height"], commit
            )
        except self._errors as e:
            m = _WRONG_SIG.search(str(e))
            if m:
                return f"wrong_signature#{m.group(1)}"
            if isinstance(e, self._not_enough):
                return "not_enough_power"
            return f"invalid:{e}"
        return "ok"

    # -- what the harness holds a request to --------------------------

    def expected(self, tokens: list) -> list:
        """The reference's verdict of each request. The signatures of
        all their distinct commits are checked first, in one go."""
        distinct = sorted(set(tokens) - set(self._expected))
        self.reference.prime([self._commit(t) for t in distinct], self.light)
        for token in distinct:
            self._expected[token] = self.reference.verdict(self._commit(token), self.light)
        return [self._expected[t] for t in tokens]

    def sent(self, token: tuple, min_batch: int, chunk) -> tuple:
        """(device dispatches, signatures) one request must add to the
        program's counters: one batch verifier a key class, none under
        the install's min_batch, a dispatch per full streamed chunk."""
        batches = sigs = 0
        for n in self.groups.values():
            if n < min_batch:
                continue
            batches += -(-n // chunk) if chunk else 1
            sigs += n
        return batches, sigs

    def work(self, token: tuple, work) -> dict:
        """int32 multiply-adds and bytes the signatures this request
        verifies need (chipbench/work.py)."""
        total = {"madds": 0, "bytes": 0}
        for kind, n in self.groups.items():
            per = work.per_signature(kind, self.ring.sign_bytes_len)
            total["madds"] += n * per["madds"]
            total["bytes"] += n * per["bytes"]
        return total
