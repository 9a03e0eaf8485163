"""Entry `light.Client.verify_light_block_at_height` with
`sequential=True`: what a light client does after being offline (an IBC
relayer, a wallet, a state-syncing node, a light proxy for many).

A request is one sync: a fresh `Client` over a fresh `LightStore`, its
trust root the segment's first header, one primary and one witness that
are in-memory `Provider`s decoding each light block from its wire bytes
inside the request (a fresh object each time, so no per-object memo of
an earlier sync exists), the primary serving `light_blocks(first, last)`
in bulk, then `verify_light_block_at_height(root + hops, now)` on one
event loop made here. The outcome is the verdict as a short string in
the reference's vocabulary (chipbench/reference/light_verify.py), built
from the result or the error and from what the store then holds.
"""

from __future__ import annotations

import asyncio
import re
import time

from chipbench import gen, light_gen
from chipbench.reference import light_verify as L

_WRONG_SIG = re.compile(r"wrong signature \(#(\d+)\)")


def setup(config: dict, traffic: dict, seed: int) -> "LightSyncDriver":
    return LightSyncDriver(config, traffic, seed)


class LightSyncDriver:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        from tendermint_tpu.light import Client, LightStore, TrustOptions
        from tendermint_tpu.light.errors import LightClientError
        from tendermint_tpu.light.provider import Provider
        from tendermint_tpu.store.kv import MemKV
        from tendermint_tpu.types.light import LightBlock

        if not config["sequential"] or config["witnesses"] != 1:
            raise RuntimeError("this driver syncs sequentially, with one witness")
        self.chain = chain = light_gen.Chain(config, traffic, seed)
        self.period_ns = config["trusting_period_s"] * 10**9
        self.window_hops = traffic["window_hops"]
        self.reference = L.Reference(
            chain.chain_id, chain.validators, self.period_ns,
            config["max_clock_drift_s"] * 10**9, chain.now_ns,
        )  # fmt: skip
        self.loop = asyncio.new_event_loop()
        self._expected: dict = {}  # request token -> the reference's verdict
        self.decode_s: list = []  # seconds of each request's decodes, warm-up included
        self._errors = (LightClientError, ValueError)
        self._from_wire = LightBlock.from_proto
        decode = self._decode

        class Peer(Provider):
            """Serves the chain's wire bytes."""

            def __init__(self, name: str) -> None:
                self.name = name

            def id(self) -> str:
                return self.name

            async def light_block(self, height: int):
                return decode(height)

            async def light_blocks(self, first: int, last: int) -> list:
                return [decode(h) for h in range(first, last + 1)]

            async def report_evidence(self, ev) -> None:
                raise RuntimeError("the witness serves the primary's chain")

        def client(root: dict):
            return Client(
                chain.chain_id,
                TrustOptions(period_ns=self.period_ns, height=root["height"], hash=root["hash"]),
                Peer("primary"),
                [Peer("witness")],
                LightStore(MemKV()),
                sequential=True,
                max_clock_drift_ns=config["max_clock_drift_s"] * 10**9,
            )

        self._client = client
        self._swapped: dict = {}  # place in the chain -> wire, for one request
        self._annotate = None
        self._decoding = 0.0

    def _decode(self, height: int):
        """A fresh LightBlock from the wire bytes at `height`, the
        corrupted variant's where the request carries one there."""
        at = height - gen.BASE_HEIGHT
        wire = self._swapped.get(at) or self.chain.wire[at]
        t0 = time.perf_counter()
        if self._annotate is None:
            block = self._from_wire(wire)
        else:
            with self._annotate("cb_decode"):
                block = self._from_wire(wire)
        self._decoding += time.perf_counter() - t0
        return block

    def use_control(self) -> None:
        """Put the control in the program's place: the reference with
        the signature guarantee dropped. Nothing reaches the device;
        chipbench/control.py reads what the comparison makes of it."""
        self.run = lambda token, annotate=None: self._reference_verdict(token, False)

    def _reference_verdict(self, token: tuple, check_signatures: bool = True) -> str:
        blocks = self.chain.segment_blocks(*token)
        return self.reference.verdict(blocks, blocks[0]["header"]["hash"], check_signatures)

    # -- the window's calls -------------------------------------------

    def warmup_requests(self) -> list:
        """Request tokens for set-up: a sync over each warm-up segment,
        the last of them over its corrupted variant, so every program
        and the CPU cross-examination of a bad lane have run before the
        window, on signatures the window never meets."""
        n, warm = self.chain.n_ring, self.chain.n_warm
        return [(n + j, j == warm - 1) for j in range(warm)]

    def window_request(self, i: int) -> tuple:
        return (self.chain.segment(i), self.chain.is_corrupted(i))

    def run(self, token: tuple, annotate=None) -> str:
        """One sync; the verdict as a string. The seconds its decodes
        took are kept for the `decode_host_ms` reader."""
        segment, bad = token
        first, last = self.chain.span(segment)
        self._swapped = {}
        if bad:
            at, _block, wire = self.chain.bad_variant(segment)
            self._swapped = {at: wire}
        self._annotate, self._decoding = annotate, 0.0
        client = self._client(self.chain.blocks[first]["header"])
        target = self.chain.blocks[last]["header"]["height"]
        if annotate is None:
            verdict = self._sync(client, target)
        else:
            with annotate("cb_entry"):
                verdict = self._sync(client, target)
        self.decode_s.append(self._decoding)
        return verdict

    def _sync(self, client, target: int) -> str:
        try:
            block = self.loop.run_until_complete(
                client.verify_light_block_at_height(target, self.chain.now_ns)
            )
            outcome = f"ok:{block.height}:{block.signed_header.hash().hex()}"
        except self._errors as e:
            outcome = None
            error = e
        store = client.store
        oldest, newest = store.first_light_block(), store.latest_light_block()
        stored = ""
        if oldest is not None:
            stored = f"{oldest.height}-{newest.height}"
            if store.size() != newest.height - oldest.height + 1:
                stored += f"!{store.size()}"  # a gap: no reference verdict reads so
        if outcome is None:
            # the sync stopped at the first height it did not store
            height = newest.height + 1 if newest is not None else 0
            m = _WRONG_SIG.search(str(error))
            if m:
                outcome = f"wrong_signature:{height}#{m.group(1)}"
            else:
                outcome = f"invalid:{height}:{type(error).__name__}:{error}"
        return f"{outcome};stored={stored}"

    # -- what the harness holds a request to --------------------------

    def expected(self, tokens: list) -> list:
        """The reference's verdict of each request. The signatures of
        all their distinct segments are checked first, in one go."""
        distinct = sorted(set(tokens) - set(self._expected))
        self.reference.prime(
            [b for token in distinct for b in self.chain.segment_blocks(*token)[1:]]
        )
        for token in distinct:
            self._expected[token] = self._reference_verdict(token)
        return [self._expected[t] for t in tokens]

    def _windows(self, token: tuple) -> list:
        """Hops of each merged window a sync sends the device: all of a
        clean sync's, up to the bad header's for a corrupted one."""
        segment, bad = token
        hops, window = self.chain.hops, self.window_hops
        sizes = [min(window, hops - at) for at in range(0, hops, window)]
        if bad:
            first, _last = self.chain.span(segment)
            bad_hop = self.chain.bad_variant(segment)[0] - first
            sizes = sizes[: (bad_hop - 1) // window + 1]
        return sizes

    def sent(self, token: tuple, min_batch: int, chunk) -> tuple:
        """(device dispatches, signatures) one request must add to the
        program's counters: one batch verifier a merged window, a
        dispatch per streamed chunk of it and one for the remainder."""
        batches = sigs = 0
        for hops in self._windows(token):
            n = hops * self.chain.checked
            if n < min_batch:
                continue
            batches += -(-n // chunk) if chunk else 1
            sigs += n
        return batches, sigs

    def work(self, token: tuple, work) -> dict:
        """int32 multiply-adds and bytes the signatures a clean sync
        verifies need (chipbench/work.py)."""
        total = {"madds": 0, "bytes": 0}
        for v in self.chain.validators[: self.chain.checked]:
            per = work.per_signature(v["kind"], self.chain.sign_bytes_len)
            total["madds"] += self.chain.hops * per["madds"]
            total["bytes"] += self.chain.hops * per["bytes"]
        return total
