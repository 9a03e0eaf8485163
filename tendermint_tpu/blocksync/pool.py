"""BlockPool — parallel block fetching with ordered delivery.

reference: internal/blocksync/pool.go (:98-348). Per-height requester
tasks fan out over peers advertising the height; blocks come back out in
strict height order via peek_two_blocks so the reactor can verify block
H with the LastCommit carried in block H+1.

Nothing here polls. The consumer (the reactor's pool routine) sleeps in
wait_changed() until a block arrives or the peers' range moves; the
requester-maker until a height is consumed or a peer reports a higher
one; a requester until its height's event says a block arrived, was
refused (redo_request) or can no longer come (its peer went away), or
its fetch timed out. The only timers left are that timeout, the
caught-up grace and the pause after every peer has been tried.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Set, Tuple

from ..libs import rng
from ..libs.log import get_logger
from ..libs.service import Service
from ..types.block import Block

__all__ = ["BlockPool"]

MAX_PENDING_REQUESTS = 32  # heights in flight
REQUEST_TIMEOUT = 10.0  # per-attempt fetch timeout
_CAUGHT_UP_GRACE_S = 3.0  # don't declare caught-up in the first seconds


@dataclass
class _PoolPeer:
    peer_id: str
    height: int = 0
    base: int = 0
    banned: bool = False


class BlockPool(Service):
    def __init__(
        self,
        start_height: int,
        send_request: Callable[[int, str], None],  # (height, peer_id)
    ) -> None:
        super().__init__(name="blockpool", logger=get_logger("blocksync.pool"))
        self.height = start_height  # next height to verify/apply
        self._send_request = send_request
        self.peers: Dict[str, _PoolPeer] = {}
        self.max_peer_height = 0
        self._blocks: Dict[int, Tuple[Block, str]] = {}  # height → (block, peer)
        self._requesters: Dict[int, asyncio.Task] = {}
        # height -> "something about this height changed": its block
        # arrived, was dropped again, or the peer asked for it is gone
        self._block_events: Dict[int, asyncio.Event] = {}
        self._asked: Dict[int, str] = {}  # height -> peer of the open fetch
        # what the consumer reads changed (blocks, peers' range)
        self._changed = asyncio.Event()
        # a requester can be made (a height consumed, a higher range)
        self._room = asyncio.Event()
        self._started_at = 0.0

    async def on_start(self) -> None:
        self._started_at = time.monotonic()
        self.spawn(self._make_requesters_routine(), "make-requesters")

    # -- peer bookkeeping --

    def set_peer_range(self, peer_id: str, base: int, height: int) -> None:
        """From StatusResponse (reference: pool.go SetPeerRange)."""
        peer = self.peers.get(peer_id)
        if peer is None:
            peer = _PoolPeer(peer_id=peer_id)
            self.peers[peer_id] = peer
        peer.base = base
        peer.height = height
        self._peers_changed()

    def remove_peer(self, peer_id: str) -> None:
        """Received blocks are kept; the fetches open at this peer are
        asked of another at once (reference: pool.go removePeer redoes
        its requesters)."""
        self.peers.pop(peer_id, None)
        self._peers_changed()
        self._refetch_from(peer_id)

    def ban_peer(self, peer_id: str) -> None:
        """Sent us a bad block (reference: pool.go RedoRequest path)."""
        peer = self.peers.get(peer_id)
        if peer is not None:
            peer.banned = True
        self._peers_changed()
        self._refetch_from(peer_id)

    def _peers_changed(self) -> None:
        self.max_peer_height = max(
            (p.height for p in self.peers.values() if not p.banned), default=0
        )
        self._changed.set()
        self._room.set()

    def _refetch_from(self, peer_id: str) -> None:
        """Wake the requesters still waiting for a block from
        `peer_id`: it will not come."""
        for h, asked in self._asked.items():
            if asked == peer_id and h not in self._blocks:
                self._block_events[h].set()

    # -- block intake --

    def add_block(self, peer_id: str, block: Block) -> None:
        """reference: pool.go:280-305 AddBlock."""
        h = block.header.height
        if h < self.height or h in self._blocks:
            return
        if h not in self._requesters:
            return  # unsolicited height
        self._blocks[h] = (block, peer_id)
        self._block_events[h].set()
        self._changed.set()

    async def wait_changed(self) -> None:
        """Sleep until what the consumer reads may have changed since
        this last returned: a block arrived, the peers' range moved,
        or the caught-up grace ran out."""
        if not self._changed.is_set():
            grace = self._started_at + _CAUGHT_UP_GRACE_S - time.monotonic()
            await _wait(self._changed, grace + 0.01 if grace > 0 else None)
        self._changed.clear()

    # -- ordered consumption (reference: pool.go:218-260) --

    def peek_two_blocks(self) -> Tuple[Optional[Block], Optional[Block]]:
        first = self._blocks.get(self.height)
        second = self._blocks.get(self.height + 1)
        return (
            first[0] if first else None,
            second[0] if second else None,
        )

    def first_block_peer(self) -> Optional[str]:
        first = self._blocks.get(self.height)
        return first[1] if first else None

    def second_block_peer(self) -> Optional[str]:
        second = self._blocks.get(self.height + 1)
        return second[1] if second else None

    def pop_request(self) -> None:
        """Block at self.height verified and applied; advance."""
        h = self.height
        self._blocks.pop(h, None)
        t = self._requesters.pop(h, None)
        if t is not None and not t.done():
            t.cancel()
        self._block_events.pop(h, None)
        self._asked.pop(h, None)
        self.height = h + 1
        self._tasks = [x for x in self._tasks if not x.done()]
        self._room.set()

    def redo_request(self, height: int) -> None:
        """Verification failed: drop fetched blocks from this height up and
        refetch from other peers (reference: pool.go RedoRequest)."""
        for h in list(self._blocks.keys()):
            if h >= height:
                del self._blocks[h]
                # the requester for h is still alive: it wakes, finds
                # its block gone and asks another peer
                self._block_events[h].set()

    def is_caught_up(self) -> bool:
        """reference: pool.go:200-216."""
        if not self.peers:
            return False
        if time.monotonic() - self._started_at < _CAUGHT_UP_GRACE_S:
            return False
        return self.height >= self.max_peer_height

    # -- requesters --

    async def _make_requesters_routine(self) -> None:
        while True:
            self._room.clear()
            pending = len(self._requesters)
            while (
                pending < MAX_PENDING_REQUESTS
                and self.height + pending <= self.max_peer_height
            ):
                h = self.height + pending
                self._block_events[h] = asyncio.Event()
                self._requesters[h] = self.spawn(
                    self._requester(h), f"req-{h}"
                )
                pending += 1
            await self._room.wait()

    async def _requester(self, height: int) -> None:
        """Fetch `height` from some peer; retry across peers until a block
        arrives, and again if it is refused (reference: pool.go
        bpRequester:415-470). Ends by being cancelled, when the height
        is consumed (pop_request)."""
        tried: Set[str] = set()
        event = self._block_events[height]
        while True:
            event.clear()
            if height in self._blocks:
                # fetched: nothing to do unless redo_request drops it
                await event.wait()
                continue
            peer = self._pick_peer(height, tried)
            if peer is None:
                tried.clear()  # all peers tried; start over
                await asyncio.sleep(1.0)
                continue
            tried.add(peer.peer_id)
            self._asked[height] = peer.peer_id
            self._send_request(height, peer.peer_id)
            # back at the top either way: with the block, or without it
            # (timeout, the peer gone, the block dropped again) to ask
            # another peer
            await _wait(event, REQUEST_TIMEOUT)

    def _pick_peer(self, height: int, tried: Set[str]) -> Optional[_PoolPeer]:
        candidates = [
            p
            for p in self.peers.values()
            if not p.banned
            and p.height >= height
            and (p.base == 0 or p.base <= height)
            and p.peer_id not in tried
        ]
        if not candidates:
            return None
        return rng.choice(candidates)


async def _wait(event: asyncio.Event, timeout: Optional[float]) -> None:
    """Until `event` is set or `timeout` seconds have passed (None:
    however long). asyncio.wait, not wait_for: on Python 3.10, wait_for
    swallows a cancellation that races the event being set (bpo-42130
    family), leaving a requester alive forever and hanging
    Service.stop()'s gather. wait() re-raises the outer cancel
    unconditionally."""
    waiter = asyncio.ensure_future(event.wait())
    try:
        await asyncio.wait({waiter}, timeout=timeout)
    finally:
        waiter.cancel()
