"""Block sync reactor — catch up to the chain head, then hand off to
consensus.

reference: internal/blocksync/reactor.go. Serves BlockRequests from the
block store, feeds responses into the pool, and runs the verification
pipeline: block H is verified with the LastCommit inside block H+1 via
VerifyCommitLight — the batched device-verify showcase during catch-up —
then applied through the BlockExecutor.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Deque, Optional, Tuple

from ..libs import trace
from ..libs.log import get_logger
from ..libs.service import Service
from ..p2p.channel import Channel
from ..p2p.peermanager import PeerStatus
from ..p2p.types import ChannelDescriptor, Envelope, PeerError
from ..state.execution import BlockExecutor
from ..state.types import State
from ..store.block_store import BlockStore
from ..types.block_id import BlockID
from ..types.validation import verify_commit_light
from .msgs import (
    BlockRequestMessage,
    BlockResponseMessage,
    BlocksyncCodec,
    NoBlockResponseMessage,
    StatusRequestMessage,
    StatusResponseMessage,
)
from .metrics import BlocksyncMetrics
from .pool import BlockPool

__all__ = [
    "BlocksyncReactor",
    "BLOCKSYNC_CHANNEL",
    "blocksync_channel_descriptor",
]

BLOCKSYNC_CHANNEL = 0x40
_STATUS_UPDATE_INTERVAL = 2.0


class _MeteredCodec:
    """BlocksyncCodec with every inbound decode (the router runs it, on
    the peer's receive task) under a `blocksync_decode` span and on the
    reactor's `decode_seconds` counter: a BlockResponse is a whole
    block, its LastCommit's signatures included."""

    encode = staticmethod(BlocksyncCodec.encode)

    def __init__(self, metrics: BlocksyncMetrics) -> None:
        self._seconds = metrics.decode_seconds

    def decode(self, data: bytes):
        t0 = time.perf_counter()
        with trace.span("blocksync_decode", bytes=len(data)):
            msg = BlocksyncCodec.decode(data)
        self._seconds.inc(time.perf_counter() - t0)
        return msg


def blocksync_channel_descriptor(
    metrics: Optional[BlocksyncMetrics] = None,
):
    """reference: reactor.go:66-75. With the reactor's `metrics`, the
    channel's decodes are counted there."""
    return ChannelDescriptor(
        channel_id=BLOCKSYNC_CHANNEL,
        message_type=(
            BlocksyncCodec if metrics is None else _MeteredCodec(metrics)
        ),
        priority=5,
        send_queue_capacity=1000,
        recv_buffer_capacity=1024,
        name="blocksync",
    )


class BlocksyncReactor(Service):
    def __init__(
        self,
        state: State,
        block_exec: BlockExecutor,
        block_store: BlockStore,
        channel: Channel,
        peer_updates: asyncio.Queue,
        block_sync: bool = True,  # start in sync mode?
        consensus_reactor=None,  # switch target when caught up
        event_bus=None,
        metrics: Optional[BlocksyncMetrics] = None,
    ) -> None:
        super().__init__(name="blocksync", logger=get_logger("blocksync"))
        self.metrics = metrics if metrics is not None else BlocksyncMetrics()
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.channel = channel
        self.peer_updates = peer_updates
        self.block_sync = block_sync
        self.consensus_reactor = consensus_reactor
        self.event_bus = event_bus
        start_height = state.last_block_height + 1
        if start_height == 1:
            start_height = state.initial_height
        self.pool = BlockPool(start_height, self._request_block)
        self.synced = False
        # the last blocks refused: (height, the commit's error, the
        # providers banned for it), for an operator and the debug bundle
        self.refusals: Deque[Tuple[int, str, Tuple[str, ...]]] = deque(
            maxlen=16
        )

    async def on_start(self) -> None:
        self.spawn(self._recv_routine(), "recv")
        self.spawn(self._peer_update_routine(), "peer-updates")
        if self.block_sync:
            await self._start_sync_routines()

    async def on_stop(self) -> None:
        if self.pool.is_running:
            await self.pool.stop()

    async def start_sync(self, state: State) -> None:
        """Begin block sync from a statesync-bootstrapped state
        (reference: node wiring bcReactor.SwitchToBlockSync after
        stateSyncReactor.Sync)."""
        self.state = state
        self.block_sync = True
        start = state.last_block_height + 1
        self.pool.height = max(self.pool.height, start)
        await self._start_sync_routines()

    async def _start_sync_routines(self) -> None:
        # idempotent: two concurrent pool routines would double-apply blocks
        if getattr(self, "_sync_routines_started", False):
            return
        self._sync_routines_started = True
        if not self.pool.is_running:
            await self.pool.start()
        self.spawn(self._pool_routine(), "pool")
        self.spawn(self._status_routine(), "status")

    def _request_block(self, height: int, peer_id: str) -> None:
        self.channel.try_send(
            Envelope(message=BlockRequestMessage(height=height), to=peer_id)
        )

    # -- inbound --

    async def _recv_routine(self) -> None:
        async for envelope in self.channel:
            try:
                await self._handle_msg(envelope)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                self.logger.error(
                    "failed to process blocksync message", err=str(e)
                )
                await self.channel.send_error(
                    PeerError(node_id=envelope.from_peer, err=str(e))
                )

    async def _handle_msg(self, envelope: Envelope) -> None:
        """reference: reactor.go:236-320 handleMessage."""
        msg = envelope.message
        peer_id = envelope.from_peer
        if isinstance(msg, BlockRequestMessage):
            block = self.block_store.load_block(msg.height)
            if block is not None:
                self.channel.try_send(
                    Envelope(
                        message=BlockResponseMessage(block=block), to=peer_id
                    )
                )
            else:
                self.channel.try_send(
                    Envelope(
                        message=NoBlockResponseMessage(height=msg.height),
                        to=peer_id,
                    )
                )
        elif isinstance(msg, BlockResponseMessage):
            if msg.block is not None:
                self.pool.add_block(peer_id, msg.block)
        elif isinstance(msg, NoBlockResponseMessage):
            pass  # requester will time out and retry another peer
        elif isinstance(msg, StatusRequestMessage):
            self.channel.try_send(
                Envelope(
                    message=StatusResponseMessage(
                        height=self.block_store.height(),
                        base=self.block_store.base(),
                    ),
                    to=peer_id,
                )
            )
        elif isinstance(msg, StatusResponseMessage):
            self.pool.set_peer_range(peer_id, msg.base, msg.height)
        else:
            raise ValueError(
                f"unexpected blocksync message {type(msg).__name__}"
            )

    async def _peer_update_routine(self) -> None:
        while True:
            update = await self.peer_updates.get()
            if update.status == PeerStatus.UP:
                # learn the peer's range; offer ours
                self.channel.try_send(
                    Envelope(
                        message=StatusRequestMessage(), to=update.node_id
                    )
                )
                self.channel.try_send(
                    Envelope(
                        message=StatusResponseMessage(
                            height=self.block_store.height(),
                            base=self.block_store.base(),
                        ),
                        to=update.node_id,
                    )
                )
            elif update.status == PeerStatus.DOWN:
                self.pool.remove_peer(update.node_id)

    async def _status_routine(self) -> None:
        while True:
            await asyncio.sleep(_STATUS_UPDATE_INTERVAL)
            self.channel.try_send(
                Envelope(message=StatusRequestMessage(), broadcast=True)
            )

    # -- the sync pipeline (reference: reactor.go:322-450 poolRoutine) --

    async def _pool_routine(self) -> None:
        while True:
            if self.pool.is_caught_up():
                await self._switch_to_consensus()
                return
            first, second = self.pool.peek_two_blocks()
            if first is None or second is None:
                with trace.span("blocksync_wait", height=self.pool.height):
                    await self.pool.wait_changed()
                continue
            await self._verify_apply(first, second)

    async def _verify_apply(self, first, second) -> None:
        """Verify `first` with `second.LastCommit`, then apply
        (reference: reactor.go:452-520)."""
        height = first.header.height
        with trace.span("block_parts", height=height):
            first_parts = first.make_part_set()
        try:
            # `commits`: the commits whose votes went into one batch
            with trace.span("blocksync_verify", height=height, commits=1):
                first_id = BlockID(
                    hash=first.hash(), part_set_header=first_parts.header()
                )
                # the whole LastCommit of block H+1 in one device batch
                # call
                verify_commit_light(
                    self.state.chain_id,
                    self.state.validators,
                    first_id,
                    height,
                    second.last_commit,
                )
        except Exception as e:
            self.logger.error(
                "invalid last commit during block sync",
                height=height,
                err=str(e),
            )
            # punish both providers and refetch
            providers = tuple(
                peer_id
                for peer_id in (
                    self.pool.first_block_peer(),
                    self.pool.second_block_peer(),
                )
                if peer_id
            )
            self.refusals.append((height, str(e), providers))
            for peer_id in providers:
                self.pool.ban_peer(peer_id)
                await self.channel.send_error(
                    PeerError(node_id=peer_id, err=f"bad block: {e}")
                )
            self.pool.redo_request(height)
            self.metrics.redo_requests.inc()
            return

        with trace.span("block_store_save", height=height):
            self.block_store.save_block(
                first, first_parts, second.last_commit
            )
        self.state = await self.block_exec.apply_block(
            self.state, first_id, first
        )
        self.pool.pop_request()
        self.metrics.blocks_applied.inc()
        if self.pool.height % 100 == 0:
            self.logger.info(
                "block-synced", height=self.pool.height,
                target=self.pool.max_peer_height,
            )

    async def _switch_to_consensus(self) -> None:
        """reference: reactor.go poolRoutine switch branch +
        consensus/reactor.go:252 SwitchToConsensus."""
        self.synced = True
        self.block_sync = False
        self.logger.info(
            "caught up; switching to consensus",
            height=self.state.last_block_height,
        )
        if self.event_bus is not None:
            from ..types import events as E

            self.event_bus.publish_block_sync_status(
                E.EventDataBlockSyncStatus(
                    complete=True, height=self.state.last_block_height
                )
            )
        if self.pool.is_running:
            await self.pool.stop()
        if self.consensus_reactor is not None:
            # rebuild LastCommit from the stored seen-commit, then roll the
            # round state forward (reference: consensus/reactor.go:252-306)
            cs = self.consensus_reactor.cs
            if self.state.last_block_height > 0:
                cs._reconstruct_last_commit_from_store(self.state)
            cs._update_to_state(self.state)
            await self.consensus_reactor.switch_to_consensus(self.state)
