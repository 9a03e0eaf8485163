"""The sync pipeline wakes on events, not on timers: the pool's
consumer when a block arrives or the peers' range moves, a requester
when its block is refused or its peer goes away, the requester-maker
when a peer reports a higher range. Each case lets the loop turn a few
times with no time passing: a 20, 50 or 100 ms poll would not have
fired. And the reactor's decodes, refusals and applied blocks are
counted in the registry it is handed."""

import asyncio
import time

import pytest

from tendermint_tpu.blocksync import (
    BlockPool,
    BlockResponseMessage,
    BlocksyncCodec,
    StatusResponseMessage,
    blocksync_channel_descriptor,
)
from tendermint_tpu.blocksync.metrics import BlocksyncMetrics
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.metrics import Registry
from tendermint_tpu.types.block import make_block
from tendermint_tpu.types.commit import Commit

TURNS = 8  # loop iterations a wake-up may take; none of them waits


def run(coro):
    return asyncio.run(coro)


async def turns(n: int = TURNS) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


def block_at(height: int):
    block = make_block(height, [], Commit(), [])
    block.header.height = height
    return block


async def started_pool(sent: list, peers=("peerA", "peerB"), top: int = 5) -> BlockPool:
    pool = BlockPool(1, lambda h, p: sent.append((h, p)))
    await pool.start()
    for peer in peers:
        pool.set_peer_range(peer, 0, top)
    await turns()
    return pool


def test_a_reported_range_makes_the_requesters_at_once():
    async def go():
        sent = []
        t0 = time.monotonic()
        pool = await started_pool(sent)
        assert {h for h, _p in sent} == {1, 2, 3, 4, 5}
        # a higher range: the new heights are asked for without a poll
        pool.set_peer_range("peerA", 0, 7)
        await turns()
        assert {h for h, _p in sent} == {1, 2, 3, 4, 5, 6, 7}
        assert time.monotonic() - t0 < 0.02
        await pool.stop()

    run(go())


def test_the_consumer_wakes_when_a_block_arrives():
    async def go():
        pool = await started_pool([])
        pool._changed.clear()
        woke = []

        async def consumer():
            while pool.peek_two_blocks()[1] is None:
                await pool.wait_changed()
                woke.append(time.monotonic())

        task = asyncio.ensure_future(consumer())
        await turns()
        assert not woke and not task.done()
        t0 = time.monotonic()
        pool.add_block("peerA", block_at(1))
        await turns()
        assert len(woke) == 1 and not task.done()  # one block is not two
        pool.add_block("peerB", block_at(2))
        await turns()
        assert task.done() and len(woke) == 2
        assert woke[-1] - t0 < 0.04  # the old poll slept 50 ms
        # consuming a height makes room for the next requester at once
        before = set(pool._requesters)
        pool.set_peer_range("peerA", 0, 40)
        await turns()
        assert max(pool._requesters) == 32
        pool.pop_request()
        await turns()
        assert max(pool._requesters) == 33 and min(pool._requesters) == 2 and before
        await pool.stop()

    run(go())


@pytest.mark.parametrize("how", ["redo_request", "ban_peer", "remove_peer"])
def test_a_requester_asks_another_peer_without_waiting(how):
    async def go():
        sent = []
        pool = await started_pool(sent)
        first = dict(sent)  # height -> the peer first asked
        t0 = time.monotonic()
        if how == "redo_request":
            # the block came and was refused: dropped, and asked of the other peer
            pool.add_block(first[1], block_at(1))
            pool.add_block(first[2], block_at(2))
            await turns()
            assert len(sent) == 5
            pool.redo_request(2)
            await turns()
            again = sent[5:]
            assert [h for h, _p in again] == [2] and again[0][1] != first[2]
            assert pool.peek_two_blocks()[0] is not None and pool.peek_two_blocks()[1] is None
        else:
            # the peer went away with fetches open: each is asked of the other
            getattr(pool, how)("peerA")
            await turns()
            again = sent[5:]
            assert sorted(h for h, _p in again) == sorted(h for h, p in first.items() if p == "peerA")
            assert {p for _h, p in again} <= {"peerB"}
        assert time.monotonic() - t0 < 0.04  # the old poll slept 100 ms
        await pool.stop()

    run(go())


def test_the_channels_decodes_are_spans_and_seconds_in_the_reactors_registry():
    registry = Registry()
    metrics = BlocksyncMetrics(registry)
    descriptor = blocksync_channel_descriptor(metrics)
    wire = BlocksyncCodec.encode(BlockResponseMessage(block=block_at(3)))
    trace.reset()
    trace.enable()
    try:
        message = descriptor.decode(wire)
        status = descriptor.decode(BlocksyncCodec.encode(StatusResponseMessage(height=9, base=1)))
    finally:
        trace.disable()
    assert message.block.header.height == 3 and status.height == 9
    assert descriptor.encode(message) == wire
    spans = [s for s in trace.snapshot() if s.name == "blocksync_decode"]
    assert [s.attrs["bytes"] for s in spans] == [len(wire), 6]
    assert 0 < metrics.decode_seconds.value() < 1
    rendered = registry.render()
    for name in ("blocksync_blocks_applied", "blocksync_decode_seconds", "blocksync_redo_requests"):
        assert name in rendered
    # without a reactor's metrics the channel is the plain codec
    assert blocksync_channel_descriptor().message_type is BlocksyncCodec
