"""Indexer sink + service tests (reference model:
internal/state/indexer/indexer_service_test.go, sink/kv/kv_test.go)."""

import asyncio

from tendermint_tpu.abci import types as abci
from tendermint_tpu.eventbus import EventBus
from tendermint_tpu.state.indexer import (
    IndexerService,
    KVSink,
    NullSink,
    TxResult,
)
from tendermint_tpu.store.kv import MemKV
from tendermint_tpu.types import events as E
from tendermint_tpu.types.tx import tx_hash


def run(coro):
    return asyncio.run(coro)


def make_tx_result(height, index, tx, key=b"k", indexed=True):
    return TxResult(
        height=height,
        index=index,
        tx=tx,
        result=abci.ResponseDeliverTx(
            events=(
                abci.Event(
                    type="app",
                    attributes=(
                        abci.EventAttribute(b"key", key, indexed),
                        abci.EventAttribute(b"noindex", b"x", False),
                    ),
                ),
            )
        ),
    )


def test_kv_sink_tx_roundtrip_and_search():
    sink = KVSink(MemKV())
    trs = [
        make_tx_result(1, 0, b"tx-a", key=b"apple"),
        make_tx_result(1, 1, b"tx-b", key=b"banana"),
        make_tx_result(2, 0, b"tx-c", key=b"apple"),
    ]
    sink.index_tx_events(trs)

    got = sink.get_tx_by_hash(tx_hash(b"tx-a"))
    assert got is not None and got.tx == b"tx-a" and got.height == 1

    # search by indexed app event
    hits = sink.search_tx_events("app.key = 'apple'")
    assert [t.tx for t in hits] == [b"tx-a", b"tx-c"]

    # non-indexed attributes are not searchable
    assert sink.search_tx_events("app.noindex = 'x'") == []

    # reserved keys: height + hash
    assert [t.tx for t in sink.search_tx_events("tx.height = 2")] == [b"tx-c"]
    h = tx_hash(b"tx-b").hex().upper()
    assert [t.tx for t in sink.search_tx_events(f"tx.hash = '{h}'")] == [b"tx-b"]

    # conjunction intersects
    hits = sink.search_tx_events("app.key = 'apple' AND tx.height < 2")
    assert [t.tx for t in hits] == [b"tx-a"]

    # range over heights
    hits = sink.search_tx_events("tx.height >= 1")
    assert len(hits) == 3


def test_kv_sink_block_events():
    sink = KVSink(MemKV())
    sink.index_block_events(
        5,
        [
            abci.Event(
                type="val_update",
                attributes=(abci.EventAttribute(b"pubkey", b"aa", True),),
            )
        ],
    )
    sink.index_block_events(6, [])
    assert sink.has_block(5) and sink.has_block(6) and not sink.has_block(7)
    assert sink.search_block_events("val_update.pubkey = 'aa'") == [5]
    assert sink.search_block_events("block.height > 5") == [6]


def test_indexer_service_end_to_end():
    async def go():
        bus = EventBus()
        await bus.start()
        sink = KVSink(MemKV())
        svc = IndexerService([sink, NullSink()], bus)
        await svc.start()

        class _Hdr:
            height = 3

        class _Blk:
            header = _Hdr()

        bus.publish_new_block(
            E.EventDataNewBlock(
                block=_Blk(),
                block_id=None,
                result_end_block=abci.ResponseEndBlock(
                    events=(
                        abci.Event(
                            type="end",
                            attributes=(
                                abci.EventAttribute(b"done", b"yes", True),
                            ),
                        ),
                    )
                ),
            )
        )
        bus.publish_tx(
            E.EventDataTx(
                height=3,
                tx=b"indexed-tx",
                index=0,
                result=abci.ResponseDeliverTx(),
            ),
            tx_hash=tx_hash(b"indexed-tx"),
        )
        # service consumes asynchronously
        for _ in range(100):
            if sink.has_block(3) and sink.get_tx_by_hash(tx_hash(b"indexed-tx")):
                break
            await asyncio.sleep(0.01)
        assert sink.has_block(3)
        assert sink.search_block_events("end.done = 'yes'") == [3]
        assert sink.get_tx_by_hash(tx_hash(b"indexed-tx")).height == 3
        await svc.stop()
        await bus.stop()

    run(go())


def test_kv_sink_nul_bytes_in_values():
    """Values containing the key separator must not corrupt matching."""
    sink = KVSink(MemKV())
    sink.index_tx_events(
        [make_tx_result(1, 0, b"tx-nul", key=b"a\x00b"),
         make_tx_result(1, 1, b"tx-plain", key=b"a")]
    )
    hits = sink.search_tx_events("app.key = 'a'")
    assert [t.tx for t in hits] == [b"tx-plain"]
    hits = sink.search_tx_events("app.key CONTAINS 'a'")
    assert {t.tx for t in hits} == {b"tx-nul", b"tx-plain"}


def _sql_sink():
    from tendermint_tpu.state.sink_sql import SQLSink

    return SQLSink("sqlite::memory:", chain_id="sql-chain")


def test_sql_sink_search_parity_with_kv():
    """The SQL sink (reference psql schema) answers the same queries
    the KV sink does — over every operator the query language has."""
    kv = KVSink(MemKV())
    sql = _sql_sink()
    trs = [
        make_tx_result(1, 0, b"tx-a", key=b"apple"),
        make_tx_result(1, 1, b"tx-b", key=b"banana"),
        make_tx_result(2, 0, b"tx-c", key=b"apple"),
        make_tx_result(3, 0, b"tx-d", key=b"apricot"),
    ]
    kv.index_tx_events(trs)
    sql.index_tx_events(trs)
    h = tx_hash(b"tx-b").hex().upper()
    for q in (
        "app.key = 'apple'",
        "app.noindex = 'x'",
        "tx.height = 2",
        f"tx.hash = '{h}'",
        "app.key = 'apple' AND tx.height < 2",
        "tx.height >= 1",
        "app.key CONTAINS 'ap'",
        "app.key EXISTS",
    ):
        assert [t.tx for t in sql.search_tx_events(q)] == [
            t.tx for t in kv.search_tx_events(q)
        ], q
    assert sql.get_tx_by_hash(tx_hash(b"tx-c")).height == 2
    sql.close()


def test_sql_sink_block_events_and_schema():
    sql = _sql_sink()
    sql.index_block_events(
        5,
        [
            abci.Event(
                type="epoch",
                attributes=(abci.EventAttribute(b"phase", b"end", True),),
            )
        ],
    )
    sql.index_block_events(6, [])
    assert sql.has_block(5) and sql.has_block(6) and not sql.has_block(7)
    assert sql.search_block_events("epoch.phase = 'end'") == [5]
    assert sql.search_block_events("block.height > 5") == [6]
    # the reference schema shape is queryable directly (operators join
    # these tables; psql/schema.sql)
    rows = sql._exec(
        "SELECT b.height, e.type, a.composite_key, a.value "
        "FROM attributes a JOIN events e ON e.rowid = a.event_id "
        "JOIN blocks b ON b.rowid = e.block_id"
    ).fetchall()
    assert (5, "epoch", "epoch.phase", "end") in rows
    sql.close()


def test_sql_sink_replay_is_idempotent():
    sql = _sql_sink()
    tr = make_tx_result(4, 0, b"tx-r", key=b"kiwi")
    sql.index_tx_events([tr])
    sql.index_tx_events([tr])  # replay after crash-restart
    assert len(sql.search_tx_events("app.key = 'kiwi'")) == 1
    sql.close()


def test_sql_sink_in_node_config(tmp_path):
    """`indexer = ["psql"]` boots a node writing the SQL sink and
    tx_search over RPC answers from it."""
    import time as _time

    from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
    from tendermint_tpu.node.node import make_node
    from tests.test_node import make_genesis, make_home

    async def go():
        priv = PrivKeyEd25519.from_seed(b"\x71" * 32)
        genesis = make_genesis([priv])
        cfg = make_home(tmp_path, 0, genesis, priv)
        cfg.tx_index.indexer = ["psql"]
        cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
        node = make_node(cfg)
        from tendermint_tpu.state.sink_sql import SQLSink

        assert any(isinstance(s, SQLSink) for s in node.indexer.sinks)
        await node.start()
        try:
            tx = b"sql-sink-tx=%d" % _time.time_ns()
            await node.mempool.check_tx(tx)
            deadline = _time.monotonic() + 30
            sink = next(
                s for s in node.indexer.sinks if isinstance(s, SQLSink)
            )
            h = tx_hash(tx)
            while sink.get_tx_by_hash(h) is None:
                assert _time.monotonic() < deadline, "tx never indexed"
                await asyncio.sleep(0.1)
            got = sink.get_tx_by_hash(h)
            assert got.tx == tx
            # tx_search serves from the SQL sink (no kv sink configured)
            from tendermint_tpu.rpc.jsonrpc import RPCRequest

            resp = await node.rpc_env.tx_search(
                RPCRequest(
                    method="tx_search",
                    params={"query": f"tx.hash='{h.hex().upper()}'"},
                    req_id=1,
                )
            )
            assert resp["total_count"] == 1
            assert resp["txs"][0]["hash"] == h.hex()
        finally:
            await node.stop()

    run(go())
