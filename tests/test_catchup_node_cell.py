"""The benchmark's node cell, `commit-150.catchup-node`, rehearsed on
the CPU backend at `chipbench/tests/tiny/blocksync_node.json`'s sizes:
its data against the program's own hashes, decoder and kvstore, the
plain reference against a real `Node` on clean and corrupted requests,
what a request must add to the program's counters, the cell end to end
through `chipbench/run.run_cell` with the look for a chip replaced in
the test (never in run.py), the control and two planted faults, which
must not come out correct, and the readers of the `blocksync` layer's
metrics on spans made by hand.

Nothing these tests print is a speed.
"""

from __future__ import annotations

import json
import os
import types

import pytest

from chipbench import node_faults, node_gen
from chipbench import run as harness
from chipbench.reference import commit_verify as R
from chipbench.reference import light_verify as L
from chipbench.reference import node_replay as N
from chipbench.tests.rehearsal import MANIFEST, args, rehearsing, tiny_of

CELL = "commit-150.catchup-node"
LAYER = "blocksync (blocksync/reactor.py, state/execution.py, store/)"
NODE_METRICS = (
    "blocksync_verify_host_ms", "block_validate_host_ms", "block_apply_host_ms",
    "block_store_host_ms", "blocksync_wait_host_ms", "blocks_per_dispatch",
)  # fmt: skip
SEED = 2_147_483_659
TINY = tiny_of("blocksync_node")


def _files() -> tuple:
    return (
        harness.load_json(os.path.join(harness.HERE, "configs", "commit-150-node.json")),
        harness.load_json(os.path.join(harness.HERE, "traffic", "catchup-node.json")),
    )


def _cut() -> tuple:
    config, traffic = _files()
    return {**config, **TINY["config"]}, {**traffic, **TINY["traffic"]}


@pytest.fixture
def tiny(monkeypatch):
    """The cell as the manifest has it, cut so that a CPU holds it."""
    with rehearsing(monkeypatch):
        yield


# -- the manifest -------------------------------------------------------


def test_the_manifest_has_the_cell_its_configuration_and_its_readers():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="commit-150-node", traffic="catchup-node", chips=1)
    assert len(cell["why"]) <= 200 and "make_node" in cell["why"]
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "commit-150-node"]
    config, traffic = _files()
    assert entry["file"] == "chipbench/configs/commit-150-node.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == []
    assert config["reference"] == "node_replay" and config["validators"] == 150
    assert (config["peers"], config["txs_per_block"], config["tx_bytes"], config["key_ring"]) == (4, 8, 256, 64)
    assert len(config["chain_id"]) == 13 and len(config["guarantees"]) == 4
    assert config["db_backend"] == "memdb" and len(config["assumed"]) >= 6
    assert traffic["driver"] == "blocksync_node" and traffic["trace_requests"] == 2
    assert (traffic["blocks_per_request"], traffic["chain_blocks"], traffic["corrupt_every"]) == (8, 513, 64)
    assert traffic["corrupted_in"] == [4, 60] and traffic["warmup_blocks"] == 17
    assert "(16, 1200)" in traffic["why"] and "(908, 0)" in traffic["why"] and traffic["who"]
    new = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == list(NODE_METRICS)
    at = MANIFEST["per_layer"].index(new[0])
    assert MANIFEST["per_layer"][at : at + len(new)] == new  # added as one block, at the end
    # of the 40 metrics PR 35 found. The lists only grow at their ends (the driver reads an entry put
    # ahead of accepted ones as an edit of them), so the block stays at 40 and later PRs' follow it
    assert at == 40 and at + len(new) <= len(MANIFEST["per_layer"])
    for m in new:
        assert m["moves"] == "commits_per_s" and m["layer"] == LAYER
        assert os.path.exists(os.path.join(harness.HERE, "layer_metrics", m["name"] + ".py"))
    # the lap stays cold: what is inserted between two visits of a
    # height passes the cache's two generations
    from tendermint_tpu.crypto import sigcache

    lap = (traffic["chain_blocks"] - 1) * config["validators"]
    assert lap == 76_800 > 2 * sigcache.DEFAULT_CAPACITY
    assert traffic["corrupt_every"] * traffic["blocks_per_request"] + 1 == traffic["chain_blocks"]


# -- the data -----------------------------------------------------------


@pytest.fixture(scope="module")
def chain():
    config, _traffic = _cut()
    deployment = node_gen.Deployment(config, SEED)
    return node_gen.Chain(deployment, config, SEED, "window", 10)


def test_the_generators_hashes_and_wire_bytes_are_the_programs(chain):
    from tendermint_tpu.blocksync.msgs import BlocksyncCodec
    from tendermint_tpu.types.params import ConsensusParams

    assert chain.d.checked == 17 and chain.sign_bytes_len == 115
    assert ConsensusParams().hash() == chain.d.consensus_hash
    before = None
    for block in chain.blocks:
        message = BlocksyncCodec.decode(block["response"])
        got = message.block
        assert BlocksyncCodec.encode(message) == block["response"]
        assert got.to_proto() == block["wire"]
        assert got.hash() == block["header"]["hash"] == N.header_hash(block["header"])
        got.validate_basic()  # its own data, commit and evidence hashes
        parts = got.make_part_set().header()
        assert (got.hash(), parts.total, parts.hash) == block["block_id"]
        decoded = N.decode_block(N.decode_response(block["response"]))
        assert dict(decoded["header"], hash=block["header"]["hash"]) == block["header"]
        assert decoded["txs"] == block["txs"] and all(len(tx) == 256 for tx in block["txs"])
        if before is None:
            assert block["last_commit"] is None and decoded["last_commit"]["votes"] == []
        else:
            assert decoded["last_commit"] == block["last_commit"]
            assert got.header.last_block_id.hash == before["header"]["hash"]
            assert block["last_commit"]["block_hash"] == before["header"]["hash"]
        before = block
    # a corrupted variant differs from its block in one bit, in a vote
    # the light tally checks
    bad = chain.corrupted_response(5, 16)
    diff = [a ^ b for a, b in zip(bad, chain.response(5))]
    assert len(bad) == len(chain.response(5)) and sum(bin(d).count("1") for d in diff) == 1
    # one key ring: the state stops growing
    keys = {tx.partition(b"=")[0] for block in chain.blocks for tx in block["txs"]}
    assert len(keys) == 64


def test_the_references_kvstore_and_hash_rules_are_the_programs(chain):
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.state.execution import results_hash
    from tendermint_tpu.state.types import median_time
    from tendermint_tpu.types.commit import Commit
    from tendermint_tpu.types.validator import Validator, ValidatorSet
    from tendermint_tpu.crypto.keys import pubkey_from_type_and_bytes

    validators = chain.d.validators
    app, plain = KVStoreApplication(), N.KVStore(validators)
    app.init_chain(
        abci.RequestInitChain(
            validators=tuple(
                abci.ValidatorUpdate(pub_key=abci.PubKey("ed25519", v["pub"]), power=v["power"])
                for v in validators
            )
        )
    )
    assert app._compute_app_hash() == plain.app_hash() == chain.blocks[0]["header"]["app_hash"]
    for block, after in zip(chain.blocks, chain.blocks[1:]):
        results = [app.deliver_tx(abci.RequestDeliverTx(tx=tx)) for tx in block["txs"]]
        for tx in block["txs"]:
            plain.deliver(tx)
        assert app.commit().data == plain.app_hash() == after["header"]["app_hash"]
        assert results_hash(results) == N.results_hash(8) == after["header"]["last_results_hash"]
    vals = ValidatorSet(
        [
            Validator(pub_key=pubkey_from_type_and_bytes(v["kind"], v["pub"]), voting_power=v["power"])
            for v in validators
        ]
    )
    commit = chain.blocks[3]["last_commit"]
    assert median_time(Commit.from_proto(node_gen.gen.encode_commit(commit)), vals) == (
        N.median_time(commit, validators)
    ) == chain.blocks[3]["header"]["time_ns"]
    assert N.consensus_hash(22020096, -1) == chain.d.consensus_hash
    assert N.parts_header(b"x" * 70_000) == (2, L.merkle_root([b"x" * 65536, b"x" * 4464]))


def test_the_reference_names_other_faults_than_signatures(chain):
    def replay(blocks, **constants):
        run = N.Replay(dict(chain.d.reference(), **constants))
        for block in blocks:
            run.serve("peer0", block["response"])
        return run.verdict()

    clean = chain.blocks[:5]
    tip = clean[3]["header"]
    assert replay(clean) == (
        f"ok:4:{tip['hash'].hex()}:{chain.blocks[4]['header']['app_hash'].hex()};stored=1-4"
    )
    assert replay(clean[:1]) == "ok:0::" + N.KVStore(chain.d.validators).app_hash().hex() + ";stored="
    # a block of another chain's state: its app hash is not the state's
    assert replay(clean, version_app=7) == "invalid:1:version;stored="
    swapped = clean[:2] + [dict(clean[2], response=node_gen.encode_response(
        node_gen.encode_block(dict(clean[2]["header"], app_hash=b"\x01" * 32), clean[2]["txs"],
                              clean[2]["last_commit"])))] + clean[3:]  # fmt: skip
    # its hash is no longer what the next block's commit signs
    assert replay(swapped) == "invalid:3:commit_for_another_block;stored=1-2"
    gap = clean[:2] + clean[3:]
    assert replay(gap).startswith("ok:1:") and replay(gap).endswith(";stored=1-1")
    # the control: a flipped vote goes through the light check, and
    # the block that carries it is then not the one its own commit
    # signs (the part set is of the bytes served)
    bad = [("peer0", b["response"]) for b in clean[:3]] + [("peer1", chain.corrupted_response(4, 3))]
    bad.append(("peer2", chain.response(5)))
    truth, control = N.Replay(chain.d.reference()), N.Replay(chain.d.reference(), check_signatures=False)
    for peer, response in bad:
        truth.serve(peer, response)
        control.serve(peer, response)
    assert truth.verdict().endswith(";refused=3#3;banned=peer0,peer1;stored=1-2")
    assert control.verdict() == "invalid:4:commit_for_another_block;stored=1-3"


# -- the reference and the program --------------------------------------


def test_the_reference_and_the_node_agree_and_the_counters_move_as_reckoned(tiny):
    cell = harness.load_cell(CELL)
    driver = harness.load_module("drivers", "blocksync_node").setup(cell.config, cell.traffic, SEED)
    try:
        installed = harness.install_device_path(cell.config)
        read = harness.make_counter_reader()
        warm = driver.warmup_requests()
        # the first touch (a clean request on a node and a chain of its
        # own), then the warm-up's clean and corrupted ones
        assert [t[1] for t in warm] == [False, False, True]
        assert [driver._place(t)[0] for t in warm] == [
            driver._first_touch_net, driver._warm_net, driver._warm_net
        ]  # fmt: skip
        w = len(warm)
        tokens = warm + [driver.window_request(i) for i in range(6)]
        assert [t[1] for t in tokens].count(True) == 1 + 1
        got, moved = [], []
        for token in tokens:
            before = read()
            got.append(driver.run(token))
            moved.append(dict(zip(harness.COUNTERS, (b - a for a, b in zip(before, read())))))
        # the warm-up's corrupted request is compared too (run.py's
        # warmup_verdict_mismatches), so no height may have reached its
        # node twice before the refusal: the first touch took the first
        # dispatch's stall and the fetches that time out over it
        served = [
            N.decode_block(N.decode_response(wire))["header"]["height"]
            for _peer, wire in driver._served[warm[-1]]
        ]
        until_refused = served[: served.index(driver._warm_net.laps.bad_height) + 1]
        assert len(until_refused) == len(set(until_refused)), served
        assert got == driver.expected(tokens)
        for token, delta in zip(tokens, moved):
            sent = driver.sent(token, installed["min_batch"], installed["chunk"])
            assert (delta["batches"], delta["sigs"]) == sent, token
            assert (delta["cache_hits"], delta["memo_hits"]) == driver.hits(token), token
            assert delta["faults"] == 0
        # two blocks a request: a light verification of 17 each, a full
        # one of the other 8 each but for the chain's first block
        assert [driver.sent(t, 8, None) for t in tokens[w : w + 3]] == [(3, 42), (4, 50), (4, 50)]
        assert [driver.hits(t) for t in tokens[w : w + 3]] == [(17, 0), (34, 0), (34, 0)]
        bad = next(t for t in tokens[w:] if t[1])
        # a refused commit costs no dispatch more: 16 of its 17 votes are found again
        assert driver.sent(bad, 8, None) == (4, 50) and driver.hits(bad) == (50, 0)
        assert driver.sent(bad, 64, None) == (0, 0)
        laps = driver._window_net.laps
        verdict = got[tokens.index(bad)]
        assert f";refused={laps.refused_height}#{laps.bad_index};banned=peer" in verdict
        assert verdict.endswith(f";stored=1-{laps.target(laps.position)}")
        assert got[w].startswith("ok:2:") and got[w].endswith(";stored=1-2") and "refused" not in got[w]
        # the next lap's first request is a fresh node at height 2 again
        assert got[w + 4] == got[w]
        # the first touch and the warm-up's first are two chains' second heights
        assert got[0].startswith("ok:2:") and got[1].startswith("ok:2:") and got[0] != got[1]
        assert len(driver.decode_s) == len(tokens) and min(driver.decode_s) > 0
        per_signature = types.SimpleNamespace(per_signature=lambda kind, n: {"madds": 7, "bytes": n})
        assert driver.work(tokens[w + 1], per_signature) == {"madds": 7 * 50, "bytes": 115 * 50}
        # the node is the program's own, and its second install changed nothing
        from tendermint_tpu.crypto import tpu_verifier
        from tendermint_tpu.node import Node

        assert type(driver._node) is Node and driver._node.cfg.tpu.enable
        assert driver._node.cfg.blocksync.enable and driver._node.blocksync_reactor.block_sync
        # first touches in the first request alone: a Node's own install,
        # one a lap, left every bucket warm
        assert moved[0]["warm_misses"] <= 2 and not any(d["warm_misses"] for d in moved[1:])
        assert tpu_verifier.installed() == installed["min_batch"]
    finally:
        driver.close()


# -- the cell, end to end -----------------------------------------------


def test_the_cell_rehearses_correct(tiny):
    result = harness.run_cell(args(CELL, seconds=2.5))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["checks"]["corrupted_requests_min"]["value"] >= 1
    assert result["checks"]["bypassed_requests"]["value"] == 0
    assert set(result["metrics"]) == {"commits_per_s", "verify_p95_ms", "setup_s"}
    assert list(result)[-1] == "checks"
    json.dumps(result)


def test_the_control_fails_the_cells_comparison(tiny):
    result = harness.run_cell(args(CELL, seconds=1.0), prepare=lambda d: d.use_control())
    assert not result["correct"]
    assert (
        result["checks"]["verdict_mismatches"]["value"]
        == result["checks"]["corrupted_requests_min"]["value"]
        >= 1
    )


@pytest.mark.parametrize(
    "fault, check",
    [("light-skipped", "verdict_mismatches"), ("cache-blind", "bypassed_requests")],
)
def test_a_broken_timed_path_is_not_correct(tiny, fault, check, monkeypatch):
    plant = node_faults.FAULTS[fault]
    result = harness.run_cell(
        args(CELL, seconds=2.5), prepare=lambda d: plant(d, put=monkeypatch.setattr)
    )
    assert not result["correct"]
    assert result["checks"][check]["value"] > 0 and result["failed"] > 0
    # every request's full verifications found nothing in the cache
    assert result["checks"]["bypassed_requests"]["value"] >= result["attempted"] - 1


def test_a_traced_rehearsal_reports_the_node_paths_metrics(tiny):
    result = harness.run_cell(args(CELL, trace=1, seconds=2.5))
    assert result["correct"], result["checks"]
    got = result["metrics"]
    wanted = {m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(got) <= wanted and set(NODE_METRICS) <= set(got)
    assert got["blocks_per_dispatch"] == {"value": 1.0, "unit": "count"}
    for name in NODE_METRICS[:4] + ("decode_host_ms", "validation_host_ms", "dispatch_host_prep_ms",
                                    "gather_wait_ms", "signbytes_host_ms", "commit_plan_host_ms"):
        assert got[name]["value"] > 0, name
    assert got["blocksync_wait_host_ms"]["value"] >= 0
    # a light verification and the full one of the same commit a
    # block: 17 of 17 + 25 probes hit, less the chain's first block
    assert 33 < got["sigcache_hit_share"]["value"] < 100 * 17 / 42 + 1
    assert got["window_compiles"]["value"] == 0
    assert got["decode_native_share"] == {"value": 100.0, "unit": "%"}
    # two dispatches a block, a tile and its SHA-512 each, one fewer
    # for the chain's first block
    lo, hi = TINY["device_launches"]
    assert lo <= got["device_launches"]["value"] <= hi
    # 17 in a 32-lane bucket and 8 in an 8-lane one: 30 lanes of 80 a
    # request, of 72 in a lap's first
    assert 100 * 30 / 80 <= got["pad_waste_share"]["value"] <= 100 * 30 / 72
    # every accepted metric that lists no cells has to be in a traced
    # line of this cell too; a CPU rehearsal lacks only the device's,
    # and the set-up's compiles where the process had the programs
    unlisted = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    off_chip = {"sigverify_roofline", "verify_mfu", "kernel_device_ms", "device_idle_share",
                "ladder_device_ms", "decode_points_device_ms", "setup_cache_load_s",
                "gather_device_idle_ms"}  # fmt: skip
    assert unlisted - off_chip - set(got) == set()
    # the gather's split adds up to its wait, less collections in the job
    split = [got[m]["value"] for m in ("gather_handoff_ms", "gather_ready_ms", "gather_fetch_ms")]
    assert min(split) >= 0 and sum(split) <= got["gather_wait_ms"]["value"] * (1 + 1e-9)
    assert all(isinstance(m["value"], (int, float)) for m in got.values())
    json.dumps(result)


# -- the readers, on spans made by hand ---------------------------------


def _reader(metric: str):
    return harness.load_module("layer_metrics", metric).read


def _ctx(spans=(), requests=1):
    return types.SimpleNamespace(spans=list(spans), requests=requests)


def _span(sid, name, start, dur, parent=0, **attrs):
    return types.SimpleNamespace(
        span_id=sid, name=name, start_us=float(start), dur_us=float(dur),
        parent_id=parent, root_id=1, attrs=attrs,
    )  # fmt: skip


def _two_blocks(commits=(1, 1)):
    """Two blocks through the sync pipeline as the program's spans draw
    them: the wait, the part set, the light verification, the store,
    then the executor's phases, each a root of its own task."""
    spans = []
    for i, at in enumerate((0, 10_000)):
        n = 10 * i
        spans += [
            _span(n + 1, "blocksync_wait", at, 400),
            _span(n + 9, "gc_collect", at + 100, 100, parent=n + 1, generation=0),
            _span(n + 2, "block_parts", at + 400, 300, height=i + 1),
            _span(n + 3, "blocksync_verify", at + 700, 4000, height=i + 1, commits=commits[i]),
            _span(n + 4, "block_store_save", at + 4700, 500, height=i + 1),
            _span(n + 6, "validate_block", at + 5200, 3000, parent=n + 5),
            _span(n + 7, "exec_block", at + 8200, 600, parent=n + 5),
            _span(n + 8, "abci_commit", at + 8800, 200, parent=n + 5),
            _span(n + 5, "block_execute", at + 5200, 4500, height=i + 1, txs=8),
        ]
    return spans


def test_the_readers_on_two_blocks_built_by_hand():
    spans = _two_blocks()
    assert _reader("blocksync_verify_host_ms")(_ctx(spans)) == pytest.approx(8.0)
    assert _reader("blocksync_verify_host_ms")(_ctx(spans, requests=2)) == pytest.approx(4.0)
    assert _reader("block_validate_host_ms")(_ctx(spans)) == pytest.approx(6.0)
    # 4500 - 3000, twice
    assert _reader("block_apply_host_ms")(_ctx(spans)) == pytest.approx(3.0)
    # 300 + 500, twice
    assert _reader("block_store_host_ms")(_ctx(spans)) == pytest.approx(1.6)
    # 400 less the collection inside it, twice
    assert _reader("blocksync_wait_host_ms")(_ctx(spans)) == pytest.approx(0.6)
    assert _reader("blocks_per_dispatch")(_ctx(spans)) == 1.0
    assert _reader("blocks_per_dispatch")(_ctx(_two_blocks(commits=(4, 2)))) == 3.0


@pytest.mark.parametrize("metric", NODE_METRICS)
def test_a_program_without_the_spans_has_nothing_to_read(metric):
    """A parent commit syncs the same blocks and opens `block_execute`
    alone: the reader returns nothing and does not raise, and the line
    leaves the metric out."""
    others = [s for s in _two_blocks() if s.name in ("block_execute", "gc_collect")]
    assert _reader(metric)(_ctx(others)) is None
    assert _reader(metric)(_ctx([], requests=0)) is None
