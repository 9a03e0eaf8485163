"""Node configuration.

Mirrors the reference's master Config struct and sections (reference:
config/config.go:61-74 — Base, RPC, P2P, Mempool, StateSync, Consensus,
TxIndex, Instrumentation, PrivValidator) with TOML persistence via stdlib
tomllib for reads and a template writer for `init`.

Consensus timeouts follow config/config.go:923-939 (propose/prevote/
precommit + deltas, timeout-commit).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Optional

__all__ = [
    "Config",
    "BaseConfig",
    "RPCConfig",
    "P2PConfig",
    "MempoolConfig",
    "StateSyncConfig",
    "BlockSyncConfig",
    "ConsensusConfig",
    "TxIndexConfig",
    "InstrumentationConfig",
    "PrivValidatorConfig",
    "TPUConfig",
    "load_config",
    "write_config",
]

MODE_VALIDATOR = "validator"
MODE_FULL = "full"
MODE_SEED = "seed"

# Canonical device-batch bucket sizes: the single source both curves'
# verifiers follow (ops.verifier re-exports this as
# DEFAULT_BUCKET_SIZES; config.py owns it because it must stay
# importable without jax).
# 12288 exists for the 10k-validator commit config (BASELINE 5): padding
# 10k sigs to 16384 wastes 39% of the device program; 12288 = 96 * 128
# stays a multiple of the 128-lane vector tile and cuts that to 18%.
DEFAULT_BUCKET_SIZES = (8, 32, 128, 512, 2048, 8192, 12288, 16384)

# The narrowest bucket whose sr25519 merlin challenges a device program
# makes (ops/sr25519_kernel.py). On a TPU v5e host the program's rows
# and launch cost the host about as much as 128 transcripts, and half of
# what 512 do; narrower launches (singles, the install's probe, small
# sets) keep the host's transcripts and compile no program a message
# length. Here for the same reason: tmtrace's shape model reads it
# without importing jax.
MERLIN_DEVICE_LANES = 512


def bucket_for(n: int, sizes) -> int:
    """Smallest configured bucket >= n (`sizes` ascending), or n itself
    when oversized. Beside the table for the same reason: the seam's
    telemetry asks it without importing jax."""
    for b in sizes:
        if n <= b:
            return b
    return n


@dataclass
class BaseConfig:
    chain_id: str = ""
    moniker: str = "anonymous"
    mode: str = MODE_VALIDATOR
    home: str = "~/.tendermint_tpu"
    # sqlite | memdb. A deliberate cut from the reference's five
    # backends (config.go:179-197 goleveldb/cleveldb/boltdb/rocksdb/
    # badgerdb, all ordered KV stores behind tm-db): sqlite is the
    # embedded on-disk default (store/kv.py SqliteKV implements the
    # same ordered-KV contract), memdb serves tests/ephemeral nodes.
    # Another engine is one KVStore subclass away — register it with
    # store.kv.register_backend(name, factory) before node start and
    # set this knob to that name; nothing above store/kv.py knows
    # which engine is underneath ("goleveldb"/"default" alias to
    # sqlite so reference config.toml files work unchanged).
    db_backend: str = "sqlite"  # sqlite | memdb | registered name
    db_dir: str = "data"
    log_level: str = "info"
    log_format: str = "plain"
    genesis_file: str = "config/genesis.json"
    node_key_file: str = "config/node_key.json"
    abci: str = "builtin"  # builtin | socket | grpc
    proxy_app: str = "kvstore"

    def root(self) -> str:
        return os.path.expanduser(self.home)

    def path(self, rel: str) -> str:
        return os.path.join(self.root(), rel)


@dataclass
class PrivValidatorConfig:
    key_file: str = "config/priv_validator_key.json"
    state_file: str = "data/priv_validator_state.json"
    listen_addr: str = ""  # non-empty => remote signer


@dataclass
class RPCConfig:
    laddr: str = "tcp://127.0.0.1:26657"
    max_open_connections: int = 900
    max_subscription_clients: int = 100
    max_subscriptions_per_client: int = 5
    timeout_broadcast_tx_commit: float = 10.0
    max_body_bytes: int = 1_000_000
    # per-block serving cache (rpc/servingcache.py): LRU capacity in
    # blocks for each artifact family (encoded LightBlock blobs, held
    # tx-proof merkle trees); 0 disables the cache for this node
    serving_cache_blocks: int = 64


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    persistent_peers: str = ""
    bootstrap_peers: str = ""
    max_connections: int = 64
    max_incoming_connection_attempts: int = 100
    send_rate: int = 5_120_000
    recv_rate: int = 5_120_000
    pex: bool = True
    handshake_timeout: float = 20.0
    dial_timeout: float = 3.0
    queue_type: str = "priority"  # fifo | priority
    # jittered capped exponential dial backoff (peermanager)
    min_retry_time: float = 0.25
    max_retry_time: float = 600.0
    max_retry_time_persistent: float = 20.0
    # keepalive liveness (router; any received traffic counts)
    ping_interval: float = 30.0
    pong_timeout: float = 15.0
    # slow-peer shedding: this many send-queue drops inside the window
    # evicts the peer with reason slow_peer and bans it for the sit-out
    slow_peer_drop_threshold: int = 64
    slow_peer_window: float = 10.0
    slow_peer_ban: float = 30.0


@dataclass
class MempoolConfig:
    recheck: bool = True
    broadcast: bool = True
    size: int = 5000
    max_txs_bytes: int = 1 << 30
    cache_size: int = 10000
    keep_invalid_txs_in_cache: bool = False
    max_tx_bytes: int = 1 << 20
    ttl_duration: float = 0.0  # seconds; 0 = no TTL
    ttl_num_blocks: int = 0
    # Admission shards: CheckTx takes only its tx-key-hashed shard's
    # lock, so concurrent admissions on different shards overlap their
    # app round-trips instead of convoying behind one pool-wide lock.
    # Consensus's lock() is an epoch barrier across every shard, so the
    # Commit+Update exclusion is unchanged. 1 = the pre-shard layout.
    shards: int = 8
    # Max txs bundled into one gossip envelope / one batched admission
    # call (broadcast_tx ingestion and post-commit recheck reuse it as
    # the ABCI pipelining grain).
    tx_batch_size: int = 64


@dataclass
class StateSyncConfig:
    enable: bool = False
    rpc_servers: list[str] = field(default_factory=list)
    trust_height: int = 0
    trust_hash: str = ""
    trust_period: float = 168 * 3600.0
    discovery_time: float = 15.0
    chunk_request_timeout: float = 15.0
    fetchers: int = 4


@dataclass
class BlockSyncConfig:
    enable: bool = True


@dataclass
class ConsensusConfig:
    wal_file: str = "data/cs.wal/wal"
    # Reference defaults, config/config.go:923-939 (milliseconds there).
    timeout_propose: float = 3.0
    timeout_propose_delta: float = 0.5
    timeout_prevote: float = 1.0
    timeout_prevote_delta: float = 0.5
    timeout_precommit: float = 1.0
    timeout_precommit_delta: float = 0.5
    timeout_commit: float = 1.0
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval: float = 0.0
    peer_gossip_sleep_duration: float = 0.1
    # Block-part gossip window: how many missing parts one data-gossip
    # iteration may burst to a peer before sleeping. Sends beyond the
    # first use try_send, so a slow peer's full send queue sheds the
    # rest of the window (backpressure) instead of stalling the routine.
    peer_gossip_part_window: int = 16
    peer_query_maj23_sleep_duration: float = 2.0
    double_sign_check_height: int = 0

    def propose_timeout(self, round_: int) -> float:
        return self.timeout_propose + self.timeout_propose_delta * round_

    def prevote_timeout(self, round_: int) -> float:
        return self.timeout_prevote + self.timeout_prevote_delta * round_

    def precommit_timeout(self, round_: int) -> float:
        return self.timeout_precommit + self.timeout_precommit_delta * round_


@dataclass
class TxIndexConfig:
    # kv | null | psql (reference: config/config.go TxIndexConfig +
    # the psql sink under internal/state/indexer/sink/psql)
    indexer: list[str] = field(default_factory=lambda: ["kv"])
    # DSN for the "psql" sink: sqlite:<path>, sqlite::memory:, or
    # postgres://... (needs psycopg). Empty = sqlite file in the data dir.
    psql_conn: str = ""


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    namespace: str = "tendermint_tpu"
    # span tracing (libs/trace.py): record the commit-verification
    # pipeline into the in-memory ring, exportable as Chrome-trace JSON
    # via the debug bundle. Off by default — the disabled path is a
    # no-op. Process-wide switch (the ring is shared).
    trace_spans: bool = False
    trace_ring_capacity: int = 8192
    # slow-request exemplars (libs/trace.py): requests exceeding their
    # per-route SLO (rpc/metrics.py slo_for) capture their span tree
    # into a second bounded ring, exported in the debug bundle as
    # slow_requests.json. Off by default; process-wide like the ring.
    slo_exemplars: bool = False
    slo_exemplar_capacity: int = 64
    # consensus flight recorder (consensus/timeline.py): bounded
    # per-node ring of height/round events (step transitions,
    # threshold crossings, timeouts, gossip stall-resets), served by
    # the consensus_timeline RPC route and the debug bundle. ON by
    # default — like the WAL it earns its keep post-mortem; the
    # disabled path is one attribute check per step transition.
    consensus_timeline: bool = True
    consensus_timeline_capacity: int = 4096
    # wall-clock sampling profiler (libs/profiler.py): daemon sampler
    # over sys._current_frames() with subsystem + asyncio-task
    # attribution, served by the `profile` RPC route, the debug
    # bundle (profile.json) and the tmload bottleneck ledger. Off by
    # default — sampling costs ~1-3% wall at the default 97 Hz;
    # task-label *arming* (profiler_labels) is on so a profile
    # started mid-run over RPC still sees long-lived pumps' origins
    # (one attribute write per task spawn).
    profiler: bool = False
    profiler_hz: float = 97.0
    profiler_max_stacks: int = 2048
    profiler_labels: bool = True


@dataclass
class TPUConfig:
    """Device-offload knobs — no analog in the reference; this gates the
    TPU-backed BatchVerifier and merkle kernels (the north-star seam,
    reference: crypto/crypto.go:53-61)."""

    enable: bool = True
    min_batch_size: int = 8  # below this, CPU single-verify wins
    bucket_sizes: list[int] = field(
        default_factory=lambda: list(DEFAULT_BUCKET_SIZES)
    )
    donate_buffers: bool = True
    # devices > 1 shards signature batches over a data-parallel
    # jax.sharding.Mesh of that many devices (tendermint_tpu.parallel);
    # 0 = every visible device, 1 = single chip (no mesh)
    devices: int = 1


@dataclass
class Config:
    base: BaseConfig = field(default_factory=BaseConfig)
    priv_validator: PrivValidatorConfig = field(default_factory=PrivValidatorConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    blocksync: BlockSyncConfig = field(default_factory=BlockSyncConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(
        default_factory=InstrumentationConfig
    )
    tpu: TPUConfig = field(default_factory=TPUConfig)

    def ensure_dirs(self) -> None:
        root = self.base.root()
        for sub in ("config", "data", os.path.dirname(self.consensus.wal_file)):
            os.makedirs(os.path.join(root, sub), exist_ok=True)


_SECTIONS = {
    "base": BaseConfig,
    "priv_validator": PrivValidatorConfig,
    "rpc": RPCConfig,
    "p2p": P2PConfig,
    "mempool": MempoolConfig,
    "statesync": StateSyncConfig,
    "blocksync": BlockSyncConfig,
    "consensus": ConsensusConfig,
    "tx_index": TxIndexConfig,
    "instrumentation": InstrumentationConfig,
    "tpu": TPUConfig,
}


def _parse_toml_value(val: str):
    """One scalar/list/inline-table value of the supported subset.
    Raises ValueError on anything else."""
    import ast

    if val == "true":
        return True
    if val == "false":
        return False
    if val.startswith("{") and val.endswith("}"):
        # inline table of scalars (e2e manifests: {double-prevote = 3})
        out = {}
        inner = val[1:-1].strip()
        if inner:
            for pair in inner.split(","):
                k, eq, v = pair.partition("=")
                if not eq:
                    raise ValueError(f"bad inline table entry: {pair!r}")
                out[k.strip()] = _parse_toml_value(v.strip())
        return out
    try:
        # numbers, quoted strings (same escapes our writers emit),
        # and flat lists thereof
        return ast.literal_eval(val)
    except (ValueError, SyntaxError) as e:
        raise ValueError(f"unsupported TOML value: {val!r}") from e


def _parse_toml_subset(text: str) -> dict:
    """Fallback parser for the TOML subset our own writers emit
    (write_config, e2e manifests: sections incl. dotted names;
    bool/number/string/flat-list/inline-table values) — Python < 3.11
    ships no tomllib, and the container may not carry tomli."""
    raw: dict = {}
    cur: dict = raw  # keys before any [section] are document-root keys
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("#"):
            continue
        if '"' not in line:
            # trailing comments are only safe to strip when no string
            # value could contain the '#'
            line = line.split("#", 1)[0].strip()
        if not line:
            continue
        if line.startswith("[") and line.endswith("]"):
            cur = raw
            for part in line[1:-1].strip().split("."):
                cur = cur.setdefault(part.strip(), {})
            continue
        if "=" not in line:
            # tomllib rejects junk lines; silently skipping would let a
            # typo'd setting fall back to its default with no error
            raise ValueError(f"unparseable TOML line: {line!r}")
        key, _, val = line.partition("=")
        cur[key.strip()] = _parse_toml_value(val.strip())
    return raw


def load_config(path: str) -> Config:
    try:
        import tomllib
    except ImportError:
        tomllib = None

    if tomllib is not None:
        with open(path, "rb") as f:
            raw = tomllib.load(f)
    else:
        with open(path, encoding="utf-8") as f:
            raw = _parse_toml_subset(f.read())
    cfg = Config()
    for section, cls in _SECTIONS.items():
        data = raw.get(section, {})
        known = {f.name for f in dataclasses.fields(cls)}
        setattr(
            cfg, section, cls(**{k: v for k, v in data.items() if k in known})
        )
    return cfg


def _toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return str(v)
    if isinstance(v, str):
        return '"' + v.replace("\\", "\\\\").replace('"', '\\"') + '"'
    if isinstance(v, list):
        return "[" + ", ".join(_toml_value(x) for x in v) + "]"
    raise TypeError(f"unsupported TOML value: {v!r}")


def write_config(cfg: Config, path: str) -> None:
    lines = ["# tendermint-tpu node configuration", ""]
    for section in _SECTIONS:
        obj = getattr(cfg, section)
        lines.append(f"[{section}]")
        for f in dataclasses.fields(obj):
            lines.append(f"{f.name} = {_toml_value(getattr(obj, f.name))}")
        lines.append("")
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as fh:
        fh.write("\n".join(lines))
