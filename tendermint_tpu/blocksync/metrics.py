"""Block sync metrics struct (reference: internal/blocksync has none;
the names follow internal/consensus/metrics.go's BlockSyncing family),
per-node when threaded from node assembly — see consensus/metrics.py
for the pattern.
"""

from __future__ import annotations

from typing import Optional

from ..libs.metrics import DEFAULT_REGISTRY, Registry

__all__ = ["BlocksyncMetrics"]


class BlocksyncMetrics:
    def __init__(self, registry: Optional[Registry] = None) -> None:
        r = registry if registry is not None else DEFAULT_REGISTRY
        self.blocks_applied = r.counter(
            "blocksync",
            "blocks_applied",
            "Blocks verified, stored and executed by the sync pipeline.",
        )
        self.decode_seconds = r.counter(
            "blocksync",
            "decode_seconds",
            "Seconds spent decoding inbound block sync messages.",
        )
        self.redo_requests = r.counter(
            "blocksync",
            "redo_requests",
            "Blocks refused by the commit that follows them: both "
            "providers banned, the height and all above fetched again.",
        )
