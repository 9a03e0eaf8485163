"""Host time a request spends in block sync's own verification of a
block: the durations of the program's `blocksync_verify` spans
(blocksync/reactor.py `_verify_apply`: the block's BlockID from its
header hash and part-set header, then `verify_commit_light` over the
commit the next block carries, its dispatch and gather included)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "blocksync_verify", self_time=False)
