"""Host time a request spends validating blocks before they are
executed: the durations of the program's `validate_block` spans
(state/execution.py: the header held to the state, then the block's own
LastCommit through `verify_commit` over the whole set, two thirds of it
found in the cache, its dispatch and gather included)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "validate_block", self_time=False)
