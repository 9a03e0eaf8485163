"""Host time a request spends on the block store: the durations of the
program's `block_parts` spans (the block encoded again and cut into its
part set) and `block_store_save` spans (blocksync/reactor.py: the
meta, the parts, the commit and the seen commit written in one batch)."""

from chipbench import span_tree


def read(ctx):
    if not span_tree.of(ctx).named("block_store_save"):
        return None
    return span_tree.ms_a_request(ctx, "block_parts", "block_store_save", self_time=False)
