"""Host time a request spends routing the cache's misses to their key
class's batch: the self time of `batch_route` (one pass over the
misses; a key type that cannot batch is verified in place there)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "batch_route")
