"""Host time a request's gathers spend after the device finished: the
self time of the `gather_fetch` spans (the bitmap's copy to the host,
which assembles a mesh's shards, and the size mask) and of the
`gather_job` spans around them (the bitmap made Python bools, the
fault plane's unarmed hook)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "gather_fetch", "gather_job")
