"""Host time a request's sync pipeline spent with nothing to verify:
the self time of the program's `blocksync_wait` spans (the reactor's
pool routine asleep until the pool holds two blocks). With the pool
waking it on arrival this is the time the blocks took to come, not a
timer's remainder; the wait left open at a request's end runs into the
next."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "blocksync_wait")
