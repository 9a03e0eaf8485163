"""Host time a request spends launching device programs: the self time
of the `device_launch` spans, one a program call (the transfers of its
arguments and the enqueue of the jitted call)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "device_launch")
