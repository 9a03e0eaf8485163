"""Host time a request's gathers wait for the device to finish its
program: the self time of the `gather_ready` spans (`block_until_ready`
on the bitmap, ops/verifier.py). It holds the program's own run and any
time the program had yet to start; `gather_device_idle_ms` is the part
of it in which the device ran nothing."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "gather_ready")
