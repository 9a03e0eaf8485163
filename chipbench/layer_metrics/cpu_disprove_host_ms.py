"""Host time a request spends cross-examining on the CPU the lanes the
device called invalid: the self time of `cpu_disprove`, over all the
window's requests (only a corrupted request has such a lane)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "cpu_disprove")
