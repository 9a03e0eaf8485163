"""Host time a request spends encoding the votes' sign-bytes: the self
time of the program's `sign_bytes` span (`Commit.sign_bytes_batch`, or
the light path's one pass over the checked prefix)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "sign_bytes")
