"""Host time a request spends packing rows for the device: the self
time of the `pack_rows` spans in the kernels' `dispatch()` (size check,
byte joins, the SHA-512 pre-image), under a streamed chunk or under
`verify()`'s dispatch alike."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "pack_rows")
