"""Host time a request spends on the header-chain checks of its
windows: the self time of the program's `light_header_checks` spans
(light/client.py: `LightBlock.validate_basic` of a window's blocks;
light/verifier.py: their `adjacent_header_checks`). The merkle roots
inside them (`merkle_hash` spans: a header's 14 fields, a validator
set's leaves) are spans of their own and left out, as self time is."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "light_header_checks")
