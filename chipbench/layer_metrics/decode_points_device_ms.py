"""Device time a request spends decoding points in the tile programs
(field decode, decompression and its power chain): the union of the
profiler's "XLA Ops" intervals under the `decode_points` scope
(ed25519) or the `ristretto_decode` scope (sr25519), over the traced
requests."""

from chipbench import stage_time


def read(ctx):
    return stage_time.ms_a_request(ctx, "decode_points", "ristretto_decode")
