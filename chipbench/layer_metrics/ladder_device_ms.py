"""Device time a request spends in the 64-window scalar-multiplication
walk of the tile programs: the union of the profiler's "XLA Ops"
intervals whose operation carries the `dual_mult` scope of
ops/ed25519_kernel.py, over the traced requests."""

from chipbench import stage_time


def read(ctx):
    return stage_time.ms_a_request(ctx, "dual_mult")
