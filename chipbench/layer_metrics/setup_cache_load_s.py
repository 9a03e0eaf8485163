"""Seconds of set-up JAX spent compiling or loading programs
(jax.monitoring): compilation in a checkout's first run, the
persistent cache's read-back afterwards."""


def read(ctx):
    if not ctx.compiles_in_setup:
        return None
    return sum(s for _t, s in ctx.compiles_in_setup)
