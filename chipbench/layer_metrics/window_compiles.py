"""Programs JAX compiled or loaded inside the window (jax.monitoring's
backend_compile_duration events). Reads 0: set-up warms every shape."""


def read(ctx):
    return len(ctx.compiles_in_window)
