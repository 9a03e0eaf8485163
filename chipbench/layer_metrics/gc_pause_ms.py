"""Host time a request loses to Python's garbage collector: the seconds
between `gc.callbacks`' start and stop over the window, all three
generations, over the window's requests. A full collection walks every
tracked object of the process (about a million with JAX and the program
imported) and stops the one caller for as long."""


def read(ctx):
    if not ctx.requests or not ctx.gc_pauses:
        return None
    return sum(seconds for _t, seconds, _gen in ctx.gc_pauses) * 1e3 / ctx.requests
