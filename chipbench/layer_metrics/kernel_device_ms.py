"""Device time of the signature programs a request, from the profiler
trace: the summed duration of every program execution on the device in
the traced part of the window, over the requests traced. The window
drives nothing else on the device."""


def read(ctx):
    t = ctx.trace
    if not t or not t["requests"] or not t["program_s"]:
        return None
    return t["program_s"] * 1e3 / t["requests"]
