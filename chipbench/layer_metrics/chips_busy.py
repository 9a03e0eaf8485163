"""Chips that did any of the work: the device planes of the profiler's
trace that ran at least one operation (`trace_reduce.py`'s `devices`).
The mesh's size, or the mesh is not in use."""


def read(ctx):
    t = ctx.trace
    if not t or not t["requests"] or not t["devices"]:
        return None
    return t["devices"]
