"""Host time of commit verification a request: the entry call's wall
time less the `tpu_dispatch` spans inside it (libs/trace) — sign-bytes,
cache probes, the tally, and the packing and launch of the chunks that
`add()` streams before `verify()` opens its span. The decode in front
of the entry is `decode_host_ms`."""


def read(ctx):
    dispatch_us = sum(s.dur_us for s in ctx.spans if s.name == "tpu_dispatch")
    if not ctx.requests or not dispatch_us:
        return None
    entry_s = sum(ctx.entry_s) - sum(ctx.driver.decode_s[-ctx.requests :])
    return (entry_s * 1e3 - dispatch_us / 1e3) / ctx.requests
