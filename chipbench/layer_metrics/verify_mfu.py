"""The whole request path's share of the chip's int32 ceiling: the
multiply-adds the traced requests' verified signatures need (work.py)
over the traced window — first traced request's start to the last
one's end, decode, host prep, launches, device and gather all inside —
times the ceiling the deployment has: the one chip's (peaks.json) times
the configuration's `devices` (1 when it names none), not the host's
count, so a one-device deployment on a four-chip host keeps its
reading. It bounds what any kernel's roofline share can mean end to
end, and still reads when a kernel has left the path."""


def read(ctx):
    t = ctx.trace
    if not t or not t["requests"] or not t["window_s"]:
        return None
    need = ctx.driver.work(ctx.tokens[0], ctx.work)
    peak = ctx.peaks["int32_madd_per_s"]["value"] * ctx.config.get("devices", 1)
    return 100.0 * need["madds"] * t["requests"] / (t["window_s"] * peak)
