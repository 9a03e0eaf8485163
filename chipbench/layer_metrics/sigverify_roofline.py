"""The signature programs' share of their roofline: the least time the
chip could take for the signatures the traced requests VERIFIED (not
the padded lanes) — chipbench/work.py's int32 multiply-adds over the
measured int32 ceiling of peaks.json, or their bytes over HBM's rate,
whichever is larger — over the trace's program time. That time is
summed over the chips, so on a mesh chip-seconds of need stand over
chip-seconds spent and the share needs no `devices` (verify_mfu's
window is wall time, and does)."""


def read(ctx):
    t = ctx.trace
    if not t or not t["requests"] or not t["program_s"]:
        return None
    need = ctx.driver.work(ctx.tokens[0], ctx.work)
    least, _bound = ctx.work.least_seconds(
        need["madds"] * t["requests"], need["bytes"] * t["requests"], ctx.peaks
    )
    return 100.0 * least / t["program_s"]
