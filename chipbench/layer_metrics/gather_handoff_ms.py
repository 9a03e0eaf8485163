"""Host time a request spends in the gather outside the gather's own
job: the self time of the `tpu_gather` spans less the durations of the
`gather_job` spans that follow them (`follows`, libs/trace.py). On a
TPU the job runs on a pooled watchdog thread, so this is the two thread
crossings (the worker woken, the caller woken) and the caller's loop
over its handles. With `gather_ready_ms` and `gather_fetch_ms` it adds
up to `gather_wait_ms`, less the collector's pauses inside the job."""

from chipbench import span_tree


def read(ctx):
    tree = span_tree.of(ctx)
    gathers = tree.named("tpu_gather")
    ids = {s.span_id for s in gathers}
    jobs = [s for s in tree.named("gather_job") if s.attrs.get("follows") in ids]
    if not ctx.requests or not jobs:
        return None
    waited_us = sum(tree.self_us(s) for s in gathers)
    return (waited_us - sum(s.dur_us for s in jobs)) / 1e3 / ctx.requests
