"""Host packing and launch a request: the `host_prep_s` the batch seam
records on each `tpu_dispatch` span (the same reading it feeds the
tpu_host_prep_seconds histogram), summed over the window."""


def read(ctx):
    prep = [s.attrs["host_prep_s"] for s in ctx.spans
            if s.name == "tpu_dispatch" and "host_prep_s" in s.attrs]
    if not ctx.requests or not prep:
        return None
    return sum(prep) * 1e3 / ctx.requests
