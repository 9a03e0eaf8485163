"""Host time a request spends on the verified-signature cache: the
self time of `sigcache_probe` (key build, the bulk probe, the miss
list) and of `sigcache_populate` (the proven keys after the verdict,
and the commit memo's entry). The memo's own probe is a few
microseconds inside `commit_plan` and is not separable from it."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "sigcache_probe", "sigcache_populate")
