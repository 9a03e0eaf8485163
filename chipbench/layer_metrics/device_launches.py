"""Device programs a request launches: the number of `device_launch`
spans over the window's requests (a tile and its SHA-512 are two)."""

from chipbench import span_tree


def read(ctx):
    launches = span_tree.of(ctx).named("device_launch")
    if not ctx.requests or not launches:
        return None
    return len(launches) / ctx.requests
