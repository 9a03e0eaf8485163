"""Hops a request verified a second time, one at a time, after a merged
window failed without naming its hop: the sum of `hops` over the
window's `light_fallback` spans (light/client.py `_verify_sequential`),
a request. 0 where the window has `light_sync` spans and no
`light_fallback`; nothing to read where it has no `light_sync` span at
all (a parent commit, which falls back without saying so)."""

from chipbench import span_tree


def read(ctx):
    tree = span_tree.of(ctx)
    if not ctx.requests or not tree.named("light_sync"):
        return None
    return sum(s.attrs["hops"] for s in tree.named("light_fallback")) / ctx.requests
