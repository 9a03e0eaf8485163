"""Host time a request spends fetching light blocks: the self time of
the program's `light_fetch` spans (light/client.py: the trust root, the
target, one bulk fetch a window), the provider's work less the decodes'
own spans (`commit_decode`)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "light_fetch")
