"""Host time a request spends saving verified light blocks: the self
time of the program's `light_store_save` spans (light/client.py: the
saves of a window's blocks, and the target's save and the prune)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "light_store_save")
