"""Hops the light client verified as one merged device batch: the mean
`hops` over the window's `light_window` spans (light/client.py
`_verify_sequential`, one a merged window). 32 on a TPU
(`min(SEQUENTIAL_BATCH_HOPS, group_affinity())`); a program without the
span (a parent commit) has nothing to read."""

from chipbench import span_tree


def read(ctx):
    hops = [s.attrs["hops"] for s in span_tree.of(ctx).named("light_window")]
    if not hops:
        return None
    return sum(hops) / len(hops)
