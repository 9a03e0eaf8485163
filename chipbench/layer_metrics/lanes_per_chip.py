"""Lanes of a tile launch that one chip of the mesh computes: the mean
`lanes_per_device` (bucket / mesh size) over the `shard_place` spans
whose `device_launch` runs a tile program and not SHA-512. 512 while
full 2,048-signature chunks leave `add()` whatever the mesh; a chunk
width that follows the mesh would raise it."""

from chipbench import span_tree


def read(ctx):
    tree = span_tree.of(ctx)
    lanes = []
    for s in tree.named("shard_place"):
        launch = tree.by_id.get(s.parent_id)
        program = launch.attrs.get("program", "") if launch is not None else ""
        if "sha512" not in program:
            lanes.append(s.attrs["lanes_per_device"])
    if not lanes:
        return None
    return sum(lanes) / len(lanes)
