"""Host time a request spends sharding host arrays onto the mesh: the
self time of the `shard_place` spans (parallel/sharding.py `_place`),
one a transfer, three a tile launch and one a SHA-512 launch. They are
children of `device_launch`, so under a mesh `launch_host_ms` is the
enqueue alone. A program without the span (a parent commit, or any
one-chip install, whose `_place` is a plain `jnp.asarray`) has nothing
to read."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "shard_place")
