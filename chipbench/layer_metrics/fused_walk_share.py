"""Share of a window's tile launches whose program runs the 64-window
walk of `[S]B - [k]A` as the fused Pallas kernel (ops/fused_walk.py):
100 x the `device_launch` spans with `walk == "fused"` over those that
carry a `walk` (ops/verifier.py `_launch` puts "fused" or "scan" on a
tile program's span, read off the program it launches: whether what
jit traced for those operands holds a Pallas kernel; none on
SHA-512's). 100 where every launched tile holds the kernel; 0 would
say that the scan program served. A program whose spans carry no
`walk` (a parent commit, which has the scan alone) has nothing to
read."""

from chipbench import span_tree


def read(ctx):
    walks = [
        s.attrs["walk"]
        for s in span_tree.of(ctx).named("device_launch")
        if "walk" in s.attrs
    ]
    if not walks:
        return None
    return 100.0 * walks.count("fused") / len(walks)
