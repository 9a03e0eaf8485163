"""Share of a request's decode and verification that the program's
span tree attributes to a phase: over every `commit_decode` and
`batch_accumulate` span of the window, the part of its interval that
phase spans below it cover, over its duration. `commit_decode` has no
phase inside it and counts whole; what is left of `batch_accumulate` is
its self time, host work the tree gives no name. 100 less this share is
what the tree still cannot see."""

from chipbench import span_tree


def read(ctx):
    tree = span_tree.of(ctx)
    decodes = tree.named("commit_decode")
    verifies = tree.named("batch_accumulate")
    wall = sum(s.dur_us for s in decodes + verifies)
    if not verifies or not wall:
        return None
    covered = sum(s.dur_us for s in decodes)
    covered += sum(tree.covered_us(s) for s in verifies)
    return 100.0 * covered / wall
