"""Commits whose votes block sync verified in one batch: the mean
`commits` over the window's `blocksync_verify` spans. 1 while a block
is verified alone; what batching across blocks (ROADMAP A4) would
raise. A program without the span (a parent commit) has nothing to
read."""

from chipbench import span_tree


def read(ctx):
    commits = [s.attrs.get("commits") for s in span_tree.of(ctx).named("blocksync_verify")]
    commits = [c for c in commits if c is not None]
    if not commits:
        return None
    return sum(commits) / len(commits)
