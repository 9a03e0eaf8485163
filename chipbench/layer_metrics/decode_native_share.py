"""Share of the window's commit decodes that took the native signature
scan: over every `commit_decode` span that says which `path` it took,
those that say `native` (the other value is `generic`, the decoder the
scan falls back to: a missing compiler on the machine, or a commit
whose bytes are not the canonical layout). A program whose span has no
`path` (a parent commit) has nothing to read."""

from chipbench import span_tree


def read(ctx):
    paths = [s.attrs.get("path") for s in span_tree.of(ctx).named("commit_decode")]
    paths = [p for p in paths if p is not None]
    if not paths:
        return None
    return 100.0 * paths.count("native") / len(paths)
