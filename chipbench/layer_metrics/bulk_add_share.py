"""Share of the signatures handed to batch verifiers that entered as
whole columns: 100 x sum of `bulk` / sum of `sigs` over the window's
`batch_add` spans (crypto/batch.py `drain_classes`, one span a key
class of a commit verification). `bulk` is the class's rows where its
verifier takes columns through an `add_many` of its own (the device
verifiers, crypto/tpu_verifier.py) and 0 where it inherits the loop
over `add()`: 100 when every miss of the window crossed the seam
without per-vote Python. A program whose span has no `bulk` (a parent
commit, which calls `add()` a vote) has nothing to read."""

from chipbench import span_tree


def read(ctx):
    spans = [
        s for s in span_tree.of(ctx).named("batch_add") if "bulk" in s.attrs
    ]
    sigs = sum(s.attrs["sigs"] for s in spans)
    if not sigs:
        return None
    return 100.0 * sum(s.attrs["bulk"] for s in spans) / sigs
