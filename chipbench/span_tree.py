"""The program's span tree, as every span-reading metric reads it.

The program (tendermint_tpu/libs/trace.py) records one span a phase of
a commit verification: name, start, duration, the span that opened it
(`parent_id`) and the outermost span of its tree (`root_id`). The
readers under layer_metrics/ all go through this file, so that self
time means one thing:

  self time   a span's duration less the union of its children's
              intervals, clipped to the span. A collection of Python's
              garbage collector that landed inside a phase is a
              `gc_collect` child of it, so it is left out like any
              other child: a phase's self time is the phase's own work.
  a tree      the spans that share a `root_id`. The harness opens no
              program span of its own, so a request is two trees: the
              decode (`commit_decode`) and the verification
              (`batch_accumulate`).

A program without `root_id` (a parent commit) groups by walking
`parent_id` upwards; a program without a span name reads nothing, and
the reader leaves its metric out.
"""

from __future__ import annotations

from chipbench.trace_reduce import clip, union_length


class Tree:
    def __init__(self, spans) -> None:
        self.spans = list(spans)
        self.by_id = {s.span_id: s for s in self.spans}
        self.kids: dict = {}
        self.by_name: dict = {}
        for s in self.spans:
            self.kids.setdefault(s.parent_id, []).append(s)
            self.by_name.setdefault(s.name, []).append(s)

    def named(self, *names) -> list:
        return [s for name in names for s in self.by_name.get(name, [])]

    def covered_us(self, span) -> float:
        """Length of the part of `span` that its children cover."""
        lo, hi = span.start_us, span.start_us + span.dur_us
        kids = self.kids.get(span.span_id)
        if not kids:
            return 0.0
        return union_length(
            clip([(k.start_us, k.start_us + k.dur_us) for k in kids], lo, hi)
        )

    def self_us(self, span) -> float:
        return span.dur_us - self.covered_us(span)

    def root_of(self, span):
        root = getattr(span, "root_id", None)
        if root is not None:
            return root
        while span.parent_id and span.parent_id in self.by_id:
            span = self.by_id[span.parent_id]
        return span.span_id

    def by_root(self) -> dict:
        """root id -> the spans of that tree, in the ring's order
        (children before their parents)."""
        out: dict = {}
        for s in self.spans:
            out.setdefault(self.root_of(s), []).append(s)
        return out


def of(ctx) -> Tree:
    """The tree of a traced run's spans, built once for all readers."""
    tree = getattr(ctx, "span_tree", None)
    if tree is None:
        tree = ctx.span_tree = Tree(ctx.spans)
    return tree


def ms_a_request(ctx, *names, self_time: bool = True):
    """Milliseconds a request spent in the spans of these names: their
    self time, or with `self_time=False` their whole duration. None
    where the window has no such span."""
    tree = of(ctx)
    spans = tree.named(*names)
    if not ctx.requests or not spans:
        return None
    if self_time:
        total_us = sum(tree.self_us(s) for s in spans)
    else:
        total_us = sum(s.dur_us for s in spans)
    return total_us / 1e3 / ctx.requests
