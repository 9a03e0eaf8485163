"""Key classes a drain had in flight together: the mean `overlapped`
over the window's `batch_drain` spans (crypto/batch.py `drain_classes`,
one span a commit verification that sent anything to a batch verifier).
`overlapped` counts the drain's verifiers whose every launch was
enqueued before the drain's first gather began: 2 on a mixed
ed25519/sr25519 commit, 1 with one key class, 0 where the verifiers
work on the host. A program that drains one class after the other (a
parent commit) opens no such span and has nothing to read."""

from chipbench import span_tree


def read(ctx):
    overlapped = [
        s.attrs["overlapped"]
        for s in span_tree.of(ctx).named("batch_drain")
        if "overlapped" in s.attrs
    ]
    if not overlapped:
        return None
    return sum(overlapped) / len(overlapped)
