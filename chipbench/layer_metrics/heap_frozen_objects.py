"""Tracked objects the collector no longer walks: the program's gauge
`heap_frozen_objects` (`tpu_verifier.stats()`), `gc.get_freeze_count()`
after the seam's last settle. After each first touch of a device program
the seam collects once and freezes (libs/heap.py), so this counts the
traced programs and whatever else was alive then; a full collection's
cost follows what is left outside it. A program without the gauge (a
parent commit) has nothing to read."""


def read(ctx):
    from tendermint_tpu.crypto import tpu_verifier

    return tpu_verifier.stats().get("heap_frozen_objects")
