"""Lanes of the dispatched buckets that carried padding:
pad_waste / (sigs + pad_waste) from tpu_verifier.stats() deltas."""


def read(ctx):
    c = ctx.counters
    lanes = c["sigs"] + c["pad_waste"]
    if not lanes:
        return None
    return 100.0 * c["pad_waste"] / lanes
