"""Share of sr25519's merlin challenges the device made: 100 x the rows
(`n`) of the window's `merlin_challenges` spans with `form == "device"`
over the rows of all that carry a `form` (ops/sr25519_kernel.py puts
"device" on a message-length group the merlin program ran, at
MERLIN_DEVICE_LANES and wider, and "host" on one the host's
transcripts made). 100 where every challenge of the window came from
the program. A program whose spans carry no `form` (a parent commit,
which made them all on the host) has nothing to read."""

from chipbench import span_tree


def read(ctx):
    spans = [
        s for s in span_tree.of(ctx).named("merlin_challenges") if "form" in s.attrs
    ]
    rows = sum(s.attrs["n"] for s in spans)
    if not rows:
        return None
    device = sum(s.attrs["n"] for s in spans if s.attrs["form"] == "device")
    return 100.0 * device / rows
