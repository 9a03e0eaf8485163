"""Host time a request spends blocked on the device: the self time of
the `tpu_gather` spans (the gather of every handle in flight). Beside
`kernel_device_ms` it says how much of the device's time the host's
own work already hides."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "tpu_gather")
