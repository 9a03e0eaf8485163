"""Host time a request spends dispatching the full chunks `add()`
streams to the device before `verify()` runs: the durations of the
`tpu_stream_dispatch` spans (packing, merlin, transfers and launches
inside them). `validation_host_ms` counts this time as validation."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "tpu_stream_dispatch", self_time=False)
