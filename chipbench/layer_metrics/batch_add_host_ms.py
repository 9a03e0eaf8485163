"""Host time a request spends handing signatures to the batch
verifiers: the self time of `batch_add` (`create_batch_verifier` and
the `add()` calls of one key class), the chunks that `add()` streams
to the device (`tpu_stream_dispatch` children) left out."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "batch_add")
