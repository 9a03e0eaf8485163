"""Host time a request spends on sr25519's merlin challenges: the self
time of the `merlin_challenges` spans (`challenge_batch` and the
challenges' byte rows), which run on the host before each sr25519
launch."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "merlin_challenges")
