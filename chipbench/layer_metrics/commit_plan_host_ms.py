"""Host time a request spends on the plan of a commit verification:
the self time of `commit_plan` (the processed indexes, the tally, the
commit memo's key and probe)."""

from chipbench import span_tree


def read(ctx):
    return span_tree.ms_a_request(ctx, "commit_plan")
