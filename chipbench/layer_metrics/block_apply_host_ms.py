"""Host time a request spends applying blocks once they are valid: the
program's `block_execute` spans (state/execution.py `apply_block`) less
the `validate_block` spans inside them: the ABCI calls (`exec_block`,
`abci_commit`), the state's save (`state_save`) and the events."""

from chipbench import span_tree


def read(ctx):
    whole = span_tree.ms_a_request(ctx, "block_execute", self_time=False)
    validate = span_tree.ms_a_request(ctx, "validate_block", self_time=False)
    if whole is None or validate is None:
        return None
    return whole - validate
