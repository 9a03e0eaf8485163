"""`light-150.sync` rehearsed by hand, beside the other cells'
rehearsals (`pytest chipbench/tests`): the cell's own tiny sizes, its
fixtures and its cases are those of the repo's tier-1 file,
tests/test_light_sync_cell.py, collected here under this directory's
conftest (one CPU device). test_rehearse.py's `TINY` is keyed by driver
and has no entry for `light_sync`, so its cases for this cell fail on
the lookup: PERF.md, Open questions.
"""

from tests.test_light_sync_cell import (  # noqa: F401 - collected from here
    chain,
    test_a_broken_timed_path_is_not_correct,
    test_a_traced_rehearsal_reports_the_light_clients_metrics,
    test_the_cell_rehearses_correct,
    test_the_control_fails_the_cells_comparison,
    test_the_generators_hashes_and_wire_bytes_are_the_programs,
    test_the_readers_on_a_sync_built_by_hand,
    test_the_reference_and_the_program_agree_on_clean_and_corrupted_syncs,
    tiny,
    windows,
)
