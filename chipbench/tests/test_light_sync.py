"""`light-150.sync`'s own cases beside the rehearsals every cell gets in
test_rehearse.py: the generator's hashes and wire bytes against the
program's, the reference and the program on clean and corrupted syncs,
the light client's readers on a sync built by hand. They are those of
the repo's tier-1 file, tests/test_light_sync_cell.py, collected here
with that file's own fixtures (its `tiny` stands in for conftest.py's in
this module).
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
