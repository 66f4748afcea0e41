"""Tier-1 sees the benchmark's contract: the cases of
``benchmark/tests/test_contract.py`` (no jax, seconds) run here too, so a PR
that breaks what the yardstick promises a later PR — a clean lint, the pools
of the mixes that stand byte for byte, the flags each cell hands the
program, the comparison's recorded numbers, the rate rule, the lexicon's
shares — fails tier-1 and not only a run by hand of ``benchmark/tests``.
The flags of cells added since that file was written are recorded in
``benchmark/tests/conftest.py`` (imported first: it is no conftest of THIS
directory) or, since PR 35, in the cell's own test file: the cases of
``benchmark/tests/test_hash2e18_ab4.py`` that need no rehearsal run here too
(its two fault cases drive the harness for a minute each and run by hand;
``tests/test_tenant_deployment.py`` holds their in-process twin); so do
those of ``benchmark/tests/test_hash2e18_lang4.py`` (PR 42; its three fault
cases' twins are in ``tests/test_tenant_lang_deployment.py``) and of
``benchmark/tests/test_hash2e18_grid4.py`` (PR 47; its four fault cases'
twins are in ``tests/test_tenant_grid.py``) and of
``benchmark/tests/test_hash2e20_grid4.py`` (PR 52: the arms on the 2 x 2
mesh; its rehearsal, control and fault cases take minutes each at 2^20 dims
and run by hand, their twins are in ``tests/test_tenant_grid_mesh.py``).
``benchmark/tests/test_publish_ms_p95.py`` (PR 53) holds the metric that PR
appended for every cell, and the two cases of the file before it that held a
cell's own metrics to the END of its list, as they now read.
"""

import benchmark.tests.conftest as _added_since  # noqa: F401
from benchmark.tests.test_contract import *  # noqa: F401,F403
from benchmark.tests.test_logit2e18 import (  # noqa: F401
    test_gate_counts_no_row_on_this_program,
    test_gate_hands_over_to_train_unchanged,
    test_gate_refuses_a_labeler_that_falls_back,
    test_program_flags_are_the_recorded_list,
    test_the_mix_names_the_gated_driver,
    test_the_cell_is_the_fixtures_learner_on_its_own_files,
)
from benchmark.tests.test_hash2e18_ab4 import (  # noqa: F401
    test_program_flags_are_the_recorded_list as test_ab4_program_flags_are_the_recorded_list,
    test_readers_find_nothing_in_a_program_without_the_plane,
    test_the_cell_is_hash2e18_trimmed_280_with_the_plane_on,
    test_the_cell_reports_the_planes_metrics_and_the_shared_ones,
)
from benchmark.tests.test_hash2e18_lang4 import (  # noqa: F401
    test_program_flags_are_the_recorded_list as test_lang4_program_flags_are_the_recorded_list,
    test_readers_on_a_span_file_worked_by_hand,
    test_the_cell_is_the_ab4_cell_with_the_other_key,
    test_the_cell_reports_ab4s_metrics_and_its_own_three,
)
from benchmark.tests.test_hash2e18_grid4 import (  # noqa: F401
    test_program_flags_are_the_recorded_list as test_grid4_program_flags_are_the_recorded_list,
    test_readers_on_a_trace_made_by_hand,
    test_the_cell_is_hash2e18_trimmed_280_with_four_recipes_on_its_rows,
)
from benchmark.tests.test_hash2e20_grid4 import (  # noqa: F401
    test_program_flags_are_the_recorded_list as test_mesh_grid4_program_flags_are_the_recorded_list,
    test_readers_on_a_trace_made_by_hand as test_mesh_grid4_readers_on_a_trace_made_by_hand,
    test_the_cell_is_hash2e20_with_grid4s_four_recipes_on_its_rows,
)
from benchmark.tests.test_lasso2e18 import (  # noqa: F401
    test_program_flags_are_the_recorded_list as test_lasso2e18_program_flags_are_the_recorded_list,
    test_readers_on_a_trace_and_a_span_file_made_by_hand,
    test_the_cell_is_hash2e18_trimmed_280_under_the_l1_updater,
    test_the_cell_reports_its_controls_metrics_less_four_and_its_own_four,
    test_the_limits_cannot_see_the_four_numeric_weights,
    test_the_work_count_is_the_most_a_batch_reads_and_feeds_no_roofline,
    # the two cases of files before it that a ninth cell makes stale, as
    # they now read, under the names they had
    test_the_four_chip_cells_are_these_two_of_nine as test_the_four_chip_cells_are_these_two_of_eight,
    test_publish_ms_p95_lists_every_cell_and_a_cells_own_metrics_follow_it as test_the_metric_is_the_last_entry_and_every_cell_lists_it,
)
from benchmark.tests.test_publish_ms_p95 import (  # noqa: F401
    # the mesh cell's case and grid4's case of these names as they now read:
    # a metric appended for every cell stands after a cell's own (the new
    # file's docstring; grid4's was restated once before, by PR 52)
    test_the_cell_reports_hash2e20s_metrics_the_arms_two_and_its_own_two,
    test_grid4_reports_the_single_models_metrics_and_its_own_three as test_the_cell_reports_the_single_models_metrics_and_its_own_three,
)
