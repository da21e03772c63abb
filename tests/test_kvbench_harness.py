"""The harness's own checks of what decides every verdict and what a new
configuration trips over first: the last line's checker, the traffic
generators, the gap readers, the name rules, ``programs_per_step``, the
Solar-Open2 files' arithmetic and the trace readers' names. Their cases
live beside the harness (``kvbench/tests``, a suite of its own) and are
collected here too, as ``test_kvbench_launches.py`` collects
``test_launches.py``'s: about a second together."""

from kvbench.tests.test_check_line import (  # noqa: F401 (collected here too)
    test_compared_is_optional_last_and_pairs_of_numbers,
    test_each_fault_is_refused,
    test_good_line_passes,
)
from kvbench.tests.test_generators import (  # noqa: F401
    test_another_structure_seed_is_another_order,
    test_doc_reask_reuse_distance,
    test_every_seed_offers_the_same_work,
    test_open_loop_covers_the_window,
    test_pure_function_of_seed,
    test_quantile_sets_and_apportion,
    test_sessions_extend_earlier_prompts,
    test_short_is_unshared_and_closed,
)
from kvbench.tests.test_itl_readers import (  # noqa: F401
    test_every_cell_reports_a_judged_gap_and_the_tail_per_layer,
    test_gap_readers,
    test_no_gap_is_none,
)
from kvbench.tests.test_names import (  # noqa: F401
    test_benchmark_resolves,
    test_contract_shape,
    test_harness_and_readers_reach_reference_and_counts_by_name_only,
    test_harness_names_no_cell,
    test_missing_file_names_the_path,
    test_rehearsal_groups_replace_values,
    test_unknown_workload_lists_the_known,
)
from kvbench.tests.test_programs_per_step import (  # noqa: F401
    test_nothing_to_read_is_none,
    test_programs_per_step,
    test_the_recorded_fixture_reads_above_one,
)
from kvbench.tests.test_solar_open2 import (  # noqa: F401
    cfg,
    conf,
    test_a_chunks_flops_grow_with_its_tokens_and_its_keys,
    test_a_program_without_the_kernels_reports_nothing,
    test_orphaned_snapshots_are_counted_over_the_window,
    test_the_arithmetic_of_the_cut,
    test_the_cell_reports_its_three_metrics,
    test_the_file_keeps_every_published_width,
    test_the_recurrences_work_is_counted_from_the_definition,
    test_the_reference_imports_nothing_of_the_program,
    test_the_references_recurrence_is_the_definition,
    test_the_scans_share_is_the_definitions_work_over_the_kernels_time,
    test_the_steps_share_is_the_states_bytes_over_the_kernels_time,
)
from kvbench.tests.test_trace_names import (  # noqa: F401
    test_each_trace_reader_matches_the_constant,
)
