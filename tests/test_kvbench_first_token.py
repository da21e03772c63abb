"""A request's way to its first token as the benchmark reads it
(``kvbench/metrics/_first_token.py`` and the five readers of PR 59), on
built slices: the cases live beside the harness's own tests
(``kvbench/tests/test_first_token.py``) and are collected here, as
``tests/test_kvbench_launches.py`` collects the pairing's."""

from kvbench.tests.test_first_token import (  # noqa: F401 (collected here)
    test_a_dispatch_outside_the_markers_launches_is_not_its_chunk,
    test_a_marker_whose_chunks_the_slice_cut_is_left_out,
    test_a_slice_with_no_marker_reads_zero_and_an_untraced_run_nothing,
    test_every_attribute_the_engine_gives_is_read,
    test_load_keeps_an_event_as_short_as_the_marker,
    test_one_document_alone,
    test_outside_the_engine_joins_the_windows_own_requests_only,
    test_own_device_share_under_a_shifted_clock,
    test_the_cells_that_report_them,
    test_the_summary_checks_the_marker_against_the_trace_around_it,
    test_three_requests_one_behind_another,
)
