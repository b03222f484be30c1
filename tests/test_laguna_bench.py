"""What the benchmark adds for the Laguna configuration, guarded by the
driver's own run: the cases of `bench/tests/test_run_cpu_laguna.py` (the
cell's command end to end on the CPU at toy width, the operation counts, the
five new readers on hand-made tables, the files the cell is found by),
collected here under their own names so that each counts, the way
tests/test_bench_reduction.py collects the reduction's. They are the bench's
functions, imported: one definition, two places it runs."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "bench"), os.path.join(ROOT, "bench", "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
pytest.register_assert_rewrite("test_run_cpu_laguna")

from test_run_cpu_laguna import (  # noqa: E402,F401
    test_altered_token_is_not_correct,
    test_attention_calls_are_told_by_their_place,
    test_control_is_not_correct,
    test_counts_at_the_published_widths,
    test_parameter_count_is_the_models_own,
    test_readers_on_a_hand_made_table,
    test_readers_read_nothing_on_another_program,
    test_readers_read_nothing_where_the_count_is_off,
    test_sound_run_is_correct_and_reads_its_counters,
    test_the_cell_is_found_by_its_files,
)
