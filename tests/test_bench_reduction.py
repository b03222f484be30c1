"""The benchmark's reduction, guarded by the driver's own run (ISSUE 33's
copy, ISSUE 40): the pure-Python cases of `bench/tests/test_trace_reduce.py`,
`test_program_spans.py` and `test_idle_attribution.py` (hand-made tables and
one recorded trace; no engine, no chip), collected here under their own names
so that each counts. They are the bench's functions, imported: one
definition, two places it runs. The cases that drive a toy engine through
`bench/run.py` stay with `bench/tests`, run by hand."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "bench"), os.path.join(ROOT, "bench", "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
pytest.register_assert_rewrite("test_trace_reduce", "test_program_spans",
                               "test_idle_attribution")

from test_trace_reduce import (  # noqa: E402,F401
    test_a_last_seconds_trace_is_stopped_by_close_window_alone,
    test_a_middle_trace_is_stopped_by_tick_once,
    test_a_run_cut_by_either_edge_is_no_step,
    test_busy_never_exceeds_the_window,
    test_collective_exposed_ms_is_per_whole_step,
    test_decode_rooflines_set_kernel_time_against_the_same_steps,
    test_device_events_without_an_anchor_give_no_result,
    test_events_that_overhang_the_span_are_clipped_to_it,
    test_flash_roofline_is_the_same_from_6_93_steps_and_from_7_9,
    test_module_ms_counts_only_busy_time,
    test_names,
    test_recorded_trace,
    test_train_mfu_takes_the_rate_before_the_profiler_was_asked_for,
    test_union_and_exposed,
)
from test_program_spans import (  # noqa: E402,F401
    recorded,
    test_a_device_plane_that_lies_early_shows_as_a_negative_lead,
    test_alignment_on_the_recorded_trace,
    test_anchors_take_the_last_calls_where_the_counts_differ,
    test_reader_returns_none_without_spans,
    test_readers_by_hand,
    test_ring_is_empty_where_the_program_has_no_spans,
)
from test_idle_attribution import (  # noqa: E402,F401
    test_a_ring_without_the_new_spans_gives_the_device_idle_alone,
    test_an_empty_interval_gives_none_and_says_why,
    test_busy_union_and_last_end,
    test_own_time_names_the_innermost_span,
    test_readers_return_none_without_spans_or_without_a_trace,
    test_runner_programs_are_told_from_the_drains_passes,
    test_skew_is_bounded_by_the_fences_and_moves_neither_one_clock_metric,
    test_steps_with_a_prefill_are_kept_apart,
    test_the_accepted_readers_read_what_they_read_without_the_children,
    test_the_identity_holds_for_any_offset,
    test_turnaround_parts_add_up_and_name_the_new_spans,
)


def test_the_manifest_lists_the_five_for_the_serving_cells():
    """PR 40's five idle metrics stand together in the manifest, in their
    order, and list every serving cell. (`bench/tests/test_idle_attribution`
    holds them to be the manifest's LAST five, which stopped being so when
    PR 42 appended a cell's metrics behind them, as the contract asks of
    every new entry; that file is the benchmark's and a model_config PR may
    not edit it, so the durable half of its case is here.)"""
    import idle_attribution as ia
    import run as R

    m = R.load_json(R.ROOT, "BENCHMARK.json")
    names = [e["name"] for e in m["per_layer"]]
    at = names.index(ia.METRICS[0])
    mine = m["per_layer"][at:at + len(ia.METRICS)]
    assert [e["name"] for e in mine] == list(ia.METRICS)
    serving = [w["name"] for w in m["workloads"]
               if any(w["name"] in e.get("workloads", [])
                      for e in m["end_to_end"]
                      if e["name"] == "serve_tokens_per_s")]
    assert len(serving) >= 4
    for e in mine:
        assert e["workloads"] == serving and e["better"] == "lower"
        assert e["moves"] == "serve_tokens_per_s"


def test_collective_exposed_all_forms_ms_counts_the_asynchronous_forms():
    """PR 45's reader beside the accepted one on one hand-made table: a
    step with a synchronous all-reduce (100 ns, 50 of them under the
    fusion), an `async-collective-start` nothing runs beside (30 ns) and an
    `async-collective-done` that outlasts the fusion by 40 ns. The accepted
    reader sees the first alone; without asynchronous forms the two agree,
    and neither reads anything on one chip."""
    import test_trace_reduce as T

    both = [T.reader("collective_exposed_ms"),
            T.reader("collective_exposed_all_forms_ms")]

    def ctx_with(per_step):
        table = T.stepped(whole=3, head=500, tail=500)
        table.ops[0] += [(name, k * T.PERIOD + at, dur)
                         for k in range(-1, 3) for name, at, dur in per_step]
        table.ops[1] = list(table.ops[0])
        table.modules[1] = list(table.modules[0])
        return T.train_ctx(T.tr.Trace(ops=table.ops, modules=table.modules),
                           -400, 3400)

    sync = ("all-reduce", 550, 100)
    fusion_ends = 600 + T.REST
    ctx = ctx_with([sync, ("async-collective-start", 20, 30),
                    ("async-collective-done", fusion_ends - 20, 60)])
    assert [read(ctx) for read in both] == [
        pytest.approx(50e-6), pytest.approx((50 + 30 + 40) * 1e-6)]
    plain = ctx_with([sync])
    assert both[0](plain) == both[1](plain) == pytest.approx(50e-6)
    one_chip = T.train_ctx(T.stepped(3, 500, 500), -400, 3400)
    assert [read(one_chip) for read in both] == [None, None]


def test_the_manifest_lists_the_all_forms_reader_for_the_four_chip_cell():
    import run as R

    m = R.load_json(R.ROOT, "BENCHMARK.json")
    by_name = {e["name"]: e for e in m["per_layer"]}
    mine = by_name["collective_exposed_all_forms_ms"]
    accepted = by_name["collective_exposed_ms"]
    assert {k: mine[k] for k in mine if k != "name"} == \
        {k: accepted[k] for k in accepted if k != "name"}
