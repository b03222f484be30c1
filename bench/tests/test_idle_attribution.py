"""The idle attribution's arithmetic (ISSUE 40) on hand-made runs with a
known answer: a ring of engine steps on the bench's clock and a device plane
on another, with a planted skew between the trace's host plane (which the
anchors tie to the bench's clock) and its device plane (which only the two
causal fences bound). No chip, no engine: pure Python over the tables."""
import statistics

import pytest

import idle_attribution as ia
import program_spans as ps
import trace_reduce as tr

MS = 1_000_000
OFFSET = 7_000_000_123            # the trace's HOST plane = bench clock + OFFSET
BETWEEN_NS = 800_000              # the client's code between two steps


def run_of(steps=12, skew=0, prefill_every=0, spans=True, jitter=True,
           late_event=False):
    """A synthetic traced run: `steps` engine steps, BETWEEN_NS apart, each a
    decode launch (and before it, every `prefill_every`-th step, a prefill
    launch with its own drain). `skew`: how far the device plane lies from
    the host plane, in ns. `spans=False`: the parent's ring (no
    runner.account / stage / dispatch, no drain.enqueue / fetch).
    `late_event`: a device event 1.6 ms AFTER each step's fetch returned,
    later than the least dispatch latency allows: no offset explains both. Returns (ctx, truth); truth holds the medians a reader
    must find, in ms."""
    ring, ops, mods, host, records = [], [], [], [], []
    sid = iter(range(1, 10**6))
    true = {"launch": [], "fetch": [], "turn": [], "idle": [], "kind": []}
    dev = lambda t: t + OFFSET + skew          # bench ns -> device plane
    prev = None                                # (r, e, s, decode_only)
    mods.append(("feed", dev(-5 * MS), MS))
    b = 0
    for k in range(steps):
        j = (k * 37 % 11) * 10_000 if jitter else 0     # 0..100 us
        root = next(sid)
        t = b + 10_000
        ring.append(("engine.plan", t, t + 190_000, next(sid), root, k, None,
                     None))
        t += 190_000
        with_prefill = bool(prefill_every) and k % prefill_every == 1
        first_d = first_s = None
        busy_here = 0
        launches = (["prefill"] if with_prefill else []) + ["decode"]
        for kind in launches:
            # build_batch, launch (account, stage, dispatch), commit (drain)
            ring.append(("engine.build_batch", t, t + 150_000, next(sid),
                         root, k, None, None))
            t += 150_000
            launch = next(sid)
            l0 = t
            d = t + 150_000
            if spans:
                ring.append(("runner.account", t + 10_000, t + 110_000,
                             next(sid), launch, k, None, None))
                ring.append(("runner.stage", t + 110_000, d, next(sid),
                             launch, k, None, None))
                ring.append(("runner.dispatch", d, d + 1_200_000, next(sid),
                             launch, k, None, None))
            t = d + 1_250_000
            ring.append(("runner.launch", l0, t, launch, root, k, None,
                         {"kind": kind, "key": 4}))
            # the device: the program begins 1.4 ms (+ jitter) after the
            # host entered the dispatch, runs 15 ms (a prefill 3), then the
            # argmax pass 10 us later
            s = d + 1_400_000 + j
            dur = 15 * MS if kind == "decode" else 3 * MS
            mods.append((f"_{kind}_step", dev(s), dur))
            ops.append(("fusion", dev(s), dur))
            ops.append(("argmax", dev(s + dur + 10_000), 40_000))
            mods.append(("_argmax", dev(s + dur + 10_000), 40_000))
            e = s + dur + 50_000
            busy_here += dur + 40_000
            commit, drain = next(sid), next(sid)
            c0 = t
            r = e + 650_000 + (j // 2)          # the fetch's latency
            if spans:
                ring.append(("drain.enqueue", t + 20_000, t + 400_000,
                             next(sid), drain, k, None, None))
                ring.append(("drain.fetch", t + 400_000, r, next(sid), drain,
                             k, None, None))
                ring.append(("drain.fetch", r + 30_000, r + 180_000,
                             next(sid), drain, k, None, {"what": "counts"}))
            if late_event:
                ops.append(("late", dev(r + 1_600_000), 5_000))
                busy_here += 5_000
            ring.append(("engine.drain", t + 10_000, r + 200_000, drain,
                         commit, k, None, None))
            t = r + 500_000
            ring.append(("engine.commit", c0, t, commit, root, k, None, None))
            if first_d is None:
                first_d, first_s = d, s
        end = t + 200_000
        ring.append(("engine.step", b, end, root, None, k, None, None))
        records.append((b - 2_000, end + 3_000))
        host.append(("bench.engine_step", b - 2_000 + OFFSET,
                     end - b + 5_000))
        if prev is not None:
            pr, pe, ps_, pbusy, pdec = prev
            true["kind"].append(pdec and not with_prefill)
            true["launch"].append(first_s - first_d)
            true["fetch"].append(pr - pe)
            true["turn"].append(first_d - pr)
            true["idle"].append(first_s - ps_ - pbusy)
        prev = (r, e if not late_event else r + 1_605_000, first_s, busy_here,
                not with_prefill)
        b = end + BETWEEN_NS
    mods.append(("feed", dev(b + MS), MS))
    lo, hi = records[0][0] - MS, records[-1][1] + 3 * MS
    trace = tr.clip(tr.Trace(ops={0: ops}, modules={0: mods}, host=host),
                    lo + OFFSET, hi + OFFSET)
    ctx = {"trace": trace, "median": statistics.median,
           "trace_span": (lo / 1e9, hi / 1e9),
           "steps": [(a / 1e9, z / 1e9, 4, 100, 4) for a, z in records]}
    ctx["_program_spans"] = ps.Spans(ctx, ring)
    med = lambda key, want: statistics.median(
        v for v, dec in zip(true[key], true["kind"]) if dec == want) / 1e6
    truth = {want: {key: med(key, want) for key in
                    ("launch", "fetch", "turn", "idle")}
             for want in {True, False} & set(true["kind"])}
    return ctx, truth


def read(ctx, name):
    return ia.metric(ctx, name)


# ------------------------------------------------------------ the pieces


def test_busy_union_and_last_end():
    b = ia.Busy([("a", 0, 100), ("b", 50, 100), ("c", 400, 50)])
    assert b.inside(0, 1000) == 200
    assert b.inside(120, 420) == 30 + 20
    assert b.inside(150, 400) == 0
    assert b.last_end_before(400, 0) == 150
    assert b.last_end_before(1000, 0) == 450
    assert b.last_end_before(430, 0) == 430        # cut to the instant asked
    assert b.last_end_before(1000, 420) is None    # none STARTS past 420
    assert ia.Busy([]).inside(0, 10) == 0


def test_own_time_names_the_innermost_span():
    s = lambda name, t0, t1, sid, parent: (name, t0, t1, sid, parent, 1,
                                           None, None)
    spans = [s("engine.step", 0, 100, 1, None), s("engine.commit", 10, 60, 2, 1),
             s("engine.drain", 20, 50, 3, 2), s("custom", 70, 80, 4, 1)]
    got = ia.own_time(spans, 30, 90)
    # drain 30..50, commit 50..60, the step 60..70 and 80..90, custom 70..80
    assert got == {"drain_tail": 20, "commit": 10, "step_tail": 20,
                   "custom": 10}
    assert sum(got.values()) == 60


def test_runner_programs_are_told_from_the_drains_passes():
    for name in ("_decode_step", "_prefill_step", "_decode_multi_step",
                 "_ragged_core"):
        assert ia.is_runner_program(name)
    for name in ("_argmax", "isfinite", "concatenate", "_reduce_all"):
        assert not ia.is_runner_program(name)


# ------------------------------------------------------- the decomposition


@pytest.mark.parametrize("skew", [0, MS, -MS, 1_234_567, -1_500_000])
def test_skew_is_bounded_by_the_fences_and_moves_neither_one_clock_metric(
        skew):
    ctx, truth = run_of(skew=skew)
    t = truth[True]
    # one clock each: exact, whatever the device plane's skew
    assert read(ctx, "device_idle_per_step_ms") == pytest.approx(t["idle"])
    assert read(ctx, "host_turnaround_ms") == pytest.approx(t["turn"])
    got = ia.attribute(ctx)
    fen = got["fence"]
    # the true offset of the device plane lies inside the interval, the
    # anchors' (the host plane's) only while the skew is small
    assert fen["lo_ns"] <= OFFSET + skew <= fen["hi_ns"]
    assert fen["anchor_offset_ns"] == OFFSET
    assert fen["anchor_inside"] == (fen["lo_ns"] <= OFFSET <= fen["hi_ns"])
    # (the device plane may lie early by the least dispatch latency, late
    # by the least fetch latency, before the anchors' offset falls outside)
    assert fen["anchor_inside"] == (-1_400_000 <= skew <= 650_000)
    # the least dispatch latency 1.4 ms, the least fetch latency 0.65
    width = read(ctx, "clock_fence_width_us")
    assert width == pytest.approx(1400 + 650)
    half = width / 2e3
    assert abs(read(ctx, "launch_dispatch_ms") - t["launch"]) <= half
    assert abs(read(ctx, "drain_fetch_ms") - t["fetch"]) <= half
    assert got["decode_only"]["plus_minus_ms"] == pytest.approx(half)


@pytest.mark.parametrize("skew", [0, MS, -MS])
def test_the_identity_holds_for_any_offset(skew):
    """launch + fetch = idle - in_step_idle - turnaround: gap by gap exactly
    (jitter off: every gap alike, so the medians add up too)."""
    ctx, truth = run_of(skew=skew, jitter=False)
    got = ia.attribute(ctx)["decode_only"]
    assert got["identity_residual_ms"] == pytest.approx(0, abs=1e-9)
    assert got["launch_dispatch_ms"] + got["drain_fetch_ms"] == \
        pytest.approx(truth[True]["launch"] + truth[True]["fetch"])
    assert got["rest_ms"] == pytest.approx(
        got["launch_dispatch_ms"] + got["drain_fetch_ms"]
        + got["host_turnaround_ms"])
    # with jitter the medians differ from the sums by under 0.1 ms
    jittered = ia.attribute(run_of(skew=skew)[0])["decode_only"]
    assert abs(jittered["identity_residual_ms"]) < 0.1


def test_turnaround_parts_add_up_and_name_the_new_spans():
    ctx, truth = run_of(jitter=False)
    t = ia.attribute(ctx)["decode_only"]
    parts = t["turnaround_parts_ms"]
    assert sum(parts.values()) == pytest.approx(t["host_turnaround_ms"])
    assert parts["account"] == pytest.approx(0.1)
    assert parts["stage"] == pytest.approx(0.04)
    assert parts["counts_fetch"] == pytest.approx(0.15)
    assert parts["plan"] == pytest.approx(0.19)
    assert parts["build_batch"] == pytest.approx(0.15)
    assert parts[ia.BETWEEN] > 0 and parts["commit"] > 0
    assert "runner.dispatch" not in parts          # it starts at d


def test_an_empty_interval_gives_none_and_says_why(capsys):
    ctx, truth = run_of(late_event=True)
    got = ia.attribute(ctx)
    assert got["fence"]["lo_ns"] > got["fence"]["hi_ns"]
    assert "empty interval" in got["why_no_fence"]
    for name in ("launch_dispatch_ms", "drain_fetch_ms",
                 "clock_fence_width_us"):
        assert read(ctx, name) is None
    # what one clock gives is still there
    assert read(ctx, "device_idle_per_step_ms") > 0
    assert read(ctx, "host_turnaround_ms") > 0
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[idle_attribution]")]
    assert len(said) == 1 and "empty interval" in said[0]


def test_steps_with_a_prefill_are_kept_apart():
    ctx, truth = run_of(steps=17, prefill_every=4)
    got = ia.attribute(ctx)
    alone, mixed = got["decode_only"], got["with_prefill"]
    # steps 1, 5, 9, 13 hold a prefill: each spoils the gap before it and
    # the gap after it, 8 of the 16 gaps between 17 whole steps
    assert (alone["gaps"], mixed["gaps"]) == (8, 8)
    assert alone["device_idle_per_step_ms"] == pytest.approx(
        truth[True]["idle"])
    assert alone["host_turnaround_ms"] == pytest.approx(truth[True]["turn"])
    assert mixed["host_turnaround_ms"] == pytest.approx(truth[False]["turn"])
    assert mixed["device_idle_per_step_ms"] > alone["device_idle_per_step_ms"]
    # the metrics are the decode-only table's
    assert read(ctx, "device_idle_per_step_ms") == \
        alone["device_idle_per_step_ms"]
    # every gap's idle, against what the run's own breakdown books to steps
    assert got["idle_sum_ms"] <= got["idle_gaps_ms"]
    assert got["idle_sum_ms"] > 0.8 * got["idle_gaps_ms"]


def test_a_ring_without_the_new_spans_gives_the_device_idle_alone():
    """The parent commit: `runner.launch` and `engine.drain`, no children."""
    ctx, truth = run_of(spans=False)
    assert read(ctx, "device_idle_per_step_ms") == pytest.approx(
        truth[True]["idle"])
    for name in ia.METRICS[1:]:
        assert read(ctx, name) is None
    assert "records none" in ia.attribute(ctx)["why_no_fence"]


@pytest.mark.parametrize("name", ["runner_launch_ms",
                                  "engine_drain_wait_ms", "engine_commit_ms",
                                  "engine_plan_ms"])
def test_the_accepted_readers_read_what_they_read_without_the_children(name):
    """A parent span keeps its extent when it gains children: the readers of
    `runner.launch`, `engine.drain`, `engine.commit` (its own time, less the
    drain in it) and `engine.plan` do not see the new spans."""
    from test_trace_reduce import reader

    with_children, _ = run_of(prefill_every=4)
    parents_ring, _ = run_of(prefill_every=4, spans=False)
    got = reader(name)(with_children)
    assert got is not None and got == reader(name)(parents_ring)


@pytest.mark.parametrize("name", ia.METRICS)
def test_readers_return_none_without_spans_or_without_a_trace(name,
                                                              monkeypatch):
    from test_trace_reduce import reader

    monkeypatch.setattr(ps, "ring", lambda: [])
    ctx = {"trace": tr.Trace(), "steps": [(0.0, 1.0, 0, 0, 0)],
           "trace_span": (0.0, 1.0), "median": statistics.median}
    assert reader(name)(ctx) is None
    # spans and anchors, and a device plane with no program on it
    ctx, _ = run_of()
    ctx["trace"] = tr.Trace(ops={}, modules={}, host=ctx["trace"].host,
                            span=ctx["trace"].span)
    ctx.pop("_idle_attribution", None)
    assert reader(name)(ctx) is None


def test_the_manifest_lists_the_five_for_the_serving_cells():
    import run as R

    m = R.load_json(R.ROOT, "BENCHMARK.json")
    mine = [e for e in m["per_layer"] if e["name"] in ia.METRICS]
    assert [e["name"] for e in mine] == list(ia.METRICS)
    assert m["per_layer"][-5:] == mine                 # appended, at the end
    serving = [w["name"] for w in m["workloads"]
               if "serve_tokens_per_s" in
               [e["name"] for e in m["end_to_end"]
                if w["name"] in e.get("workloads", [])]]
    for e in mine:
        assert e["workloads"] == serving and e["better"] == "lower"
        assert e["moves"] == "serve_tokens_per_s"


def test_a_cpu_toy_run_has_no_device_plane_and_leaves_the_five_out(capsys):
    """The whole path through `bench/run.py` at toy width: the spans are in
    the ring, the CPU's trace has no device plane, every reader returns None
    and none raises (by hand only: it drives an engine for six seconds)."""
    import json

    import jax

    import run as R
    from test_run_cpu import TOY

    m = R.load_json(TOY, "BENCHMARK.json")
    real = R.load_json(R.ROOT, "BENCHMARK.json")
    m["per_layer"] += [dict(e, workloads=["toy.decode"])
                       for e in real["per_layer"] if e["name"] in ia.METRICS]
    r = R.Run(R.parse(["--workload", "toy.decode", "--seed", "3", "--seconds",
                       "6", "--trace", "1"]), m, files=TOY)
    r.devices = jax.devices()[:1]
    r.peaks = R.load_json(R.BENCH, "peaks.json")["TPU v5 lite"]
    out = R.run_cell(r)
    json.dumps(out)
    assert not set(ia.METRICS) & set(out["metrics"])
    assert "engine_step_p50_ms" in out["metrics"]
    names = {s[ps.NAME] for s in ps.ring()}
    assert {"runner.account", "runner.stage", "runner.dispatch",
            "drain.enqueue", "drain.fetch"} <= names
