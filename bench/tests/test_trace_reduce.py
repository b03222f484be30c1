"""The reduction without a chip: on a recorded trace, three decode steps of
gpt3-1.3b.decode on a TPU v5e (my chip run, PR 24), as load() reduced them
(a one-off json.dump of the Trace's ops, modules and host, the first 4000
events of each), and on hand-made tables with a known answer: what a span
clips, what a cut run is, and that every per-step reader takes time and work
from the same whole steps (ISSUE 33)."""
import importlib.util
import json
import os
import statistics

import pytest

import run as R
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
IS_STEP = lambda name: name == "step"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(R.BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def recorded_table():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        table = json.load(f)
    as_ev = lambda evs: [(n, int(a), int(b)) for n, a, b in evs]
    return tr.Trace(ops={int(d): as_ev(e) for d, e in table["ops"].items()},
                    modules={int(d): as_ev(e)
                             for d, e in table["modules"].items()},
                    host=as_ev(table["host"]))


def test_recorded_trace():
    raw = recorded_table()
    # its three decode runs lie at 0..62.2, 65.9..128.2 and 131.8..194.1 ms;
    # the span cuts the first and the third
    trace = tr.clip(raw, 1_000_000, 190_000_000)
    steps = tr.module_ms(trace, lambda n: "decode" in n)
    assert len(steps) == 1 and 60 < steps[0] < 66
    inside, n = tr.inside_whole_runs(trace, lambda n: "decode" in n)
    kernel = tr.op_seconds(inside, tr.is_kernel)
    assert n == 1 and 0.9 < kernel / (steps[0] / 1e3) < 1.0
    assert trace.window_s == pytest.approx(0.189)
    assert 0 < trace.busy_s < trace.window_s
    bd = tr.breakdown(trace)
    assert bd["device_ops"][0][0] == "mosaic:_decode_step"
    assert bd["idle_gaps"][0][0] == "bench.engine_step"
    assert len(bd["device_ops"]) <= 10
    # the gaps are all of the span that is not busy
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        trace.window_s - trace.busy_s)
    # unclipped, the first and last runs recorded are no steps either: the
    # profiler may have cut them
    assert len(tr.module_ms(raw, lambda n: "decode" in n)) == 1


def test_names():
    assert tr.op_name("%fusion.750 = bf16[32,50304]{1,0:T(8,128)}") == "fusion"
    assert tr.op_name("%convert_reduce_fusion.7 =") == "convert_reduce_fusion"
    assert tr.op_name("%all-reduce.3 = f32[8]") == "all-reduce"
    assert tr.module_name("jit__decode_step(123456)") == "_decode_step"
    assert tr.is_collective("all-gather") and not tr.is_collective("fusion")


def test_union_and_exposed():
    t = tr.Trace(ops={0: [
        ("fusion", 0, 100), ("fusion", 50, 100),          # overlap: 0..150
        ("all-reduce", 120, 100),                         # 30 hidden, 70 bare
        ("copy", 400, 50)]})
    assert tr.union_ns(t.ops[0]) == 220 + 50
    assert tr.exposed_seconds(t, tr.is_collective) == pytest.approx(70e-9)
    assert t.busy_s == pytest.approx(270e-9)


def test_events_that_overhang_the_span_are_clipped_to_it():
    """The profiler records from wherever inside start_trace it arms the
    device to wherever inside stop_trace it disarms it: a device that never
    rests reads busier than the span is long, unless clipped."""
    raw = tr.Trace(ops={0: [("a", -500, 600),       # 100 inside
                            ("b", 100, 300),        # whole: 100..400
                            ("gone", -900, 300),    # wholly before
                            ("c", 450, 2000)],      # 550 inside, to the end
                        1: [("d", -100, 1400)]},    # covers all of it
                   host=[("bench.train_step", 0, 1000)])
    assert raw.busy_s > 1000e-9 and raw.window_s == 0.0
    t = tr.clip(raw, 0, 1000)
    assert t.window_s == 1000e-9
    # device 0: 0..400 and 450..1000 = 950; device 1: 1000
    assert t.busy_s == pytest.approx((950 + 1000) / 2 * 1e-9)
    assert t.busy_s <= t.window_s
    assert 1 - t.busy_s / t.window_s == pytest.approx(0.025)
    assert sorted(t.ops[0]) == [("a", 0, 100), ("b", 100, 300),
                                ("c", 450, 550)]
    assert tr.op_seconds(t, lambda n: n == "c") == pytest.approx(275e-9)
    assert dict(tr.breakdown(t)["idle_gaps"]) == pytest.approx(
        {"bench.train_step": 50e-9, "_span_edge_": 0.0})


@pytest.mark.parametrize("lo,hi", [(-10_000, 10_000), (0, 10_000),
                                   (-10_000, 1000), (150, 950)])
def test_busy_never_exceeds_the_window(lo, hi):
    raw = tr.Trace(ops={0: [("x", t, 90) for t in range(-2000, 3000, 70)]})
    t = tr.clip(raw, lo, hi)
    assert 0 < t.busy_s <= t.window_s


def test_module_ms_counts_only_busy_time():
    t = tr.Trace(ops={0: [("z", -50, 10), ("a", 0, 10), ("b", 30, 10),
                          ("y", 250, 10)]},
                 modules={0: [("feed", -50, 10), ("step", 0, 100),
                              ("other", 200, 10), ("step", 250, 10)]})
    assert tr.module_ms(t, IS_STEP) == [20 / 1e6]


# ---------------------------------------------- whole steps and cut ones

PERIOD, KERNEL, REST = 1000, 400, 300


def stepped(whole: int, head: int, tail: int):
    """One device's record as the profiler prints a training run: the last
    `head` ns of a step it armed inside, `whole` whole steps, the first
    `tail` ns of a step it disarmed inside (0: the device was drained, and
    the record ends with the last whole step). A step is a kernel of KERNEL
    ns at +100 and a fusion of REST ns at +600."""
    ops, mods = [], []

    def step_at(t0, a, b):
        """The step that began at t0, as recorded between a and b."""
        mods.append(("step", max(t0, a), min(t0 + PERIOD, b) - max(t0, a)))
        for name, at, dur in (("mosaic:step", 100, KERNEL),
                              ("fusion", 600, REST)):
            s, e = max(t0 + at, a), min(t0 + at + dur, b)
            if e > s:
                ops.append((name, s, e - s))

    end = whole * PERIOD
    step_at(-PERIOD, -head, 0)
    for k in range(whole):
        step_at(k * PERIOD, 0, end)
    if tail:
        step_at(end, end, end + tail)
    return tr.Trace(ops={0: ops}, modules={0: mods})


def train_ctx(table, lo, hi):
    return {"trace": tr.clip(table, lo, hi), "chips": 1, "batch": 1, "seq": 8,
            "config": {"num_layers": 1, "hidden_size": 1000}, "peaks": PEAKS,
            "median": statistics.median}


def test_a_run_cut_by_either_edge_is_no_step():
    table = stepped(whole=4, head=900, tail=30)
    # the profiler armed 100 ns after the span began and disarmed after it
    # ended, so the head lies INSIDE the span and still is no step
    t = tr.clip(table, -1000, 4010)
    assert [d for _, d, _ in tr.whole_runs(t, IS_STEP, 0)] == [PERIOD] * 4
    assert tr.module_ms(t, IS_STEP) == [(KERNEL + REST) / 1e6] * 4
    # a span that cuts the first and the last whole step leaves two
    t = tr.clip(table, 10, 3990)
    assert [t0 for t0, _, _ in tr.whole_runs(t, IS_STEP, 0)] == [1000, 2000]
    inside, n = tr.inside_whole_runs(t, IS_STEP)
    assert n == 2
    assert tr.op_seconds(inside, tr.is_kernel) == \
        pytest.approx(2 * KERNEL * 1e-9)
    # the clipped table itself still holds the cut steps' kernels
    assert tr.op_seconds(t, tr.is_kernel) > \
        3 * KERNEL * 1e-9


def test_flash_roofline_is_the_same_from_6_93_steps_and_from_7_9():
    """gpt2-124m.train read 2.375 where close_window() stopped the profiler
    (0.9 + 7 steps: 8 counted, 7.9 timed) and 2.71-2.78 where tick() did
    (6.93 steps, 8 counted) on ONE program (ISSUE 33)."""
    read = reader("flash_attn_roofline")
    flops = 3 * 2.0 * 8 * 8 * 1000            # opcount, one step of this ctx
    want = 100.0 * flops / PEAKS["bf16_flops_per_s"] / (KERNEL * 1e-9)
    drained = train_ctx(stepped(7, 900, 0), -950, 7100)
    in_flight = train_ctx(stepped(6, 900, 30), -950, 6010)
    assert read(drained) == pytest.approx(want)
    assert read(in_flight) == pytest.approx(want)
    # the drained record's last step is the last run recorded: left out with
    # the cut ones, since the profiler prints a cut run no differently
    assert len(tr.whole_runs(drained["trace"], IS_STEP, 0)) == 6
    for ctx in (drained, in_flight):
        assert reader("train_step_dev_ms")(ctx) == (KERNEL + REST) / 1e6


def test_collective_exposed_ms_is_per_whole_step():
    table = stepped(whole=3, head=500, tail=500)
    # an all-reduce in every step, the cut head's too: 100 ns of which 50
    # under the fusion
    table.ops[0] += [("all-reduce", k * PERIOD + 550, 100)
                     for k in range(-1, 3)]
    table.ops[1] = list(table.ops[0])
    table.modules[1] = list(table.modules[0])
    ctx = train_ctx(tr.Trace(ops=table.ops, modules=table.modules), -400, 3400)
    assert reader("collective_exposed_ms")(ctx) == pytest.approx(50e-6)
    one_chip = train_ctx(stepped(3, 500, 500), -400, 3400)
    assert reader("collective_exposed_ms")(one_chip) is None


def decode_ctx(span, records):
    """Five decode runs of 800 ns, 1000 ns apart from 0, a kernel of 500 in
    each; the trace's clock is the bench's plus 7 s."""
    off = 7_000_000_000
    mods = [("_decode_step", k * 1000, 800) for k in range(5)]
    ops = [("mosaic:_decode_step", k * 1000 + 100, 500) for k in range(5)]
    ops += [("fusion", k * 1000 + 600, 200) for k in range(5)]
    # the profiler's host tracer holds the calls made inside the span
    raw = tr.Trace(ops={0: ops}, modules={0: mods},
                   host=[("bench.engine_step", a, b - a) for a, b in records
                         if span[0] <= a and b <= span[1]])
    steps = [((a - off) / 1e9, (b - off) / 1e9, 1, 1000 * (k + 1), 4)
             for k, (a, b) in enumerate(records)]
    bench = ((span[0] - off) / 1e9, (span[1] - off) / 1e9)
    return {"trace": tr.clip(raw, *span), "trace_span": bench, "steps": steps,
            "config": {"num_layers": 1, "hidden_size": 250}, "peaks": PEAKS,
            "median": statistics.median, "counters": {}}


def test_decode_rooflines_set_kernel_time_against_the_same_steps():
    import program_spans as ps

    # engine steps 0..4 hold runs 0..4; the span cuts run 0 and engine step
    # 4, whose run is the last thing recorded: runs 1, 2, 3 and their
    # records count
    records = [(k * 1000 - 50, k * 1000 + 900) for k in range(5)]
    ctx = decode_ctx((200, 4850), records)
    steps, table = ps.steps_with_whole_runs(ctx, lambda n: "decode" in n)
    assert [s[3] for s in steps] == [2000, 3000, 4000]
    assert tr.op_seconds(table, tr.is_kernel) == \
        pytest.approx(3 * 500e-9)
    # 2 x 250 x 2 bytes a context token at 1 GB/s over the kernel's time
    want = 100.0 * (9000 * 1000 / 1e9) / (3 * 500e-9)
    assert reader("ragged_attn_roofline")(ctx) == pytest.approx(want)
    assert reader("decode_step_dev_ms")(ctx) == 700 / 1e6


def test_train_mfu_takes_the_rate_before_the_profiler_was_asked_for():
    """A record ends when the step before it is complete. Ten steps of 1 s,
    then the profiler is asked for and the host stalls: the rate is that of
    the first ten, whatever came after."""
    records = [(100.0 + k, 100.0 + k + (0.01 if k == 0 else 1.0))
               for k in range(11)]
    records += [(111.0, 114.0), (114.0, 115.0)]       # a stall, a late step
    ctx = {"steps": records, "t_open": 100.0, "trace_requested": 111.0,
           "batch": 2, "seq": 8, "chips": 1, "peaks": PEAKS,
           "config": {"num_layers": 1, "hidden_size": 10, "ffn_hidden": 40,
                      "vocab_size": 100}}
    import opcount

    per_token = opcount.train_flops_per_token(ctx["config"], 8)
    want = 100.0 * per_token * (10 * 16 / 11.0) / PEAKS["bf16_flops_per_s"]
    assert reader("train_mfu")(ctx) == pytest.approx(want)
    # an untraced context: the whole window's
    ctx["trace_requested"] = None
    want = 100.0 * per_token * (12 * 16 / 15.0) / PEAKS["bf16_flops_per_s"]
    assert reader("train_mfu")(ctx) == pytest.approx(want)


# ------------------------------------------------ who stops the profiler


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def time(self):
        return self.now


def ticked_run(monkeypatch, tmp_path, trace_at, period, start_takes,
               seconds=30.0):
    """Run.tick() under a loop like train.py's on a faked clock; returns
    who stopped the profiler, in order."""
    import jax.profiler

    clock, stopped = FakeClock(), []
    manifest = R.load_json(R.ROOT, "BENCHMARK.json")
    cell = manifest["workloads"][0]["name"]
    run = R.Run(R.parse(["--workload", cell, "--seconds", str(seconds),
                         "--trace", "1"]), manifest)
    run.traffic = dict(run.traffic, trace_at=trace_at)
    monkeypatch.setattr(R, "time", clock)
    monkeypatch.setattr(R.tempfile, "mkdtemp", lambda **kw: str(tmp_path))

    def start_trace(*a, **kw):
        clock.now += start_takes

    def stop_trace():
        stopped.append("tick" if run.counting else "close_window")
        clock.now += 1.2

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    run.open_window()
    t_open = clock.now
    while clock.now - t_open < seconds:
        clock.now += period
        run.tick()
    run.close_window()
    assert run.trace_span[1] - run.trace_span[0] > 0
    return stopped, run


# the device step of gpt2-124m.train is 577.8 ms: the tick seven steps past
# the profiler's start fell within a millisecond of TRACE_SECONDS (ISSUE 33)
GRID = [(0.5778 + dp / 1e4, 0.040 + ds / 1e3)
        for dp in range(-12, 13, 3) for ds in range(0, 13, 2)]


def test_a_last_seconds_trace_is_stopped_by_close_window_alone(monkeypatch,
                                                               tmp_path):
    for period, start_takes in GRID + [(0.27708, 0.045), (0.0139, 0.045)]:
        stopped, run = ticked_run(monkeypatch, tmp_path, None, period,
                                  start_takes)
        assert stopped == ["close_window"], (period, start_takes)
        assert run.profiler_stall_s == pytest.approx(start_takes)


def test_a_middle_trace_is_stopped_by_tick_once(monkeypatch, tmp_path):
    for period, start_takes in GRID[::5] + [(0.0139, 0.045)]:
        stopped, run = ticked_run(monkeypatch, tmp_path, "middle", period,
                                  start_takes)
        assert stopped == ["tick"], (period, start_takes)
        assert run.profiler_stall_s == pytest.approx(start_takes + 1.2)
        assert R.TRACE_SECONDS <= run.trace_span[1] - run.trace_span[0] \
            < R.TRACE_SECONDS + period + 1e-9


def test_device_events_without_an_anchor_give_no_result(monkeypatch,
                                                        tmp_path):
    """A device plane and none of the bench's own steps in the trace: the
    span has no clock to stand on, and the run exits non-zero."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    manifest = R.load_json(R.ROOT, "BENCHMARK.json")
    run = R.Run(R.parse(["--workload", manifest["workloads"][0]["name"],
                         "--trace", "1"]), manifest)
    run.devices, run.trace_dir, run.trace_span = [None], str(tmp_path), (1., 5.)
    table = tr.Trace(ops={0: [("fusion", 10, 100)]})
    monkeypatch.setattr(tr, "load", lambda path, n: table)
    ctx = {"steps": [(2.0, 3.0)], "trace_span": run.trace_span}
    with pytest.raises(SystemExit) as e:
        run.reduced_trace(ctx)
    assert e.value.code and "anchor" in str(e.value.code)
    # with an anchor the same table is clipped to the span on ITS clock
    table.host.append(("bench.train_step", 2_000_000_500, 10))
    tmp_path.mkdir()
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    got = run.reduced_trace({"steps": [(2.0, 3.0)],
                             "trace_span": run.trace_span})
    assert got.span == (1_000_000_500, 5_000_000_500)
