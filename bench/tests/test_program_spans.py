"""The readers built on the program's spans (ISSUE 25): the anchor alignment
on the recorded trace, hand-made spans with a known answer (the readers, and
how long after the host's launch the device begins: ISSUE 33), and every
reader on traced CPU toy runs of each traffic kind (None where its spans are
absent)."""
import json
import statistics

import pytest

import program_spans as ps
import run as R
import trace_reduce as tr
from test_run_cpu import TOY
from test_trace_reduce import reader, recorded_table

SERVE = ["engine_plan_ms", "runner_launch_ms", "engine_drain_wait_ms",
         "engine_commit_ms"]
SETUP = ["setup_import_s", "setup_build_s", "setup_compile_s"]
NEW = SERVE + ["train_host_ms_per_step"] + SETUP
OFFSET = 7_000_000_123            # trace clock = bench clock + OFFSET (ns)


def span(name, t0, t1, sid, parent=None, step=None):
    return (name, t0, t1, sid, parent, step, None, None)


def ctx_for(trace, steps_trace_ns, jitter=(0, 0, 0)):
    """A ctx whose bench-clock records are the trace's host spans moved
    back by OFFSET (plus a per-step jitter in ns)."""
    steps = [((a - OFFSET + j) / 1e9, (b - OFFSET) / 1e9, 0, 0, 0)
             for (a, b), j in zip(steps_trace_ns, jitter)]
    return {"trace": trace, "steps": steps, "median": statistics.median,
            "trace_span": (steps[0][0] - 1e-3, steps[-1][1] + 1e-3)}


# ------------------------------------------------- a hand-made tiny trace


def tiny():
    """Two engine steps, [0,400) and [500,1200) on the trace's clock, a
    decode run in each."""
    trace = tr.clip(tr.Trace(
        ops={0: [("a", 30, 70), ("b", 200, 100), ("c", 1000, 100)]},
        modules={0: [("feed", -100, 10), ("_decode_step", 30, 270),
                     ("_decode_step", 1000, 100), ("feed", 1150, 10)]},
        host=[("bench.engine_step", 0, 400),
              ("bench.engine_step", 500, 700)]), 0, 1200)
    ctx = ctx_for(trace, [(0, 400), (500, 1200)])
    b = lambda t: t - OFFSET                    # trace -> bench clock
    ring = [
        span("engine.plan", b(0), b(20), 2, 1, 1),
        span("runner.launch", b(20), b(60), 7, 1, 1),
        span("engine.drain", b(150), b(350), 4, 3, 1),
        span("engine.commit", b(150), b(390), 3, 1, 1),
        span("engine.step", b(0), b(400), 1, None, 1),
        span("runner.launch", b(520), b(560), 8, 5, 2),
        span("engine.drain", b(600), b(1150), 6, 5, 2),
        span("engine.step", b(500), b(1200), 5, None, 2),
    ]
    ctx["_program_spans"] = ps.Spans(ctx, ring)
    return ctx


def test_readers_by_hand():
    ctx = tiny()
    sp = ps.of(ctx)
    offset, residual, n = sp.align()
    assert (offset, residual, n) == (OFFSET, 0, 2)
    assert reader("engine_drain_wait_ms")(ctx) == pytest.approx(375e-6)
    assert reader("engine_plan_ms")(ctx) == pytest.approx(10e-6)
    assert reader("runner_launch_ms")(ctx) == pytest.approx(40e-6)
    # commit 240 less the drain 200 inside it; none in step 2
    assert reader("engine_commit_ms")(ctx) == pytest.approx(20e-6)
    assert sp.self_ms("engine.step") == pytest.approx([100e-6, 110e-6])
    # the decode run begins 10 ns after the host entered step 1's launch
    # and 480 ns after step 2's
    assert sp.device_lead_ms() == pytest.approx([10e-6, 480e-6])


def test_a_device_plane_that_lies_early_shows_as_a_negative_lead():
    ctx = tiny()
    early = ctx["trace"]
    shift = lambda evs: [(n, t0 - 25, d) for n, t0, d in evs]
    ctx["trace"] = tr.clip(tr.Trace(
        ops={0: shift(early.ops[0])}, modules={0: shift(early.modules[0])},
        host=early.host), 0, 1200)
    assert ps.Spans(ctx, ps.of(ctx).all).device_lead_ms() == pytest.approx(
        [-15e-6, 455e-6])


def test_anchors_take_the_last_calls_where_the_counts_differ():
    ctx = tiny()
    ctx["trace"].host.insert(0, ("bench.engine_step", -900, 50))
    sp = ps.Spans(ctx, ps.of(ctx).all)
    assert sp.align() == (OFFSET, 0, 2)


# ------------------------------------------------------ the recorded trace


@pytest.fixture(scope="module")
def recorded():
    raw = recorded_table()
    # a span that holds all three steps, as ctx_for() draws it
    return tr.clip(raw, raw.host[0][1] - 1_000_000,
                   raw.host[-1][1] + raw.host[-1][2] + 1_000_000)


def test_alignment_on_the_recorded_trace(recorded):
    host = [(t0, t0 + dur) for _, t0, dur in recorded.host]
    jitter = (0, 4_000, -6_000)                 # ns, on the bench's stamps
    ctx = ctx_for(recorded, host, jitter)
    ring = [span("engine.step", a - OFFSET + 1_000, b - OFFSET, k + 1, None, k)
            for k, (a, b) in enumerate(host)]
    sp = ps.Spans(ctx, ring)
    offset, residual, n = sp.align()
    assert n == 3 and offset == OFFSET - 0      # the median pair's jitter
    assert residual == 6_000
    # of its three decode runs the middle one is whole, and it is the
    # second record's
    records, table = ps.steps_with_whole_runs(ctx, lambda n: "decode" in n)
    assert records == [ctx["steps"][1]]
    assert tr.op_seconds(table, tr.is_kernel) == \
        pytest.approx(tr.op_seconds(tr.inside_whole_runs(
            recorded, lambda n: "decode" in n)[0],
            tr.is_kernel))


# ------------------------------------------------------------ CPU toy runs


def toy_manifest():
    """The toy benchmark with the new per-layer metrics listed in its
    cells (the toy's own manifest is the benchmark's and stays as it is)."""
    m = R.load_json(TOY, "BENCHMARK.json")
    cells = {"serve": ["toy.decode", "toy.chat"], "train": ["toy.train"]}
    for name in NEW:
        kind = ("train" if name.startswith("train") else
                "both" if name.startswith("setup") else "serve")
        m["per_layer"].append({
            "name": name, "unit": "s" if name.endswith("_s") else "ms",
            "better": "lower", "source": "program_span", "layer": "x",
            "moves": "setup_s",
            "workloads": sum(cells.values(), []) if kind == "both"
            else cells[kind]})
    return m


def run_traced(workload, seconds=6.0):
    import jax

    argv = ["--workload", workload, "--seed", "3", "--seconds", str(seconds),
            "--trace", "1"]
    r = R.Run(R.parse(argv), toy_manifest(), files=TOY)
    r.devices = jax.devices()[:r.cell["chips"]]
    r.peaks = R.load_json(R.BENCH, "peaks.json")["TPU v5 lite"]
    out = R.run_cell(r)
    json.dumps(out)
    return out["metrics"]


@pytest.mark.parametrize("workload", ["toy.decode", "toy.chat"])
def test_serving_readers_on_a_cpu_toy(workload, capsys):
    got = run_traced(workload)
    assert set(SERVE + SETUP) <= set(got)
    assert "train_host_ms_per_step" not in got
    assert all(got[n]["value"] >= 0 for n in SERVE + SETUP)
    if workload == "toy.decode":
        # the program's parts of a step lie inside the bench's own span
        parts = sum(got[n]["value"] for n in SERVE)
        assert 0 < parts <= got["engine_step_p50_ms"]["value"] * 1.5
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[program_spans] engine.step")]
    assert len(said) == 1 and "anchor_residual_us" in said[0]


def test_training_readers_on_a_cpu_toy(capsys):
    got = run_traced("toy.train")
    assert set(["train_host_ms_per_step"] + SETUP) <= set(got)
    assert not set(SERVE) & set(got)
    assert got["train_host_ms_per_step"]["value"] > 0
    assert got["setup_compile_s"]["value"] > 0
    assert "[program_spans] train.step" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_without_spans(name, monkeypatch):
    """A program that records no span (the parent commit)."""
    monkeypatch.setattr(ps, "ring", lambda: [])
    ctx = {"trace": tr.Trace(), "steps": [(0.0, 1.0, 0, 0, 0)],
           "trace_span": (0.0, 1.0), "median": statistics.median}
    assert reader(name)(ctx) is None


def test_ring_is_empty_where_the_program_has_no_spans(monkeypatch):
    from paddle_tpu import profiler

    monkeypatch.delattr(profiler, "spans")
    assert ps.ring() == []
