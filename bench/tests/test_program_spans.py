"""The readers built on the program's spans (ISSUE 25): the anchor alignment
and the device's idle split on the recorded trace plus hand-made spans with
a known answer, and every new reader on traced CPU toy runs of each traffic
kind (None where its spans are absent)."""
import importlib.util
import json
import os
import statistics

import pytest

import program_spans as ps
import run as R
import trace_reduce as tr
from test_run_cpu import TOY

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = ["engine_plan_ms", "runner_launch_ms", "engine_drain_wait_ms",
         "engine_commit_ms"]
SETUP = ["setup_import_s", "setup_build_s", "setup_compile_s"]
NEW = SERVE + ["device_idle_outside_drain_ms", "train_host_ms_per_step"] \
    + SETUP
OFFSET = 7_000_000_123            # trace clock = bench clock + OFFSET (ns)


def reader(name):
    path = os.path.join(R.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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
    """Device busy [0,100) and [200,300) and [1000,1100); two steps."""
    trace = tr.Trace(window_s=1200e-9, ops={0: [
        ("a", 0, 100), ("b", 200, 100), ("c", 1000, 100)]},
        host=[("bench.engine_step", 0, 400), ("bench.engine_step", 500, 700)])
    ctx = ctx_for(trace, [(0, 400), (500, 1200)])
    b = lambda t: t - OFFSET                    # trace -> bench clock
    ring = [
        span("engine.plan", b(0), b(20), 2, 1, 1),
        span("engine.drain", b(150), b(350), 4, 3, 1),
        span("engine.commit", b(150), b(390), 3, 1, 1),
        span("engine.step", b(0), b(400), 1, None, 1),
        span("engine.drain", b(600), b(1150), 6, 5, 2),
        span("engine.step", b(500), b(1200), 5, None, 2),
    ]
    ctx["_program_spans"] = ps.Spans(ctx, ring)
    return ctx


def test_idle_split_by_hand():
    ctx = tiny()
    sp = ps.of(ctx)
    offset, residual, n = sp.align()
    assert (offset, residual, n) == (OFFSET, 0, 2)
    per_step, no_span, total = sp.device_idle()
    # step 1 [0,400): idle 100..200 and 300..400 = 200; its drain
    # [150,350) holds 150..200 and 300..350 = 100 of it
    # step 2 [500,1200): idle 500..1000 and 1100..1200 = 600; its drain
    # [600,1150) holds 600..1000 and 1100..1150 = 450
    assert per_step == [(100, 100), (450, 150)]
    # the trace's window is 1200 ns with 300 busy; 400..500 is in no step
    assert (total, no_span) == (900, 100)
    assert reader("device_idle_outside_drain_ms")(ctx) == \
        pytest.approx(125e-6)
    assert reader("engine_drain_wait_ms")(ctx) == pytest.approx(375e-6)
    assert reader("engine_plan_ms")(ctx) == pytest.approx(10e-6)
    # commit 240 less the drain 200 inside it; none in step 2
    assert reader("engine_commit_ms")(ctx) == pytest.approx(20e-6)
    assert sp.self_ms("engine.step") == pytest.approx([140e-6, 150e-6])


def test_anchors_take_the_last_calls_where_the_counts_differ():
    ctx = tiny()
    ctx["trace"].host.insert(0, ("bench.engine_step", -900, 50))
    sp = ps.Spans(ctx, ps.of(ctx).all)
    assert sp.align() == (OFFSET, 0, 2)


# ------------------------------------------------------ the recorded trace


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        table = json.load(f)
    as_ev = lambda evs: [(n, int(a), int(b)) for n, a, b in evs]
    return tr.Trace(window_s=table["window_s"],
                    ops={int(d): as_ev(e) for d, e in table["ops"].items()},
                    modules={int(d): as_ev(e)
                             for d, e in table["modules"].items()},
                    host=as_ev(table["host"]))


def test_alignment_and_idle_on_the_recorded_trace(recorded):
    host = [(t0, t0 + dur) for _, t0, dur in recorded.host]
    jitter = (0, 4_000, -6_000)                 # ns, on the bench's stamps
    ctx = ctx_for(recorded, host, jitter)
    ring, sid = [], 0
    drains = []
    for k, (a, b) in enumerate(host):
        # a drain from 1 ms into the step until 2 ms before its end
        sid += 2
        d0, d1 = a + 1_000_000, b - 2_000_000
        drains.append((d0, d1))
        ring.append(span("engine.drain", d0 - OFFSET, d1 - OFFSET, sid,
                         sid - 1, k))
        ring.append(span("engine.step", a - OFFSET + 1_000, b - OFFSET,
                         sid - 1, None, k))
    sp = ps.Spans(ctx, ring)
    offset, residual, n = sp.align()
    assert n == 3 and offset == OFFSET - 0      # the median pair's jitter
    assert residual == 6_000
    per_step, no_span, total = sp.device_idle()
    ivs = tr.merged(recorded.ops[0])

    def idle(a, b):                             # the slow way
        return (b - a) - sum(max(0, min(y, b) - max(x, a)) for x, y in ivs)

    for (inside, outside), (a, b), (d0, d1) in zip(per_step, host, drains):
        assert inside == idle(d0, d1)
        assert inside + outside == idle(a + 1_000, b)
    # each recorded step leaves the device idle for its last 2.9 ms or so
    # (trace_events.json); the drain as drawn here ends 2 ms before the
    # step does, so about 2 ms of that idle lie outside it
    assert all(1.5e6 < outside < 4e6 for _, outside in per_step[:2])
    assert total == pytest.approx(
        recorded.window_s * 1e9 - tr.union_ns(recorded.ops[0]))


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
    # no device trace on the CPU: nothing to read, and the line leaves it out
    assert "device_idle_outside_drain_ms" not in got
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
