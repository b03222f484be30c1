"""The reduction on a recorded trace: three decode steps of
gpt3-1.3b.decode on a TPU v5e (my chip run, PR 24), as load() reduced them
(a one-off json.dump of the Trace's window_s, ops, modules and host,
the first 4000 events of each)."""
import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        table = json.load(f)
    as_ev = lambda evs: [(n, int(a), int(b)) for n, a, b in evs]
    return tr.Trace(window_s=table["window_s"],
                    ops={int(d): as_ev(e) for d, e in table["ops"].items()},
                    modules={int(d): as_ev(e)
                             for d, e in table["modules"].items()},
                    host=as_ev(table["host"]))


def test_recorded_trace(trace):
    steps = tr.module_ms(trace, lambda n: "decode" in n)
    assert len(steps) == 3 and all(60 < ms < 66 for ms in steps)
    kernel = tr.op_seconds(trace, lambda n: n.startswith("mosaic:"))
    assert 0.9 < kernel / (sum(steps) / 1e3) < 1.0      # the kernel's share
    assert trace.busy_s < trace.window_s
    bd = tr.breakdown(trace)
    assert bd["device_ops"][0][0] == "mosaic:_decode_step"
    assert bd["idle_gaps"][0][0] == "bench.engine_step"
    assert len(bd["device_ops"]) <= 10


def test_names():
    assert tr.op_name("%fusion.750 = bf16[32,50304]{1,0:T(8,128)}") == "fusion"
    assert tr.op_name("%convert_reduce_fusion.7 =") == "convert_reduce_fusion"
    assert tr.op_name("%all-reduce.3 = f32[8]") == "all-reduce"
    assert tr.module_name("jit__decode_step(123456)") == "_decode_step"
    assert tr.is_collective("all-gather") and not tr.is_collective("fusion")


def test_union_and_exposed():
    t = tr.Trace(window_s=1e-6, ops={0: [
        ("fusion", 0, 100), ("fusion", 50, 100),          # overlap: 0..150
        ("all-reduce", 120, 100),                         # 30 hidden, 70 bare
        ("copy", 400, 50)]})
    assert tr.union_ns(t.ops[0]) == 220 + 50
    assert tr.exposed_seconds(t, tr.is_collective) == pytest.approx(70e-9)
    assert t.busy_s == pytest.approx(270e-9)


def test_module_ms_counts_only_busy_time():
    t = tr.Trace(ops={0: [("a", 0, 10), ("b", 30, 10)]},
                 modules={0: [("step", 0, 100), ("other", 200, 10)]})
    assert tr.module_ms(t, lambda n: n == "step") == [20 / 1e6]
