"""`zaya1-8b.train-8k`'s command end to end on the CPU at toy width
(bench/tests/data/toy-zaya: hidden 64, 3 layers, 4 query heads on 2 key/value
heads of 16, 2 of 4 experts held), as bench/tests/test_run_cpu_hybrid.py does
for the hybrid family: the sound run is correct against reference_zaya and
its traced line carries the counters' metrics; the control (the reference
with bfloat16 master weights in the program's place) is not correct."""
import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import run as R  # noqa: E402

TOY = os.path.join(ROOT, "bench", "tests", "data", "toy-zaya")


@pytest.fixture(autouse=True)
def toy_calibration(monkeypatch):
    """The balance rests on 8 x 64 tokens here, not on the cell's 2 x 8192."""
    import reference_zaya

    monkeypatch.setattr(reference_zaya, "CALIBRATION_BATCH", (8, 64))


def run_toy(seed=3, seconds=2.0, trace=0, probe=None):
    argv = ["--workload", "toy-zaya.train", "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    if probe:
        argv += ["--probe", probe]
    r = R.Run(R.parse(argv), R.load_json(TOY, "BENCHMARK.json"), files=TOY)
    r.devices = jax.devices()[:1]
    r.peaks = R.load_json(R.BENCH, "peaks.json")["TPU v5 lite"]
    out = R.run_cell(r)
    json.dumps(out)
    return out


def test_sound_run_is_correct_and_reads_its_counters():
    out = run_toy(seed=2**31 + 7, trace=1, seconds=6.0)
    assert out["correct"] and out["failed"] == 0
    assert out["compiles_in_window"] == 0 and out["attempted"] > 2
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"moe_train_mfu", "moe_block_fill", "moe_load_max_over_mean",
            "train_host_ms_per_step"} <= set(m)
    assert 0 < m["moe_block_fill"] <= 100 and m["moe_load_max_over_mean"] >= 1
    # no device plane on the CPU: the trace's readers read nothing
    assert "cca_attn_roofline" not in m and "expert_ffn_roofline" not in m


def test_control_is_not_correct():
    out = run_toy(probe="ref-bfloat16")
    assert not out["correct"]
    bad = [c["name"] for c in out["checks"] if not c["ok"]]
    assert "delta_norm_gap_worst_leaf" in bad
