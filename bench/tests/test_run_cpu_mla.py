"""The command end to end on the CPU for the latent-attention + held-experts
family at toy width (bench/tests/data/toy-mla: hidden 128, 4 heads of
32 + 16, latent 64, 16 experts top-4 of which 4 are held, 8 layers), under
`closed-serve` and past the look for a chip, as test_run_cpu.py does for
GPT: the sound engine is correct against reference_deepseek, its control
(int8 dense matrices) is not, and a traced run reads the expert counters."""
import json
import os

import jax
import pytest

import run as R

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "toy-mla")


def run_toy(seed=3, seconds=2.0, trace=0, probe=None):
    argv = ["--workload", "toy-mla.decode", "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if probe:
        argv += ["--probe", probe]
    r = R.Run(R.parse(argv), R.load_json(TOY, "BENCHMARK.json"), files=TOY)
    r.devices = jax.devices()[:1]
    r.peaks = R.load_json(R.BENCH, "peaks.json")["TPU v5 lite"]
    out = R.run_cell(r)
    json.dumps(out)
    return out


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_cell_runs_and_is_correct(seed):
    out = run_toy(seed=seed)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_control_is_not_correct():
    # limits/toy-mla.decode.json has the readings: 1024 rows, so that the
    # router's flips average out whatever step count the machine's load gives
    out = run_toy(seed=3, probe="int8-weights")
    assert not out["correct"]
    bad = [c["name"] for c in out["checks"] if not c["ok"]]
    assert bad == ["logit_excess_error"]


def test_traced_run_reads_the_expert_counters():
    out = run_toy(trace=1, seconds=6.0)
    assert {"host_syncs_per_token", "batch_occupancy_mean",
            "moe_pairs_per_touched_expert"} <= set(out["metrics"])
    assert out["metrics"]["moe_pairs_per_touched_expert"]["value"] >= 1.0


def test_readers_find_nothing_without_the_counters():
    """On a program that keeps no expert counters (the parent commit, or
    another model family) the new readers return nothing and do not
    raise."""
    import importlib.util

    ctx = {"counters": {"tokens_generated": 5.0}, "steps": [(0, 1, 4, 9, 4)],
           "config": {"hidden_size": 8}, "trace": None, "trace_span": (0, 1)}
    for name in ("moe_pairs_per_touched_expert", "mla_attn_roofline"):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(R.BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(ctx) is None
