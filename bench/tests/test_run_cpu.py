"""The command end to end at toy width on the CPU, past the look for a chip:
every traffic kind, the four-device layout on virtual devices, each control
(a lower precision in the program's place) and each broken timed path, which
must all come out as not correct."""
import json
import os
import subprocess
import sys

import jax
import pytest

import run as R

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "toy")


def run_toy(workload, seed=3, seconds=2.0, trace=0, probe=None):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if probe:
        argv += ["--probe", probe]
    r = R.Run(R.parse(argv), R.load_json(TOY, "BENCHMARK.json"), files=TOY)
    r.devices = jax.devices()[:r.cell["chips"]]
    r.peaks = R.load_json(R.BENCH, "peaks.json")["TPU v5 lite"]
    out = R.run_cell(r)
    json.dumps(out)                     # the result line is JSON
    return out


@pytest.mark.parametrize("workload,metric", [
    ("toy.decode", "serve_tokens_per_s"), ("toy.chat", "itl_p95_ms"),
    ("toy.train", "train_tokens_per_s"),
    ("toy.train-4chip", "train_tokens_per_s")])
def test_cell_runs_and_is_correct(workload, metric):
    out = run_toy(workload, seed=2**31 + 5)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    assert out["metrics"][metric]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["device"]["count"] == (4 if workload.endswith("4chip") else 1)


def test_traced_run_reports_per_layer_metrics():
    out = run_toy("toy.decode", trace=1, seconds=6.0)  # profiler on 1 s..5 s
    assert {"host_syncs_per_token", "engine_step_p50_ms",
            "batch_occupancy_mean"} <= set(out["metrics"])
    assert "setup_s" not in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


def test_deep_toy_is_correct():
    assert run_toy("toy-deep.decode", seed=7)["correct"]


# fp8-kv is a control on the chip only: at toy depth on the CPU it reads too
# close to the sound runs (limits/toy-deep.decode.json says what it reads)
@pytest.mark.parametrize("workload,probe", [
    ("toy-deep.decode", "int8-weights"), ("toy.train", "ref-bfloat16")])
def test_control_is_not_correct(workload, probe):
    assert not run_toy(workload, probe=probe)["correct"]


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the engine's greedy pass."""
    from paddle_tpu.serving import engine

    real = engine.greedy_grid

    def off_by_one(logits):
        am, fin = real(logits)
        return (am + 1) % logits.shape[-1], fin

    monkeypatch.setattr(engine, "greedy_grid", off_by_one)
    assert not run_toy("toy.decode")["correct"]


def test_step_that_keeps_its_state_is_not_correct(monkeypatch):
    """A training step that returns its state unchanged."""
    from paddle_tpu.jit import api

    real = api.TrainStep.__call__

    def frozen(self, *batch):
        keep = (self.params, self.buffers, self.opt_state)
        copy = jax.tree_util.tree_map(lambda x: x.copy(), keep)
        loss = real(self, *batch)
        self.params, self.buffers, self.opt_state = copy
        return loss

    monkeypatch.setattr(api.TrainStep, "__call__", frozen)
    assert not run_toy("toy.train")["correct"]


def test_no_tpu_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(R.BENCH, "run.py"), "--workload",
         "gpt2-124m.train", "--seed", "1", "--seconds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
