"""The command end to end on the CPU for the hybrid family at toy width
(bench/tests/data/toy-hybrid: hidden 128, two periods of three Gated
DeltaNet layers and one full-attention layer, 4 heads, a state of 16 x 32 a
head), under `closed-serve` with requests that end and slots that are
re-taken inside the window, as test_run_cpu_mla.py does for the latent
family: the sound engine is correct against reference_olmo_hybrid, its
control (int8 matrices) and an altered token are not; the operation counts
on hand-worked shapes; each new reader on a small hand-made table, the
off-count cases that must read nothing among them."""
import importlib.util
import json
import os
import statistics
import types

import jax
import pytest

import opcount_hybrid as oh
import run as R
import trace_reduce as tr

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "toy-hybrid")
PEAKS = R.load_json(R.BENCH, "peaks.json")["TPU v5 lite"]


def run_toy(seed=3, seconds=2.0, trace=0, probe=None):
    argv = ["--workload", "toy-hybrid.decode", "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    if probe:
        argv += ["--probe", probe]
    r = R.Run(R.parse(argv), R.load_json(TOY, "BENCHMARK.json"), files=TOY)
    r.devices = jax.devices()[:1]
    r.peaks = PEAKS
    out = R.run_cell(r)
    json.dumps(out)
    return out


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_cell_runs_and_is_correct(seed):
    out = run_toy(seed=seed)
    assert out["correct"] and out["failed"] == 0
    # requests ended inside the window: their slots were taken again
    assert out["attempted"] > 4
    assert out["compiles_in_window"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_control_is_not_correct():
    out = run_toy(seed=3, probe="int8-weights")
    assert not out["correct"]
    bad = [c["name"] for c in out["checks"] if not c["ok"]]
    assert bad == ["logit_excess_error"]


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the engine's greedy pass."""
    from paddle_tpu.serving import engine

    real = engine.greedy_grid

    def off_by_one(logits):
        am, fin = real(logits)
        return (am + 1) % logits.shape[-1], fin

    monkeypatch.setattr(engine, "greedy_grid", off_by_one)
    assert not run_toy()["correct"]


def test_traced_run_reads_the_prefill_counters():
    out = run_toy(trace=1, seconds=6.0)
    assert {"host_syncs_per_token", "batch_occupancy_mean",
            "delta_prefill_real_share"} <= set(out["metrics"])
    # prompts of 8..40 tokens in buckets of 8..64
    assert 40.0 < out["metrics"]["delta_prefill_real_share"]["value"] <= 100.0


# ------------------------------------------------------ operation counts

PUBLISHED = {"num_hidden_layers": 16, "hidden_size": 3840,
             "layer_types": ["linear_attention"] * 3 + ["full_attention"],
             "linear_num_key_heads": 30, "linear_num_value_heads": 30,
             "linear_key_head_dim": 96, "linear_value_head_dim": 192,
             "linear_conv_kernel_dim": 4}
PUBLISHED["layer_types"] = PUBLISHED["layer_types"] * 8      # as published


def test_counts_on_hand_worked_shapes():
    cfg = PUBLISHED
    # the cut runs the first 16 kinds of the published 32
    assert (oh.linear_layers(cfg), oh.full_layers(cfg)) == (12, 4)
    assert oh.state_bytes(cfg) == 30 * 96 * 192 * 4 == 2_211_840
    # 3 rows of 2880 + 2880 + 5760 bf16 values
    assert oh.conv_window_bytes(cfg, 2) == 3 * 11520 * 2 == 69_120
    # 64 sequences, 12 layers, one step: each state and window in and out
    assert oh.delta_decode_bytes(cfg, 64 * 12, 2) == \
        768 * 2 * (2_211_840 + 69_120) == 3_503_554_560
    assert oh.delta_rule_flops_per_token(cfg) == \
        12 * 30 * 6 * 96 * 192 == 39_813_120
    # 4 layers x (K and V) x 3840 values x 2 B a cached token
    assert oh.attention_kv_bytes(cfg, 1000, 2) == 4 * 2 * 3840 * 2 * 1000
    tiny = dict(cfg, num_hidden_layers=4, linear_num_value_heads=2,
                linear_num_key_heads=2, linear_key_head_dim=4,
                linear_value_head_dim=8, hidden_size=16)
    assert oh.state_bytes(tiny) == 2 * 4 * 8 * 4
    assert oh.conv_window_bytes(tiny, 2) == 3 * (8 + 8 + 16) * 2
    assert oh.delta_rule_flops_per_token(tiny) == 3 * 2 * 6 * 4 * 8
    assert oh.attention_kv_bytes(tiny, 10, 2) == 1 * 2 * 16 * 2 * 10


# ----------------------------------------------- readers on small tables

OFFSET = 5_000_000_321            # trace clock = bench clock + OFFSET (ns)
TOY_CFG = {"num_hidden_layers": 4, "hidden_size": 16,
           "layer_types": ["linear_attention"] * 3 + ["full_attention"],
           "linear_num_key_heads": 2, "linear_num_value_heads": 2,
           "linear_key_head_dim": 4, "linear_value_head_dim": 8,
           "linear_conv_kernel_dim": 4}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(R.BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def table(decode_kernels=4, loops=3):
    """Four engine steps of 1000 ns on the trace's clock; the two middle
    ones are whole. Step 1: a prefill run (its `while` loops 40 ns each)
    and a decode run; step 2: a decode run. A decode run's Mosaic calls:
    10 ns each in the linear layers, 30 ns in the full layer."""
    ops, modules, host = [], [], []
    for s in range(4):
        t = s * 1000
        host.append(("bench.engine_step", t, 900))
        if s == 1:
            modules.append(("_prefill_step", t + 10, 300))
            ops += [("while", t + 20 + 50 * j, 40) for j in range(loops)]
            ops.append(("fusion", t + 250, 20))
        modules.append(("_decode_step", t + 400, 400))
        for j in range(decode_kernels):
            ops.append(("mosaic:_decode_step", t + 410 + 50 * j,
                        30 if j == 3 else 10))
        ops.append(("fusion", t + 700, 50))
    return tr.clip(tr.Trace(ops={0: ops}, modules={0: modules}, host=host),
                   0, 4000)


def ctx_for(trace, prompts=(24,)):
    # (t0, t1, events, context_tokens, decoding)
    steps = [((s * 1000 - OFFSET) / 1e9, (s * 1000 + 900 - OFFSET) / 1e9,
              2, 100 * (s + 1), 2) for s in range(4)]
    lives = [types.SimpleNamespace(
        sched=steps[1][0], req=types.SimpleNamespace(prompt=(0,) * n))
        for n in prompts]
    return {"trace": trace, "steps": steps, "lives": lives,
            "median": statistics.median, "config": TOY_CFG, "peaks": PEAKS,
            "counters": {"delta_decode_seq_steps": 24.0},
            "trace_span": (steps[0][0] - 1e-7, steps[-1][1] + 1e-7)}


def test_readers_on_a_hand_made_table():
    ctx = ctx_for(table())
    hbm, flops = PEAKS["hbm_bytes_per_s"], PEAKS["bf16_flops_per_s"]
    # whole decode runs: steps 1 and 2 (the first and last run recorded
    # are never whole); 2 live rows x 3 linear layers each, 30 ns of the
    # update's kernels a run
    want = 100 * (2 * 2 * 3 * 2 * (oh.state_bytes(TOY_CFG)
                                   + oh.conv_window_bytes(TOY_CFG, 2))
                  / hbm) / 60e-9
    assert reader("delta_state_roofline")(ctx) == pytest.approx(want)
    # contexts 200 + 300 tokens, 30 ns of the attention kernel a run
    want = 100 * (oh.attention_kv_bytes(TOY_CFG, 500, 2) / hbm) / 60e-9
    assert reader("hybrid_attn_roofline")(ctx) == pytest.approx(want)
    # one whole prefill run, 24 real tokens, three loops of 40 ns
    want = 100 * (24 * oh.delta_rule_flops_per_token(TOY_CFG) / flops) \
        / 120e-9
    assert reader("delta_prefill_roofline")(ctx) == pytest.approx(want)


def test_readers_read_nothing_where_the_count_is_off():
    # a decode run with a Mosaic call too few: not one a layer
    ctx = ctx_for(table(decode_kernels=3))
    assert reader("delta_state_roofline")(ctx) is None
    assert reader("hybrid_attn_roofline")(ctx) is None
    # a prefill run with a loop too many
    assert reader("delta_prefill_roofline")(ctx_for(table(loops=4))) is None
    # two first tokens in a step that holds one prefill run
    assert reader("delta_prefill_roofline")(
        ctx_for(table(), prompts=(24, 9))) is None


def test_readers_read_nothing_on_another_program():
    """On a program without the counters (the parent commit, another
    family) the new readers return nothing and do not raise."""
    ctx = {"counters": {"tokens_generated": 5.0}, "steps": [(0, 1, 4, 9, 4)],
           "lives": [], "config": {"hidden_size": 8}, "trace": None,
           "trace_span": (0, 1), "peaks": PEAKS}
    for name in ("delta_state_roofline", "delta_prefill_roofline",
                 "hybrid_attn_roofline", "delta_prefill_real_share"):
        assert reader(name)(ctx) is None
