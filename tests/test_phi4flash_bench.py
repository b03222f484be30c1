"""What the benchmark adds for the phi-4-mini-flash configuration, without a
chip: `phi-4-mini-flash.reason-12k`'s command end to end on the CPU at toy
width (bench/tests/data/toy-phi4flash: hidden 128, 8 layers so that every
kind is there, 4 query heads on 2 key/value heads of 32, a window of 32, a
state of 8 x 256), as tests/test_zaya_cell.py does for its family: the sound
run is correct against reference_phi4flash and its traced line carries the
counters' metric, the control and an altered token are not correct; the
operation counts on hand-worked shapes; each new reader on a small hand-made
table, the off-count cases that must read nothing among them; the files the
cell is found by."""
import importlib.util
import json
import os
import statistics
import sys
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

import opcount_phi4flash as op  # noqa: E402
import run as R  # noqa: E402
import trace_reduce as tr  # noqa: E402

TOY = os.path.join(BENCH, "tests", "data", "toy-phi4flash")
PEAKS = R.load_json(BENCH, "peaks.json")["TPU v5 lite"]
CELL = "phi-4-mini-flash.reason-12k"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_cfg():
    c = load("bench", "configs", "phi-4-mini-flash.json")
    return {a: c[k] for a, k in c["program"]["args"].items()}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_toy(seed=3, seconds=2.0, trace=0, probe=None):
    argv = ["--workload", "toy-phi4flash.reason", "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    if probe:
        argv += ["--probe", probe]
    r = R.Run(R.parse(argv), R.load_json(TOY, "BENCHMARK.json"), files=TOY)
    r.devices = jax.devices()[:1]
    r.peaks = PEAKS
    out = R.run_cell(r)
    json.dumps(out)
    return out


# ------------------------------------------------------- the cell's command


def test_sound_run_is_correct_and_reads_its_counters():
    out = run_toy(seed=2**31 + 5, trace=1, seconds=6.0)
    assert out["correct"] and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    # requests ended inside the window: their slots were taken again
    assert out["attempted"] > 4
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"host_syncs_per_token", "batch_occupancy_mean",
            "window_pages_held_share"} <= set(m)
    # 32 / 4 + 1 = 9 pages of a ring against contexts of 8..120 tokens
    assert 20.0 < m["window_pages_held_share"] <= 100.0
    # no device plane on the CPU: the trace's readers read nothing
    assert not {"shared_kv_attn_roofline", "window_attn_roofline",
                "ssm_state_roofline"} & set(m)


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


# ------------------------------------------------------ operation counts


def test_counts_at_the_published_widths():
    cfg = cell_cfg()
    kinds = op.layer_kinds(cfg)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ["mamba", "full", "gmu", "cross"]
    # a decode run's Mosaic calls: every layer but the 7 memory units
    assert len(op.kernel_layers(cfg)) == 25
    # K and V of 20 heads of 64 in bfloat16
    assert op.kv_token_bytes(cfg, 2) == 2 * 20 * 64 * 2 == 5120
    # the shared cache at 1000 cached tokens: 8 layers read it
    assert op.shared_kv_bytes(cfg, 1000, 2) == 8 * 5120 * 1000
    # 48 sequences far past the window: 512 keys each in 8 layers
    assert op.window_kv_bytes(cfg, 48, 7200.0, 2) == 8 * 5120 * 512 * 48
    assert op.window_kv_bytes(cfg, 2, 100.0, 2) == 8 * 5120 * 100 * 2
    assert op.scan_state_bytes(cfg) == 5120 * 16 * 4 == 327_680
    assert op.conv_rows_bytes(cfg, 2) == 3 * 5120 * 2 == 30_720
    assert op.scan_decode_bytes(cfg, 48 * 9) == 432 * 2 * 327_680
    assert op.scan_flops_per_token(cfg) == 9 * 6 * 5120 * 16
    assert op.attention_flops_per_key(cfg) == 40 * 6 * 64
    per = op.parameters(cfg)
    # the issue's count: 119.9 / 98.3 / 104.9 / 91.75 M a layer, 512.2 M
    # two norms and the MLP 78,653,440; W_in 26,214,400, the filter and
    # its bias 25,600, W_x 983,040, W_dt and its bias 824,320, A_log and D
    # 87,040, W_out 13,107,200
    assert per["mamba"] == 78_653_440 + 26_214_400 + 25_600 + 983_040 \
        + 824_320 + 87_040 + 13_107_200 == 119_895_040
    assert round(per["window"] / 1e6, 1) == 98.3 == round(per["full"] / 1e6, 1)
    assert round(per["gmu"] / 1e6, 1) == 104.9
    assert round(per["cross"] / 1e6, 2) == 91.77
    assert round(op.total_parameters(cfg) / 1e6) == 3853


def test_parameter_count_is_the_models_own():
    """opcount's count from shapes against the Layer's parameters, at the
    toy width."""
    from paddle_tpu.models.phi4flash import (
        Phi4FlashConfig, Phi4FlashForCausalLM,
    )

    c = load("bench", "tests", "data", "toy-phi4flash", "configs",
             "toy-phi4flash.json")
    cfg = {a: c[k] for a, k in c["program"]["args"].items()}
    model = Phi4FlashForCausalLM(Phi4FlashConfig(**dict(
        cfg, dtype="float32", init="normal")))
    have = sum(int(p._value.size) for _, p in model.named_parameters())
    assert op.total_parameters(cfg) == have


# ----------------------------------------------- readers on small tables

OFFSET = 5_000_000_321            # trace clock = bench clock + OFFSET (ns)
TOY_CFG = {"num_hidden_layers": 8, "hidden_size": 32,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "sliding_window": 8, "mb_per_layer": 2, "mamba_d_state": 4,
           "mamba_d_conv": 4, "mamba_expand": 2}
# a decode run's Mosaic calls by layer: mamba window mamba window mamba
# full (gmu: none) cross
NS = {"mamba": 10, "window": 20, "full": 30, "cross": 50}


def table(drop=0):
    """Four engine steps of 1000 ns on the trace's clock; the two middle
    decode runs are whole."""
    ops, modules, host = [], [], []
    for s in range(4):
        t = s * 1000
        host.append(("bench.engine_step", t, 900))
        modules.append(("_decode_step", t + 100, 700))
        layers = op.kernel_layers(TOY_CFG)
        for j, kind in enumerate(layers[:len(layers) - drop]):
            ops.append(("mosaic:_decode_step", t + 110 + 60 * j, NS[kind]))
        ops.append(("fusion", t + 700, 50))
    return tr.clip(tr.Trace(ops={0: ops}, modules={0: modules}, host=host),
                   0, 4000)


def ctx_for(trace, counters=None):
    # (t0, t1, events, context_tokens, decoding)
    steps = [((s * 1000 - OFFSET) / 1e9, (s * 1000 + 900 - OFFSET) / 1e9,
              2, 100 * (s + 1), 2) for s in range(4)]
    return {"trace": trace, "steps": steps, "lives": [],
            "median": statistics.median, "config": TOY_CFG, "peaks": PEAKS,
            "counters": {"ssm_decode_seq_steps": 12.0,
                         "window_pages_held": 30.0,
                         "window_pages_whole_context": 120.0}
            if counters is None else counters,
            "trace_span": (steps[0][0] - 1e-7, steps[-1][1] + 1e-7)}


def test_readers_on_a_hand_made_table():
    ctx = ctx_for(table())
    hbm = PEAKS["hbm_bytes_per_s"]
    assert op.kernel_layers(TOY_CFG) == ["mamba", "window", "mamba",
                                         "window", "mamba", "full", "cross"]
    # whole decode runs: steps 1 and 2, contexts 200 + 300 tokens; the
    # full and the cross layer read them, 30 + 50 ns a run
    want = 100 * (op.shared_kv_bytes(TOY_CFG, 500, 2) / hbm) / 160e-9
    assert reader("shared_kv_attn_roofline")(ctx) == pytest.approx(want)
    # 2 sequences a step, contexts far past the window of 8: 2 layers
    want = 100 * (2 * op.window_kv_bytes(TOY_CFG, 2, 100.0, 2) / hbm) / 80e-9
    assert op.window_kv_bytes(TOY_CFG, 2, 100.0, 2) == 2 * 64 * 8 * 2
    assert reader("window_attn_roofline")(ctx) == pytest.approx(want)
    # 2 live rows x 3 scan layers x 2 steps, 30 ns of the update a run
    want = 100 * (op.scan_decode_bytes(TOY_CFG, 12) / hbm) / 60e-9
    assert reader("ssm_state_roofline")(ctx) == pytest.approx(want)
    assert reader("window_pages_held_share")(ctx) == pytest.approx(25.0)


def test_readers_read_nothing_where_the_count_is_off():
    # a decode run with a Mosaic call too few: not one a kernel layer
    ctx = ctx_for(table(drop=1))
    for name in ("shared_kv_attn_roofline", "window_attn_roofline",
                 "ssm_state_roofline"):
        assert reader(name)(ctx) is None


def test_readers_read_nothing_on_another_program():
    """On a program without the counters or the configuration's keys (the
    parent commit, another family) the new readers return nothing and do
    not raise."""
    ctx = {"counters": {"tokens_generated": 5.0}, "steps": [(0, 1, 4, 9, 4)],
           "lives": [], "config": {"hidden_size": 8}, "trace": None,
           "trace_span": (0, 1), "peaks": PEAKS}
    for name in ("shared_kv_attn_roofline", "window_attn_roofline",
                 "ssm_state_roofline", "window_pages_held_share"):
        assert reader(name)(ctx) is None
    # this family's configuration, a trace of another program's runs
    ctx = ctx_for(table(), counters={})
    ctx["config"] = {"hidden_size": 8, "layer_types": []}
    assert reader("shared_kv_attn_roofline")(ctx) is None
    assert reader("ssm_state_roofline")(ctx) is None


# ------------------------------------------------------ the cell's files


def test_the_cell_is_found_by_its_files():
    manifest = load("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["chips"] == 1
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cells[CELL]["config"])
    assert entry["reduced"] == []
    cfg = load(entry["file"])
    # every published width as published
    for key, value in {"hidden_size": 2560, "num_attention_heads": 40,
                       "num_key_value_heads": 20, "intermediate_size": 10240,
                       "sliding_window": 512, "num_hidden_layers": 32,
                       "vocab_size": 200064, "mb_per_layer": 2}.items():
        assert cfg[key] == value == cfg["published"][key]
    assert all(cfg[k] == v for k, v in cfg["published"].items())
    traffic = load("bench", "traffic", cells[CELL]["traffic"] + ".json")
    assert (traffic["clients"], traffic["max_batch_size"],
            traffic["output_tokens"]) == (48, 48, 4096)
    limits = load("bench", "limits", CELL + ".json")
    assert {"served_logit_gap_max", "served_not_best_share",
            "logit_excess_error", "why"} <= set(limits)
    named = {m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {"shared_kv_attn_roofline", "window_attn_roofline",
            "ssm_state_roofline", "window_pages_held_share",
            "decode_step_dev_ms", "setup_compile_s"} <= named
    for name in named:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # the bytes the issue reckoned: 48 x 16384 tokens of one layer's K/V
    import traffic_gen
    assert traffic_gen.pool_blocks(traffic) == 48 * 1024 + 64
    assert 48 * 16384 * op.kv_token_bytes(cell_cfg(), 2) == 4_026_531_840
