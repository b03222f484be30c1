"""What the benchmark adds for the Laguna configuration, without a chip:
`laguna-xs.2.agent-8k`'s command end to end on the CPU at toy width
(bench/tests/data/toy-laguna: hidden 128, the dense layer and one whole
period, 6 and 8 query heads on 2 key/value heads of 32, a window of 32, 64
experts), under `closed-serve` with requests that end and slots, pages and
rings that are re-taken inside the window: the sound run is correct against
reference_laguna and its traced line carries the counters' metrics, the
control and an altered token are not correct; the operation counts on
hand-worked shapes; each new reader on a small hand-made table, the
off-count cases that must read nothing among them; the files the cell is
found by.

The toy's router selects EVERY expert (64 of 64), where the cell's selects
8 of 256: a selection that differs between a bfloat16 and a float32 forward,
in the program and in the bfloat16 reference alike and not in the same rows,
swaps whole experts and drowns a lower precision's error at 64 rows (read
at 4 of 64: sound -0.47..0.74, int8 -0.19..1.00, on five seeds); with all
selected the sound engine reads -0.05 and the controls 1.4-2.4.
tests/test_laguna.py has the selection."""
import importlib.util
import json
import os
import statistics
import types

import jax
import pytest

import laguna_trace
import opcount_laguna as op
import run as R
import trace_reduce as tr

ROOT = os.path.dirname(R.BENCH)
TOY = os.path.join(R.BENCH, "tests", "data", "toy-laguna")
PEAKS = R.load_json(R.BENCH, "peaks.json")["TPU v5 lite"]
CELL = "laguna-xs.2.agent-8k"
NEW = ("laguna_weights_roofline", "laguna_full_attn_roofline",
       "laguna_window_attn_roofline", "laguna_decode_block_fill",
       "laguna_prefill_ms_per_ktoken")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_cfg():
    c = load("bench", "configs", "laguna-xs.2.json")
    return {a: c[k] for a, k in c["program"]["args"].items()}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(R.BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_toy(seed=3, seconds=2.0, trace=0, probe=None):
    argv = ["--workload", "toy-laguna.agent", "--seed", str(seed),
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
            "moe_pairs_per_touched_expert", "window_pages_held_share",
            "laguna_decode_block_fill"} <= set(m)
    # 4 rows a step, every one of 64 experts chosen by each: 4 pairs an
    # expert in a block of 16 rows
    assert m["moe_pairs_per_touched_expert"] == pytest.approx(4.0, abs=1.0)
    assert m["laguna_decode_block_fill"] == pytest.approx(25.0, abs=6.5)
    # 32 / 16 + 1 = 3 pages of a ring against contexts of 8..120 tokens
    assert 20.0 < m["window_pages_held_share"] <= 100.0
    # no device plane on the CPU: the trace's readers read nothing
    assert not {"laguna_weights_roofline", "laguna_full_attn_roofline",
                "laguna_window_attn_roofline",
                "laguna_prefill_ms_per_ktoken"} & set(m)


@pytest.mark.parametrize("probe", ["int8-weights", "fp8-kv"])
def test_control_is_not_correct(probe):
    out = run_toy(seed=3, probe=probe)
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
    assert op.layer_kinds(cfg) == [op.FULL] + [op.SLIDING] * 3 + [op.FULL]
    assert op.sparse_layers(cfg) == [False, True, True, True, True]
    # K and V of 8 heads of 128 in bfloat16, whatever the query heads
    assert op.kv_token_bytes(cfg, 2) == 2 * 8 * 128 * 2 == 4096
    # 64 sequences at a mean context of 5600: two full layers read them
    assert op.full_kv_bytes(cfg, 64 * 5600, 2) == 2 * 4096 * 358_400 \
        == 2_936_012_800
    # far past the window: 512 keys each in the three sliding layers
    assert op.window_kv_bytes(cfg, 64, 5600.0, 2) == 3 * 4096 * 512 * 64 \
        == 402_653_184
    assert op.window_kv_bytes(cfg, 2, 100.0, 2) == 3 * 4096 * 100 * 2
    assert op.expert_bytes(cfg, 2) == 3 * 2048 * 512 * 2 == 6_291_456
    # W_q W_k W_v the gates W_o: 48 heads, then 64
    assert op.attention_parameters(cfg, 0) == 2048 * 6144 + 2 * 2048 * 1024 \
        + 2048 * 48 + 6144 * 2048 == 29_458_432
    assert op.attention_parameters(cfg, 1) == 2 * 2048 * 8192 \
        + 2 * 2048 * 1024 + 2048 * 64 == 37_879_808
    per = op.parameters(cfg)
    assert per["layers"] == [79_794_176, 846_860_288, 846_860_288,
                             846_860_288, 838_438_912]
    assert per["embedding"] + per["head"] == 411_041_792
    assert op.total_parameters(cfg) == 3_869_857_792
    assert op.total_parameters(dict(cfg, num_hidden_layers=40)) \
        == 33_442_596_864
    # what a decode step reads whatever the routing: the head, five layers'
    # attention, the dense SwiGLU, four routers and shared experts
    dense = (2048 * 100352 + 2 * 29_458_432 + 3 * 37_879_808
             + 3 * 2048 * 8192 + 4 * (2048 * 256 + 3 * 2048 * 512)) * 2
    assert op.dense_decode_weight_bytes(cfg, 2) == dense == 886_177_792
    # 221 of 256 experts touched a layer (uniform routing of 512 pairs)
    assert op.decode_weight_bytes(cfg, 2, 4 * 221) == dense \
        + 884 * 6_291_456 == 6_447_824_896


def test_parameter_count_is_the_models_own():
    """opcount's count from shapes against the Layer's parameters, at the
    toy width."""
    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM

    c = load("bench", "tests", "data", "toy-laguna", "configs",
             "toy-laguna.json")
    cfg = {a: c[k] for a, k in c["program"]["args"].items()}
    model = LagunaForCausalLM(LagunaConfig(**dict(
        cfg, dtype="float32", init="normal")))
    have = sum(int(p._value.size) for _, p in model.named_parameters())
    assert op.total_parameters(cfg) == have


# ----------------------------------------------- readers on small tables

OFFSET = 5_000_000_321            # trace clock = bench clock + OFFSET (ns)
TOY_CFG = {"num_hidden_layers": 5, "hidden_size": 16, "head_dim": 4,
           "num_key_value_heads": 2, "vocab_size": 32,
           "intermediate_size": 24, "num_experts": 8,
           "moe_intermediate_size": 4, "shared_expert_intermediate_size": 4,
           "sliding_window": 8,
           "layer_types": [op.FULL] + [op.SLIDING] * 3 + [op.FULL] * 2,
           "mlp_layer_types": ["dense"] + ["sparse"] * 5,
           "num_attention_heads_per_layer": [6, 8, 8, 8, 6, 8]}
ATTN_NS = {op.FULL: 30, op.SLIDING: 10}


def table(grouped=True, drop=0, pieces=2):
    """Four engine steps of 2000 ns on the trace's clock; the two middle
    decode runs are whole. A decode run's Mosaic calls, a layer at a time:
    the attention kernel (30 ns on a full layer, 10 on a sliding one), then
    on an expert layer under the grouped walk three products of 5 ns. Step
    1 also holds a prompt's prefill: the ring's load, `pieces` pieces of 100
    ns (a kernel in each), the head, the ring's store."""
    ops, modules, host = [], [], []
    for s in range(4):
        t = s * 2000
        host.append(("bench.engine_step", t, 1900))
        if s == 1:
            at = t + 10
            for name, ns in ([("_ring_load", 10)]
                             + [("_prefill_piece", 100)] * pieces
                             + [("_piece_head", 20), ("_ring_store", 10)]):
                modules.append((name, at, ns))
                if name == "_prefill_piece":
                    ops.append(("mosaic:_prefill_piece", at + 5, 40))
                    ops.append(("fusion", at + 50, 50))
                else:
                    ops.append(("fusion", at, ns))
                at += ns + 5
        modules.append(("_decode_step", t + 600, 1200))
        at, calls = t + 610, []
        for kind, sparse in zip(op.layer_kinds(TOY_CFG),
                                op.sparse_layers(TOY_CFG)):
            calls.append(ATTN_NS[kind])
            if sparse and grouped:
                calls += [5, 5, 5]
        for ns in calls[:len(calls) - drop]:
            ops.append(("mosaic:_decode_step", at, ns))
            at += 40
        ops.append(("fusion", t + 1700, 50))
    return tr.clip(tr.Trace(ops={0: ops}, modules={0: modules}, host=host),
                   0, 8000)


def ctx_for(trace, prompts=(3000,), counters=None):
    # (t0, t1, events, context_tokens, decoding)
    steps = [((s * 2000 - OFFSET) / 1e9, (s * 2000 + 1900 - OFFSET) / 1e9,
              2, 100 * (s + 1), 2) for s in range(4)]
    lives = [types.SimpleNamespace(
        sched=steps[1][0], req=types.SimpleNamespace(prompt=(0,) * n))
        for n in prompts]
    return {"trace": trace, "steps": steps, "lives": lives,
            "median": statistics.median, "config": TOY_CFG, "peaks": PEAKS,
            "counters": {"moe_decode_experts_touched": 40.0,
                         "moe_decode_pairs": 96.0,
                         "moe_decode_rows_multiplied": 640.0}
            if counters is None else counters,
            "trace_span": (steps[0][0] - 1e-7, steps[-1][1] + 1e-7)}


@pytest.mark.parametrize("grouped", [True, False])
def test_readers_on_a_hand_made_table(grouped):
    """The loop walk and the grouped walk read the same attention, and the
    grouped products stay in the weights' denominator."""
    ctx = ctx_for(table(grouped))
    hbm = PEAKS["hbm_bytes_per_s"]
    kinds = op.layer_kinds(TOY_CFG)
    assert (kinds.count(op.FULL), kinds.count(op.SLIDING)) == (2, 3)
    # whole decode runs: steps 1 and 2, contexts 200 + 300 tokens; the two
    # full layers read them, 30 + 30 ns a run
    want = 100 * (op.full_kv_bytes(TOY_CFG, 500, 2) / hbm) / 120e-9
    assert reader("laguna_full_attn_roofline")(ctx) == pytest.approx(want)
    # 2 sequences a step, contexts far past the window of 8: 3 layers
    assert op.window_kv_bytes(TOY_CFG, 2, 100.0, 2) == 3 * 32 * 8 * 2
    want = 100 * (2 * op.window_kv_bytes(TOY_CFG, 2, 100.0, 2) / hbm) / 60e-9
    assert reader("laguna_window_attn_roofline")(ctx) == pytest.approx(want)
    # a run's busy time: the attention's 90 ns, the fusion's 50, and under
    # the grouped walk 4 expert layers x 3 products of 5 ns; less the
    # attention: what reads the weights. 40 experts touched in 4 steps
    busy = 90 + 50 + (60 if grouped else 0)
    want = 100 * (op.decode_weight_bytes(TOY_CFG, 2, 10.0) / hbm) \
        / ((busy - 90) * 1e-9)
    assert reader("laguna_weights_roofline")(ctx) == pytest.approx(want)
    assert reader("laguna_decode_block_fill")(ctx) == pytest.approx(15.0)
    # one prompt of 3000 tokens in two pieces: 10 + 2 x 90 + 20 + 10 ns busy
    assert reader("laguna_prefill_ms_per_ktoken")(ctx) == pytest.approx(
        220e-6 / 3.0)


def test_readers_read_nothing_where_the_count_is_off():
    # a decode run with a Mosaic call too few: neither walk's count
    ctx = ctx_for(table(drop=1))
    for name in ("laguna_weights_roofline", "laguna_full_attn_roofline",
                 "laguna_window_attn_roofline"):
        assert reader(name)(ctx) is None
    # a prompt of 3000 tokens needs two pieces: a record with three is
    # left out, and nothing is left
    assert reader("laguna_prefill_ms_per_ktoken")(
        ctx_for(table(pieces=3))) is None
    # two prompts of two pieces each in a step that ran two
    assert reader("laguna_prefill_ms_per_ktoken")(
        ctx_for(table(), prompts=(3000, 2500))) is None


def test_attention_calls_are_told_by_their_place():
    cfg = TOY_CFG
    mk = lambda ns: [("mosaic:_decode_step", i, n) for i, n in enumerate(ns)]
    loop = mk([30, 10, 10, 10, 30])
    assert [e[2] for e in laguna_trace.attention_calls(cfg, loop)] \
        == [30, 10, 10, 10, 30]
    grouped = mk([30, 10, 5, 5, 5, 10, 5, 5, 5, 10, 5, 5, 5, 30, 5, 5, 5])
    assert [e[2] for e in laguna_trace.attention_calls(cfg, grouped)] \
        == [30, 10, 10, 10, 30]
    assert laguna_trace.attention_calls(cfg, grouped[:-1]) is None
    assert laguna_trace.is_prefill("_prefill_piece") \
        and laguna_trace.is_prefill("_ring_store") \
        and not laguna_trace.is_prefill("_decode_step")


def test_readers_read_nothing_on_another_program():
    """On a program without the counters or the configuration's keys (the
    parent commit, another family) the new readers return nothing and do
    not raise."""
    ctx = {"counters": {"tokens_generated": 5.0}, "steps": [(0, 1, 4, 9, 4)],
           "lives": [], "config": {"hidden_size": 8}, "trace": None,
           "trace_span": (0, 1), "peaks": PEAKS, "median": statistics.median}
    for name in NEW:
        assert reader(name)(ctx) is None
    # this family's counters, a trace of another program's runs
    other = ctx_for(table())
    other["config"] = {"hidden_size": 8, "layer_types": []}
    for name in NEW[:3] + NEW[4:]:
        assert reader(name)(other) is None


# ------------------------------------------------------ the cell's files


def test_the_cell_is_found_by_its_files():
    manifest = load("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["chips"] == 1
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cells[CELL]["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/poolside/Laguna-XS.2/"
                               "blob/main/config.json")
    cfg = load(entry["file"])
    # every published width as published; the depth is the one cut
    for key, value in {"hidden_size": 2048, "num_attention_heads": 48,
                       "num_key_value_heads": 8, "head_dim": 128,
                       "intermediate_size": 8192, "num_experts": 256,
                       "num_experts_per_tok": 8,
                       "moe_intermediate_size": 512,
                       "shared_expert_intermediate_size": 512,
                       "sliding_window": 512, "vocab_size": 100352}.items():
        assert cfg[key] == value == cfg["published"][key]
    assert all(cfg[k] == v for k, v in cfg["published"].items()
               if k != "num_hidden_layers")
    assert (cfg["num_hidden_layers"],
            cfg["published"]["num_hidden_layers"]) == (5, 40)
    assert {"published", "reduced_how", "deployment", "parameters",
            "assumed", "precision"} <= set(cfg)
    traffic = load("bench", "traffic", cells[CELL]["traffic"] + ".json")
    assert {k: traffic[k] for k in (
        "kind", "clients", "max_batch_size", "output_tokens", "stagger",
        "pool_tokens_per_slot", "pool_slack_blocks", "check_requests",
        "check_steps", "check_slots", "trace_at")} == {
            "kind": "closed-serve", "clients": 64, "max_batch_size": 64,
            "output_tokens": 1024, "stagger": True,
            "pool_tokens_per_slot": 9216, "pool_slack_blocks": 64,
            "check_requests": 4, "check_steps": 8, "check_slots": 8,
            "trace_at": "middle"}
    assert traffic["prompt_len"] == {"dist": "uniform", "lo": 2048,
                                     "hi": 8192}
    limits = load("bench", "limits", CELL + ".json")
    assert {"served_logit_gap_max", "served_not_best_share",
            "logit_excess_error", "why"} <= set(limits)
    named = {m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", ())}
    assert set(NEW) | {"moe_pairs_per_touched_expert",
                       "window_pages_held_share", "decode_step_dev_ms",
                       "setup_compile_s"} <= named
    for name in named:
        assert os.path.exists(os.path.join(R.BENCH, "layer_metrics",
                                           name + ".py"))
    # the bytes the issue reckoned: 64 x 9216 tokens of two layers' K/V,
    # three rings of 33 pages a slot
    import traffic_gen
    assert traffic_gen.pool_blocks(traffic) == 64 * 576 + 64
    assert 64 * 9216 * 2 * op.kv_token_bytes(cell_cfg(), 2) == 4_831_838_208
    assert 3 * 64 * 33 * 16 * op.kv_token_bytes(cell_cfg(), 2) \
        == 415_236_096
