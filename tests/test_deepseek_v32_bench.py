"""What the benchmark adds for the deepseek-v3.2 configuration, without a
chip: `deepseek-v3.2.decode-sparse-16k`'s command end to end on the CPU at toy
width (bench/tests/data/toy-deepseek-v32: hidden 128, 2 dense + 2 expert
layers, 8 heads, 4 groups of 4 experts with 4 held, an indexer of 4 x 32 that
keeps 16 keys, which is one page), as tests/test_phi4flash_bench.py does for
its family: the sound run is correct against reference_deepseek_v32 and its
traced line carries the counters' metric; an altered token and a selection
forced to a sequence's first keys are not correct; the operation counts on
hand-worked shapes; each new reader on a small hand-made table, the
off-count cases that must read nothing among them; the files the cell is
found by."""
import importlib.util
import json
import os
import statistics
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

import opcount_dsa as op  # noqa: E402
import run as R  # noqa: E402
import trace_reduce as tr  # noqa: E402

TOY = os.path.join(BENCH, "tests", "data", "toy-deepseek-v32")
PEAKS = R.load_json(BENCH, "peaks.json")["TPU v5 lite"]
CELL = "deepseek-v3.2.decode-sparse-16k"
READERS = ("dsa_index_roofline", "dsa_attn_roofline", "dsa_selected_share")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_cfg():
    c = load("bench", "configs", "deepseek-v3.2.json")
    return {a: c[k] for a, k in c["program"]["args"].items()}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_toy(seed=3, seconds=2.0, trace=0, probe=None):
    argv = ["--workload", "toy-deepseek-v32.sparse", "--seed", str(seed),
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
    out = run_toy(seed=2**31 + 5, trace=1, seconds=5.0)
    assert out["correct"] and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    # requests ended inside the window: their slots were taken again
    assert out["attempted"] > 4
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"host_syncs_per_token", "batch_occupancy_mean",
            "moe_pairs_per_touched_expert", "dsa_selected_share"} <= set(m)
    # 16 keys kept of contexts of 8..120
    assert 10.0 < m["dsa_selected_share"] < 100.0
    # no device plane on the CPU: the trace's readers read nothing
    assert not {"dsa_index_roofline", "dsa_attn_roofline"} & set(m)


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the engine's greedy pass."""
    from paddle_tpu.serving import engine

    real = engine.greedy_grid

    def off_by_one(logits):
        am, fin = real(logits)
        return (am + 1) % logits.shape[-1], fin

    monkeypatch.setattr(engine, "greedy_grid", off_by_one)
    assert not run_toy()["correct"]


def test_a_selection_forced_to_the_first_keys_is_not_correct(monkeypatch):
    """Every query made to attend over its sequence's FIRST index_topk keys,
    whatever the indexer scored (a prompt's rows and a decode step's alike):
    the served logits leave the reference's."""
    from paddle_tpu.models import deepseek_v3

    def first_keys(scores, k):
        return jnp.broadcast_to(jnp.arange(scores.shape[1])[None] < k,
                                scores.shape)

    monkeypatch.setattr(deepseek_v3, "topk_mask", first_keys)
    out = run_toy()
    assert not out["correct"]
    assert "logit_excess_error" in [c["name"] for c in out["checks"]
                                    if not c["ok"]]


# ------------------------------------------------------ operation counts


def test_counts_at_the_published_widths():
    cfg = cell_cfg()
    # one index key of 128 bfloat16 values a layer, 5 layers
    assert op.index_key_bytes(cfg, 2) == 5 * 128 * 2 == 1280
    # 64 heads x 128 MACs a key and layer
    assert op.index_flops_per_key(cfg) == 5 * 64 * 128 * 2 == 81_920
    # 515 k keys: 0.66 GB at 819 GB/s = 0.805 ms, their products 0.214 ms
    least = op.index_least_seconds(cfg, [515_000], 2, PEAKS)
    assert least == pytest.approx(515_000 * 1280 / PEAKS["hbm_bytes_per_s"])
    assert 515_000 * 81_920 / PEAKS["bf16_flops_per_s"] < least
    # 36 sequences far past 2048, one at 100
    assert op.selected_keys(cfg, [14_000] * 36 + [100]) == 36 * 2048 + 100
    # a selected row: 576 values, 128 heads x 1088 MACs, in 5 layers
    assert op.selected_row_bytes(cfg, 2) == 5 * 576 * 2 == 5760
    assert op.selected_row_flops(cfg) == 5 * 128 * 1088 * 2 == 1_392_640
    # their bytes and their products take as long: 7.03 and 7.07 ns a row
    by_bytes = 5760 / PEAKS["hbm_bytes_per_s"]
    by_flops = 1_392_640 / PEAKS["bf16_flops_per_s"]
    assert by_flops == pytest.approx(by_bytes, rel=0.02)
    assert op.sparse_attn_least_seconds(cfg, [14_000] * 36, 2, PEAKS) == \
        pytest.approx(36 * 2048 * max(by_bytes, by_flops))


def test_the_cut_is_the_issues_arithmetic():
    """6.45 GB of bfloat16: 3226 M parameters in one dense and four expert
    layers with 8 held experts and an eighth of the vocabulary, counted from
    the reference's shapes."""
    import math

    import reference_deepseek_v32 as ref

    cfg = cell_cfg()
    shapes = ref._shapes(cfg)
    total = sum(math.prod(s) for s in shapes.values())
    assert round(total / 1e6) == 3226
    indexer = sum(math.prod(s) for n, s in shapes.items()
                  if n.startswith("layers.0.") and "indexer" in n)
    assert round(indexer / 1e6, 2) == 13.96
    # a cached token: 5 layers x (640 + 128) lanes x 2 B
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config

    c = DeepseekV3Config(**cfg)
    lanes = -(-c.latent_dim // 128) * 128 + c.index_head_dim
    assert c.num_hidden_layers * lanes * 2 == 7680


# ----------------------------------------------- readers on small tables

OFFSET = 5_000_000_321            # trace clock = bench clock + OFFSET (ns)
TOY_CFG = {"num_hidden_layers": 3, "index_n_heads": 4, "index_head_dim": 32,
           "index_topk": 16, "kv_lora_rank": 64, "qk_rope_head_dim": 16,
           "num_attention_heads": 8}
NS = (10, 30)                     # a layer's scan, then its attention


def table(drop=0):
    """Four engine steps of 1000 ns on the trace's clock; the two middle
    decode runs are whole. A run makes two Mosaic calls a layer."""
    ops, modules, host = [], [], []
    for s in range(4):
        t = s * 1000
        host.append(("bench.engine_step", t, 900))
        modules.append(("_decode_step", t + 100, 700))
        for j in range(2 * TOY_CFG["num_hidden_layers"] - drop):
            ops.append(("mosaic:_decode_step", t + 110 + 60 * j, NS[j % 2]))
        ops.append(("fusion", t + 700, 50))
    return tr.clip(tr.Trace(ops={0: ops}, modules={0: modules}, host=host),
                   0, 4000)


def ctx_for(trace, counters=None, config=None):
    # (t0, t1, events, context_tokens, decoding)
    steps = [((s * 1000 - OFFSET) / 1e9, (s * 1000 + 900 - OFFSET) / 1e9,
              2, 100 * (s + 1), 2) for s in range(4)]
    return {"trace": trace, "steps": steps, "lives": [],
            "median": statistics.median,
            "config": TOY_CFG if config is None else config, "peaks": PEAKS,
            "counters": {"dsa_keys_scored": 1200.0,
                         "dsa_keys_selected": 300.0}
            if counters is None else counters,
            "trace_span": (steps[0][0] - 1e-7, steps[-1][1] + 1e-7)}


def test_readers_on_a_hand_made_table():
    ctx = ctx_for(table())
    hbm, flops = PEAKS["hbm_bytes_per_s"], PEAKS["bf16_flops_per_s"]
    # whole decode runs: steps 1 and 2, contexts 200 + 300 keys; 3 scans
    # of 10 ns a run. A key: 3 layers x 32 x 2 B, or 3 x 4 x 32 MACs
    least = 500 * max(3 * 32 * 2 / hbm, 3 * 4 * 32 * 2 / flops)
    assert reader("dsa_index_roofline")(ctx) == pytest.approx(
        100 * least / 60e-9)
    # 2 sequences a step of mean context 100 and 150: 16 rows each; a row
    # 3 layers x 80 values x 2 B, or 3 x 8 heads x 144 MACs; 3 x 30 ns
    row = max(3 * 80 * 2 / hbm, 3 * 8 * 144 * 2 / flops)
    assert reader("dsa_attn_roofline")(ctx) == pytest.approx(
        100 * (2 * 2 * 16 * row) / 180e-9)
    assert reader("dsa_selected_share")(ctx) == pytest.approx(25.0)


def test_readers_read_nothing_where_the_count_is_off():
    # a decode run with a Mosaic call too few: not two a layer
    ctx = ctx_for(table(drop=1))
    assert reader("dsa_index_roofline")(ctx) is None
    assert reader("dsa_attn_roofline")(ctx) is None


def test_readers_read_nothing_on_another_program():
    """On a program without the counters or the configuration's keys (the
    parent commit, another family) the new readers return nothing and do
    not raise."""
    ctx = {"counters": {"tokens_generated": 5.0}, "steps": [(0, 1, 4, 9, 4)],
           "lives": [], "config": {"hidden_size": 8}, "trace": None,
           "trace_span": (0, 1), "peaks": PEAKS}
    for name in READERS:
        assert reader(name)(ctx) is None
    # Kimi's configuration (no indexer) over a trace of decode runs
    ctx = ctx_for(table(), counters={},
                  config={"num_hidden_layers": 3, "kv_lora_rank": 64})
    for name in READERS:
        assert reader(name)(ctx) is None


# ------------------------------------------------------ the cell's files


def test_the_cell_is_found_by_its_files():
    manifest = load("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["chips"] == 1
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cells[CELL]["config"])
    cfg = load(entry["file"])
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["reduced_how"])
    traffic = load("bench", "traffic", cells[CELL]["traffic"] + ".json")
    assert (traffic["clients"], traffic["max_batch_size"],
            traffic["pool_tokens_per_slot"]) == (36, 36, 17920)
    limits = load("bench", "limits", CELL + ".json")
    assert {"served_logit_gap_max", "served_not_best_share",
            "logit_excess_error", "why"} <= set(limits)
    assert os.path.exists(os.path.join(
        BENCH, cfg["program"]["reference"] + ".py"))
    listing = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert listing[name]["workloads"] == [CELL]
        assert listing[name]["layer"] == "attention, serving (sparse)"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # the dense latent cell's roofline reader takes every kernel call of a
    # step for the walk (here the scan's calls stand among them); the
    # walk's counters are the runner's own, sparse or not
    assert CELL not in listing["mla_attn_roofline"]["workloads"]
    assert listing["latent_run_copy_share"]["workloads"][-1] == CELL
    assert listing["decode_weights_roofline"]["workloads"][-1] == CELL
    for name in ("moe_pairs_per_touched_expert", "decode_step_dev_ms",
                 "setup_compile_s"):
        assert CELL in listing[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]


def test_every_published_number_is_in_the_configuration():
    """Every key of the published config is in the file under its own name,
    unchanged unless `reduced` names it; no width is reduced."""
    cfg = load("bench", "configs", "deepseek-v3.2.json")
    changed = {k for k, v in cfg["published"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "vocab_size", "num_nextn_predict_layers"}
    assert changed | {"n_routed_experts"} == set(cfg["reduced"])
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"]
            ) == (7168, 128, 1536, 512, 18432, 2048, 256, 8, 8, 4, 64, 128,
                  2048)
    assert (cfg["experts_held"], cfg["first_expert"], cfg["max_seq_len"]
            ) == (8, 0, 20480)
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())
