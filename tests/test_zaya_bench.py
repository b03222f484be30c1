"""What the benchmark adds for the zaya1-8b configuration, without a chip:
the operation counts on hand-worked shapes, each new reader on a small
hand-made table (the off-count cases that must read nothing among them), and
the files the cell is found by."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

import opcount_zaya as oz  # noqa: E402
import trace_reduce as tr  # noqa: E402
import zaya_trace  # noqa: E402

PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_cfg():
    c = load("bench", "configs", "zaya1-8b.json")
    return {a: c[k] for a, k in c["program"]["args"].items()}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------ operation counts


def test_counts_at_the_published_widths():
    cfg = cell_cfg()
    # W_Q 2048 x 1024, W_K 2048 x 256, two value heads 2048 x 128, W_O
    assert oz.projection_flops(cfg) == 2 * 2048 * (1024 + 256 + 256 + 1024) \
        == 10_485_760
    # 1280 channels: 2 taps each, then 2 taps of 128 x 128 a head
    assert oz.convolution_flops(cfg) == 2 * 1280 * (2 + 2 * 128) == 660_480
    assert oz.router_flops(cfg) == 2 * (2048 * 256 + 2 * 256 * 256
                                        + 256 * 16) == 1_318_912
    assert oz.attention_flops_fwd(cfg, 8192) == 2 * 8192 * 8192 * 1024
    assert oz.attention_flops_fwd(cfg, 8192) / 8192 == 16_777_216
    assert oz.attention_flops_train(cfg, 1, 8192) == \
        3 * 4 * 2 * 8192 * 8192 * 1024
    assert oz.expert_flops_per_pair(cfg) == 6 * 2048 * 2048 == 25_165_824
    assert oz.head_flops(cfg) == 2 * 2048 * 32784 == 134_283_264
    # half of the tokens routed to a held expert
    layer = 10_485_760 + 660_480 + 1_318_912 + 16_777_216 + 12_582_912
    assert oz.train_flops_per_token(cfg, 8192, 0.5) == \
        3 * (4 * layer + 134_283_264)


def test_counts_on_a_hand_worked_toy():
    cfg = dict(hidden_size=8, num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=2, head_dim=4, cca_time0=2, cca_time1=3,
               router_hidden_size=4, num_experts=4, moe_intermediate_size=6,
               vocab_size=10)
    assert oz.projection_flops(cfg) == 2 * 8 * (8 + 8 + 8 + 8)
    assert oz.convolution_flops(cfg) == 2 * 16 * (2 + 3 * 4)
    assert oz.router_flops(cfg) == 2 * (8 * 4 + 2 * 16 + 16)
    assert oz.attention_flops_fwd(cfg, 5) == 2 * 25 * 8
    assert oz.expert_flops_per_pair(cfg) == 6 * 8 * 6
    assert oz.train_flops_per_token(cfg, 5, 1.0) == 3 * (
        2 * (512 + 448 + 160 + 80 + 288) + 160)


# ----------------------------------------------- readers on small tables

LAYERS = 2
CFG = dict(cell_cfg(), num_hidden_layers=LAYERS)
FLASH_NS, GROUPED_NS = 300, 20


def table(kernels_a_step=12 * LAYERS, steps=5):
    """`steps` runs of the program `step`, 10 us each; in each, Mosaic
    calls in the step's order (flash 300 ns, grouped 20 ns) with a fusion
    between them. The first and the last run are never whole."""
    kinds = zaya_trace.FORWARD * LAYERS + zaya_trace.BACKWARD * LAYERS
    ops, modules = [], []
    for s in range(steps):
        t = s * 10_000
        modules.append(("step", t, 9_000))
        for k in range(kernels_a_step):
            kind = kinds[k % len(kinds)]
            ops.append(("mosaic:step", t + 10 + k * 350,
                        FLASH_NS if kind == "F" else GROUPED_NS))
            ops.append(("fusion", t + 10 + k * 350 + 320, 10))
    return tr.Trace(ops={0: ops}, modules={0: modules}, span=(0, 10_000 * steps))


def ctx(trace, counters=None):
    c = {"trace": trace, "config": CFG, "batch": 1, "seq": 8192, "chips": 1,
         "peaks": PEAKS, "steps": [(i * 0.1, i * 0.1 + 0.1) for i in range(9)],
         "t_open": 0.0, "trace_requested": 0.55}
    if counters is not None:
        c["zaya_counters"] = counters
    return c


COUNTERS = {"moe_train_tokens": 8192.0 * 40, "moe_train_pairs": 4096.0 * 40,
            "moe_train_rows_padded": 5120.0 * 40,
            "moe_train_load_max": 560.0 * 40, "moe_train_load_mean": 512.0 * 40,
            "moe_bias_abs_max": 0.02,
            # the last steps' own pairs, the newest last: the table's three
            # whole steps are the three before the newest
            "moe_train_pairs_by_step": [0.0, 9e9, 7000.0, 9000.0, 8576.0,
                                        9e9]}


def test_kernels_are_told_apart_by_their_order_in_a_step():
    flash_s, grouped_s, n = zaya_trace.kernel_seconds(ctx(table()))
    assert n == 3                       # five runs, the outer two not whole
    assert flash_s == pytest.approx(3 * LAYERS * 3 * FLASH_NS / 1e9)
    assert grouped_s == pytest.approx(3 * LAYERS * 9 * GROUPED_NS / 1e9)


@pytest.mark.parametrize("bad", [table(kernels_a_step=12 * LAYERS - 1),
                                 table(steps=2), None])
def test_another_count_reads_nothing(bad):
    c = ctx(bad, COUNTERS)
    assert zaya_trace.kernel_seconds(c) is None
    assert reader("cca_attn_roofline")(c) is None
    assert reader("expert_ffn_roofline")(c) is None


def test_cca_attn_roofline():
    got = reader("cca_attn_roofline")(ctx(table(), COUNTERS))
    flops = 3 * oz.attention_flops_train(CFG, 1, 8192)       # three steps
    want = 100 * flops / PEAKS["bf16_flops_per_s"] / (18 * FLASH_NS / 1e9)
    assert got == pytest.approx(want)


def test_expert_ffn_roofline():
    got = reader("expert_ffn_roofline")(ctx(table(), COUNTERS))
    pairs = 7000 + 9000 + 8576          # of the three whole steps themselves
    assert pairs == 3 * LAYERS * 4096
    want = 100 * pairs * 18 * 2048 * 2048 / PEAKS["bf16_flops_per_s"] / (
        54 * GROUPED_NS / 1e9)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("by_step", [None, [], [1.0, 2.0, 3.0]])
def test_too_few_steps_kept_read_nothing(by_step):
    """Three whole steps need the counts of four."""
    c = ctx(table(), dict(COUNTERS, moe_train_pairs_by_step=by_step))
    assert zaya_trace.pairs_in_whole_steps(c, 3) is None
    assert reader("expert_ffn_roofline")(c) is None
    assert reader("cca_attn_roofline")(c) is not None


def test_counter_readers():
    c = ctx(None, COUNTERS)
    assert reader("moe_block_fill")(c) == pytest.approx(80.0)
    assert reader("moe_load_max_over_mean")(c) == pytest.approx(560 / 512)
    # five steps complete by the time the profiler was asked for: the
    # rate is four steps over 0.5 s
    rate = 4 * 8192 / 0.5
    want = 100 * oz.train_flops_per_token(CFG, 8192, 0.5) * rate / \
        PEAKS["bf16_flops_per_s"]
    assert reader("moe_train_mfu")(c) == pytest.approx(want)


def test_a_program_without_the_counters_reads_nothing(monkeypatch):
    from paddle_tpu import profiler

    monkeypatch.delattr(profiler, "step_counters")
    c = ctx(table())
    for name in ("moe_train_mfu", "expert_ffn_roofline", "moe_block_fill",
                 "moe_load_max_over_mean"):
        assert reader(name)(c) is None, name
    assert c["zaya_counters"] == {}


# ------------------------------------------------------- the cell's files


def test_the_cell_is_found_by_its_files():
    m = load("BENCHMARK.json")
    cell = next(w for w in m["workloads"] if w["name"] == "zaya1-8b.train-8k")
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "pretrain-8k-moe"
    cfg, traffic = load(entry["file"]), load("bench", "traffic",
                                             cell["traffic"] + ".json")
    limits = load("bench", "limits", cell["name"] + ".json")
    assert set(limits) == {"loss_gap_max", "grad_norm_gap_worst_leaf",
                           "delta_norm_gap_worst_leaf", "why"}
    assert (traffic["seq_len"], traffic["batch"], traffic["check_steps"],
            traffic["amp_level"]) == (8192, 1, 3, "O1")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    # every published number stands but the three cuts
    for k, v in cfg["published"].items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 262272 // 8)
    metrics = {p["name"] for p in m["per_layer"]
               if cell["name"] in p.get("workloads", [])}
    assert {"moe_train_mfu", "cca_attn_roofline", "expert_ffn_roofline",
            "moe_block_fill", "moe_load_max_over_mean", "train_step_dev_ms",
            "setup_compile_s"} <= metrics
    assert "train_mfu" not in metrics and "flash_attn_roofline" not in metrics
    for name in metrics:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name


def test_the_model_takes_the_files_arguments():
    from paddle_tpu.models.zaya import ZayaConfig

    cfg = ZayaConfig(**cell_cfg())
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert) == (16, 8, 0)
    assert cfg.hidden_size == cfg.moe_intermediate_size == 2048
