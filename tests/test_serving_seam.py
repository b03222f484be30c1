"""The seam between a served configuration and what is above it: a runner's
counts and gauges reach `snapshot()` by the names the runner gives, the
pool's by the pool's, `runner_for` reads one table, and the engine, the
metrics, the chassis and the scheduler name no configuration."""

import os
import re
import subprocess

import pytest

from _helpers import CountingStubRunner, StubPagedRunner
from paddle_tpu.inference import create_serving_engine
from paddle_tpu.serving import (
    EngineMetrics, SamplingParams, ServingEngine, naive_generate, runner_for,
    runners,
)
from paddle_tpu.serving.metrics import aggregate_snapshots

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVING = os.path.join(ROOT, "paddle_tpu", "serving")

# ------------------------------------------- (a) names nobody above spells

MADE_UP_COUNTS = ("zorp_rows_fed", "zorp_rows_tripled")
MADE_UP_GAUGES = ("quux_bytes_walked", "quux_bytes_gathered")


class MadeUpRunner(CountingStubRunner):
    """The counting stub under names of its own."""

    COUNTS = MADE_UP_COUNTS
    GAUGES = MADE_UP_GAUGES


def test_made_up_names_are_spelled_nowhere_in_the_package():
    found = subprocess.run(
        ["grep", "-rlE", "|".join(MADE_UP_COUNTS + MADE_UP_GAUGES),
         os.path.join(ROOT, "paddle_tpu")], capture_output=True, text=True)
    assert found.stdout == ""


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["default", "pipelined"])
def test_a_runners_own_names_come_out_of_the_snapshot(pipelined):
    runner = MadeUpRunner(vocab_size=31, block_size=4, max_model_len=32)
    eng = ServingEngine(runner, num_blocks=24, max_batch_size=3,
                        max_model_len=32, pipelined=pipelined)
    first = eng.metrics.snapshot()
    assert all(first[k] == 0.0 for k in MADE_UP_COUNTS + MADE_UP_GAUGES)
    work = [([1, 2, 3, 4, 5], 6), ([7, 8], 9), ([3, 1, 4, 1, 5, 9], 4)]
    rids = [eng.add_request(p, SamplingParams(max_tokens=n)) for p, n in work]
    while eng.has_work():
        eng.step()
    eng.flush()
    snap = eng.metrics.snapshot()
    assert snap["zorp_rows_fed"] == sum(t for t, _ in runner.handed) > 0
    assert snap["zorp_rows_tripled"] == sum(p for _, p in runner.handed) > 0
    assert snap["quux_bytes_walked"] == runner.quux_bytes_walked > 0
    assert snap["quux_bytes_gathered"] == runner.quux_bytes_gathered > 0
    assert eng.metrics.zorp_rows_fed.value == snap["zorp_rows_fed"]
    # a tier sums what it was never told of, and keeps its own rules
    agg = aggregate_snapshots([snap, snap])
    for k in MADE_UP_COUNTS + MADE_UP_GAUGES + ("tokens_generated",):
        assert agg[k] == 2 * snap[k], k
    assert agg["queue_depth_peak"] == snap["queue_depth_peak"]
    assert agg["host_syncs_per_token"] == snap["host_syncs_per_token"]
    assert "ttft_s_p99" not in agg and "kv_bytes_reduction_x" not in agg
    # and the runner serves as the stub does
    runner.on_step_counts = None
    outs = eng.outputs()
    for rid, (p, n) in zip(rids, work):
        assert outs[rid].output_tokens == naive_generate(
            runner, p, SamplingParams(max_tokens=n), max_model_len=32)


def test_an_instrument_keeps_its_kind_and_eng_metrics_its_names():
    from paddle_tpu.serving.metrics import Counter, Gauge

    m = EngineMetrics()
    (again,) = m.declare(Counter, ("prefill_chunks",))
    assert again is m.prefill_chunks
    with pytest.raises(ValueError, match="Counter"):
        m.declare(Gauge, ("prefill_chunks",))
    with pytest.raises(ValueError, match="snapshot"):
        m.declare(Counter, ("snapshot",))


# --------------------------------------------- (b) one table, five modules


def _llama():
    from paddle_tpu.models import Llama, LlamaConfig

    return Llama(LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                             num_heads=4, num_kv_heads=2, ffn_hidden=64,
                             max_seq_len=32))


def _gpt():
    from paddle_tpu.models import GPT, GPTConfig

    return GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=4, max_seq_len=32))


def _deepseek(**sparse):
    from paddle_tpu.models import DeepseekV3Config, DeepseekV3ForCausalLM

    return DeepseekV3ForCausalLM(DeepseekV3Config(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, experts_held=4, first_expert=0,
        max_seq_len=32, **sparse))


def _olmo():
    from paddle_tpu.models.olmo_hybrid import (
        OlmoHybridConfig, OlmoHybridForCausalLM,
    )

    return OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=4, num_attention_heads=4,
        layer_types=["linear_attention"] * 3 + ["full_attention"],
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8,
        linear_conv_kernel_dim=4, rope_parameters={"rope_theta": None},
        max_seq_len=32))


def _phi():
    from paddle_tpu.models.phi4flash import (
        Phi4FlashConfig, Phi4FlashForCausalLM,
    )

    return Phi4FlashForCausalLM(Phi4FlashConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=8, mb_per_layer=2, tie_word_embeddings=True,
        mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=None,
        max_seq_len=32))


def _laguna():
    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM

    return LagunaForCausalLM(LagunaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=5, num_attention_heads=6, num_key_value_heads=2,
        head_dim=8, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=8, shared_expert_intermediate_size=8,
        sliding_window=8, max_seq_len=32))


# what every serving cell's readers under bench/layer_metrics/ read of the
# window's counters (host_syncs_per_token; batch_occupancy_mean reads the
# histogram), then what its own readers read besides
EVERY_CELL = {"host_syncs", "tokens_generated"}
EXPERTS = {"moe_local_pairs", "moe_experts_touched",   # pairs per touched
           "latent_run_groups", "latent_copy_groups"}  # expert; run copies
LAYERS = {
    "llama": (_llama, "llama", "LlamaRunner", set()),
    # gpt3-1.3b.decode
    "gpt": (_gpt, "gpt", "GPTRunner", set()),
    # kimi-k2.7-code.decode-16k
    "deepseek_v3": (_deepseek, "deepseek_v3", "DeepseekV3Runner", EXPERTS),
    # deepseek-v3.2.decode-sparse-16k: dsa_selected_share besides
    "deepseek_v32": (
        lambda: _deepseek(index_n_heads=2, index_head_dim=8, index_topk=4),
        "deepseek_v3", "DeepseekV3Runner",
        EXPERTS | {"dsa_keys_selected", "dsa_keys_scored"}),
    # olmo-hybrid-7b.decode-wide: delta_prefill_real_share and the gate of
    # delta_state_roofline
    "olmo_hybrid": (_olmo, "olmo_hybrid", "OlmoHybridRunner",
                    {"delta_prefill_tokens", "delta_prefill_positions",
                     "delta_decode_seq_steps"}),
    # phi-4-mini-flash.reason-12k: window_pages_held_share and the gate of
    # ssm_state_roofline
    "phi4flash": (_phi, "phi4flash", "Phi4FlashRunner",
                  {"window_pages_held", "window_pages_whole_context",
                   "ssm_decode_seq_steps"}),
    # laguna-xs.2.agent-8k: pairs per touched expert and the held share of
    # the two cells above, laguna_decode_block_fill and the gate of
    # laguna_weights_roofline besides
    "laguna": (_laguna, "laguna", "LagunaRunner",
               {"moe_local_pairs", "moe_experts_touched",
                "window_pages_held", "window_pages_whole_context",
                "moe_decode_pairs", "moe_decode_rows_multiplied",
                "moe_decode_experts_touched"}),
}


@pytest.mark.parametrize("which", sorted(LAYERS))
def test_runner_for_reads_the_table_and_the_snapshot_has_the_cells_keys(
        which):
    build, module, cls, keys = LAYERS[which]
    model = build()
    runner = runner_for(model, block_size=4)
    assert type(runner).__name__ == cls
    assert type(runner).__module__ == f"paddle_tpu.serving.runners.{module}"
    assert type(runner) is runners.runner_class(model)
    snap = create_serving_engine(model, block_size=4, num_blocks=32,
                                 max_batch_size=2).metrics.snapshot()
    assert EVERY_CELL | keys <= set(snap)
    assert set(runner.COUNTS) | set(runner.GAUGES) <= set(snap)
    # what no mechanism of this engine has is not in its snapshot
    others = set().union(*(k for _, _, _, k in LAYERS.values())) - keys
    assert not others & set(snap)


def test_an_unknown_layer_is_told_the_tables_layers():
    from paddle_tpu import nn

    with pytest.raises(TypeError) as e:
        runner_for(nn.Linear(4, 4))
    assert "no serving runner for Linear" in str(e.value)
    assert runners.supported() in str(e.value)
    for layer, runner, _ in runners.RUNNERS:
        assert layer.partition(":")[2] in runners.supported()
        assert runner.startswith("paddle_tpu.serving.runners.")


# ------------------------- (c) nothing above a runner names a configuration

CONFIGURATIONS = re.compile(
    "moe_|latent_|dsa_|delta_|ssm_|window_pages_|cross_rows")


@pytest.mark.parametrize("name", ["engine.py", "metrics.py",
                                  "model_runner.py", "scheduler.py"])
def test_no_configurations_prefix_above_the_runners(name):
    with open(os.path.join(SERVING, name)) as f:
        found = [(n, line.strip()) for n, line in enumerate(f, 1)
                 if CONFIGURATIONS.search(line)]
    assert not found, found
    if name == "model_runner.py":
        with open(os.path.join(SERVING, name)) as f:
            classes = re.findall(r"^class (\w*Runner)\b", f.read(), re.M)
        assert classes == ["PagedModelRunner"]


# --------------------------------------------- (d) the engine's own keys

OWN_KEYS = """
batch_occupancy_mean cow_copies decode_horizon_steps decode_steps
e2e_latency_s_p50 e2e_latency_s_p99 handoff_bytes_out handoff_pages_in
handoff_pages_out handoff_recompute_fallbacks handoffs_in handoffs_out
horizon_overshoot_tokens host_syncs host_syncs_per_token host_tier_bytes
host_tier_drops host_tier_pages_used kv_bytes_reduction_x nan_logit_events
offload_recompute_fallbacks offload_resumes offload_spill_pages
pagein_hidden_pages pagein_hidden_ratio pagein_pages planned_ahead_steps
pool_used_pages pool_utilization_peak preemptions prefill_chunks
prefill_tokens prefix_cached_pages prefix_hit_tokens queue_depth
queue_depth_peak requests_aborted requests_added requests_finished
requests_timed_out running sessions_per_pool_x shed_requests
spec_acceptance_rate spec_accepted_tokens spec_dead_positions
spec_fused_horizons spec_proposed_tokens spec_rollback_pages step_retries
steps_per_token store_dedup_pages store_hit_pages tokens_generated
ttft_s_mean ttft_s_p50 ttft_s_p99 weight_bytes_reduction_x
""".split()
# the chassis's eight host-side gauges and the two ratios made of them
CHASSIS_KEYS = """
attn_kv_bytes_read attn_kv_bytes_gather ragged_blocks ragged_edge_blocks
tp_comm_bytes tp_comm_bytes_fp32 tp_gather_bytes tp_gather_bytes_fp32
tp_comm_bytes_reduction_x tp_gather_bytes_reduction_x
""".split()


def test_the_engines_own_snapshot_keys_are_what_they_were():
    assert sorted(EngineMetrics().snapshot()) == sorted(OWN_KEYS)
    stub = ServingEngine(StubPagedRunner(), num_blocks=24, max_batch_size=2)
    assert sorted(stub.metrics.snapshot()) == sorted(OWN_KEYS)
    snap = create_serving_engine(_gpt(), block_size=4, num_blocks=16,
                                 max_batch_size=2).metrics.snapshot()
    assert sorted(snap) == sorted(OWN_KEYS + CHASSIS_KEYS)
    # a tier's view over nothing: the sums and peaks, the ratios made again
    agg = aggregate_snapshots([])
    assert agg.pop("replicas") == 0.0 and set(agg) <= set(OWN_KEYS)
    assert set(OWN_KEYS) - set(agg) == {
        "batch_occupancy_mean", "e2e_latency_s_p50", "e2e_latency_s_p99",
        "ttft_s_mean", "ttft_s_p50", "ttft_s_p99", "host_tier_pages_used",
        "kv_bytes_reduction_x", "sessions_per_pool_x",
        "weight_bytes_reduction_x"}
