"""Serving metrics: counters, gauges, histograms for the engine.

Reference: the reference's serving stack exposes per-predictor profiling
(paddle/fluid/inference/api/analysis_predictor.cc perf stats) and the
deployment servers around it report QPS/latency. Here the engine itself
owns the counts an operator needs: queue depth, time-to-first-token,
KV-pool utilization, preemptions, host syncs. Rates and the split of a
step's time are measured from outside (bench/) and from the engine's
spans (paddle_tpu.profiler).

Everything is plain python (host-side) — the engine records around its
device calls, never inside a traced function. The clock is injectable so
scheduler unit tests run on a virtual clock.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional


class Counter:
    """Monotonic event counter."""

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


def _nothing_owed() -> None:
    pass


class Gauge:
    """Last-write-wins instantaneous value; remembers its peak. Where its
    owner puts writes off (`EngineMetrics.put_off`), `due` settles them
    before this gauge is read or written, so a reader sees every write
    that was made or owed, in the order they were."""

    def __init__(self, name: str):
        self.name = name
        self.due: Callable[[], None] = _nothing_owed
        self._value = 0.0
        self._peak = 0.0

    def set(self, v: float) -> None:
        self.due()
        self._value = float(v)
        if self._value > self._peak:
            self._peak = self._value

    @property
    def value(self) -> float:
        self.due()
        return self._value

    @property
    def peak(self) -> float:
        self.due()
        return self._peak


class Histogram:
    """Exact-sample histogram (serving workloads are small enough that we
    keep every observation; percentile() is then exact, not bucketed)."""

    def __init__(self, name: str):
        self.name = name
        self._samples: List[float] = []

    def observe(self, v: float) -> None:
        self._samples.append(float(v))

    @property
    def count(self) -> int:
        return len(self._samples)

    @property
    def sum(self) -> float:
        return sum(self._samples)

    @property
    def mean(self) -> float:
        return self.sum / self.count if self._samples else 0.0

    @property
    def max(self) -> float:
        """Worst observation (0.0 when empty) — the number the chaos
        bench commits for recovery latency (ISSUE 13): a p99 hides a
        single catastrophic recovery, the max cannot."""
        return max(self._samples) if self._samples else 0.0

    def percentile(self, p: float) -> float:
        """Exact nearest-rank percentile, p in [0, 100]."""
        if not self._samples:
            return 0.0
        s = sorted(self._samples)
        if p <= 0:
            return s[0]
        if p >= 100:
            return s[-1]
        rank = max(0, min(len(s) - 1, int(round(p / 100.0 * (len(s) - 1)))))
        return s[rank]


# EngineMetrics.snapshot() keys that are cumulative event counts — the
# keys a multi-engine tier can meaningfully SUM across replicas (ISSUE 8
# metrics aggregation). Gauges/peaks take max, ratios are recomputed from
# the summed counters, and exact percentiles are dropped: scalar
# snapshots cannot be merged into a percentile, so tier-level latency
# lives in the router's own histograms instead.
SUMMABLE_KEYS = (
    "requests_added", "requests_finished", "preemptions",
    "requests_timed_out", "requests_aborted", "step_retries",
    "nan_logit_events", "shed_requests", "tokens_generated",
    "moe_tokens_routed", "moe_local_pairs", "moe_experts_touched",
    "latent_copy_groups", "latent_run_groups",
    "dsa_keys_scored", "dsa_keys_selected",
    "delta_decode_seq_steps", "delta_prefill_tokens",
    "delta_prefill_positions", "state_slot_resets",
    "ssm_decode_seq_steps", "ssm_prefill_tokens", "cross_rows_skipped",
    "window_pages_held", "window_pages_whole_context",
    "window_pages_returned",
    "prefill_tokens", "prefill_chunks", "prefix_hit_tokens", "cow_copies",
    "prefix_cached_pages", "attn_kv_bytes_read", "attn_kv_bytes_gather",
    "ragged_blocks", "ragged_edge_blocks",
    "tp_comm_bytes", "tp_comm_bytes_fp32",
    "tp_gather_bytes", "tp_gather_bytes_fp32",
    "spec_proposed_tokens", "spec_accepted_tokens", "spec_rollback_pages",
    "spec_fused_horizons", "spec_dead_positions",
    "host_syncs", "decode_horizon_steps", "horizon_overshoot_tokens",
    "planned_ahead_steps",
    "offload_spill_pages", "pagein_pages", "pagein_hidden_pages",
    "offload_resumes", "offload_recompute_fallbacks", "host_tier_drops",
    "host_tier_bytes",
    "handoffs_out", "handoffs_in", "handoff_pages_out", "handoff_pages_in",
    "handoff_recompute_fallbacks", "handoff_bytes_out",
    "store_hit_pages", "store_dedup_pages",
    "decode_steps", "queue_depth", "running", "pool_used_pages",
)

MAX_KEYS = ("queue_depth_peak", "pool_utilization_peak")


def aggregate_snapshots(snaps) -> Dict[str, float]:
    """Merge several EngineMetrics snapshots into one tier-level view:
    counters sum, peaks take the max, and derived ratios are recomputed
    from the summed counters. Percentile keys are intentionally absent
    (see SUMMABLE_KEYS)."""
    snaps = list(snaps)
    out: Dict[str, float] = {k: 0.0 for k in SUMMABLE_KEYS}
    for k in MAX_KEYS:
        out[k] = 0.0
    for s in snaps:
        for k in SUMMABLE_KEYS:
            out[k] += float(s.get(k, 0.0))
        for k in MAX_KEYS:
            out[k] = max(out[k], float(s.get(k, 0.0)))
    toks = out["tokens_generated"]
    prop = out["spec_proposed_tokens"]
    out["spec_acceptance_rate"] = (out["spec_accepted_tokens"] / prop
                                   if prop > 0 else 0.0)
    pin = out["pagein_pages"]
    out["pagein_hidden_ratio"] = (out["pagein_hidden_pages"] / pin
                                  if pin > 0 else 0.0)
    out["steps_per_token"] = out["decode_steps"] / toks if toks > 0 else 0.0
    out["host_syncs_per_token"] = out["host_syncs"] / toks if toks > 0 \
        else 0.0
    # quantized collectives (ISSUE 15): the tier-level comm reduction
    # is recomputed from the SUMMED byte counters, never averaged
    # (per-replica ratios over different traffic cannot be averaged
    # honestly)
    comm = out["tp_comm_bytes"]
    out["tp_comm_bytes_reduction_x"] = (out["tp_comm_bytes_fp32"] / comm
                                        if comm > 0 else 0.0)
    gather = out["tp_gather_bytes"]
    out["tp_gather_bytes_reduction_x"] = (
        out["tp_gather_bytes_fp32"] / gather if gather > 0 else 0.0)
    out["replicas"] = float(len(snaps))
    return out


class EngineMetrics:
    """The engine's instrument panel, read through snapshot().

    Counts, gauges and histograms of what the engine did, counted where
    it happens. How long each part of a step took is not here: the
    engine's spans (`paddle_tpu.profiler`) carry that. TTFT is measured
    from add_request() to the first sampled token of that request
    (admission wait + prefill), by the injectable clock that also serves
    arrival times and deadlines.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = clock or time.monotonic
        self.requests_added = Counter("requests_added")
        self.requests_finished = Counter("requests_finished")
        self.preemptions = Counter("preemptions")
        # failure-side instruments (ISSUE 2): every abnormal outcome is
        # counted, so an overloaded or faulty deployment is visible in
        # snapshot() instead of in a stack trace
        self.requests_timed_out = Counter("requests_timed_out")
        self.requests_aborted = Counter("requests_aborted")
        self.step_retries = Counter("step_retries")
        self.nan_logit_events = Counter("nan_logit_events")
        self.shed_requests = Counter("shed_requests")
        self.tokens_generated = Counter("tokens_generated")
        # expert layers (a runner that serves one rank's share of an
        # expert-parallel model): tokens x layers through a router, the
        # token-expert pairs computed HERE, and held experts with at
        # least one token, summed over layers and steps. Each step's
        # program returns them and the engine reads them at the step's
        # one drain, with its tokens: host_syncs does not rise
        self.moe_tokens_routed = Counter("moe_tokens_routed")
        self.moe_local_pairs = Counter("moe_local_pairs")
        self.moe_experts_touched = Counter("moe_experts_touched")
        # the latent decode kernel's walk, read the same way: groups of
        # pages it copied, all layers, and those whose pages were
        # consecutive in the pool, copied as ONE copy
        self.latent_copy_groups = Counter("latent_copy_groups")
        self.latent_run_groups = Counter("latent_run_groups")
        # learned sparse attention, read the same way: index keys a step's
        # query rows scored (each row's context, all layers) and the keys
        # their selections kept (min(context, index_topk) a row and layer)
        self.dsa_keys_scored = Counter("dsa_keys_scored")
        self.dsa_keys_selected = Counter("dsa_keys_selected")
        # recurrent state (a runner whose linear layers keep a state slot
        # a sequence), read the same way: live rows x linear layers a
        # decode step advanced, real prompt tokens through the chunked
        # form and the positions it computed (padding and bucket
        # included), slots a prefill reset; and, set by the engine, the
        # slots that hold a running request's state
        self.delta_decode_seq_steps = Counter("delta_decode_seq_steps")
        self.delta_prefill_tokens = Counter("delta_prefill_tokens")
        self.delta_prefill_positions = Counter("delta_prefill_positions")
        self.state_slot_resets = Counter("state_slot_resets")
        self.state_slots_live = Gauge("state_slots_live")
        # a runner with a selective scan and page groups (Phi-4-mini-
        # flash): live rows x scan layers a decode step advanced, real
        # prompt tokens through the chunked scan, prompt rows that stopped
        # before the cross-decoder (outputs of each step's program); and,
        # mirrored from the pool's window group, the pages a layer of it
        # held for the rows of every decode launch, the pages a cache of
        # the whole context would have held for the same rows, and the
        # pages it gave back: sums over launches, so a window's delta
        # divides
        self.ssm_decode_seq_steps = Counter("ssm_decode_seq_steps")
        self.ssm_prefill_tokens = Counter("ssm_prefill_tokens")
        self.cross_rows_skipped = Counter("cross_rows_skipped")
        self.window_pages_held = Gauge("window_pages_held")
        self.window_pages_whole_context = Gauge("window_pages_whole_context")
        self.window_pages_returned = Gauge("window_pages_returned")
        # prefill_tokens counts tokens actually COMPUTED by prefill
        # chunks; prefix-cache hits skip the compute and land in
        # prefix_hit_tokens instead, so (computed + hit) = total context
        # and the hit counter IS the prefill-token savings (ISSUE 3)
        self.prefill_tokens = Counter("prefill_tokens")
        self.prefill_chunks = Counter("prefill_chunks")
        self.prefix_hit_tokens = Counter("prefix_hit_tokens")
        self.cow_copies = Counter("cow_copies")
        # speculative decoding (ISSUE 5): draft tokens the n-gram
        # proposer put into verify spans vs how many the target model
        # accepted; spec_rollback_pages counts pages the rejected tails
        # returned (must be matched by truncate — the leak audit's
        # over-provision check is the hard guarantee, this the gauge)
        self.spec_proposed_tokens = Counter("spec_proposed_tokens")
        self.spec_accepted_tokens = Counter("spec_accepted_tokens")
        self.spec_rollback_pages = Counter("spec_rollback_pages")
        # fused verify-in-scan (ISSUE 18): horizons that carried drafts
        # through decode_multi_spec (one drain each), and proposed-but-
        # rejected verify positions — the waste adaptive-k exists to
        # shrink on low-acceptance streams
        self.spec_fused_horizons = Counter("spec_fused_horizons")
        self.spec_dead_positions = Counter("spec_dead_positions")
        # multi-step decode (ISSUE 6): host_syncs counts every blocking
        # device->host drain the engine performs (one per step on the
        # s=1 path, one per HORIZON on the multi-step path — the number
        # the decode_horizon knob exists to shrink);
        # decode_horizon_steps counts device decode steps executed
        # inside decode_multi horizons; horizon_overshoot_tokens counts
        # drained tokens discarded because their request stopped earlier
        # in the horizon (their pages are reclaimed on the spot)
        self.host_syncs = Counter("host_syncs")
        self.decode_horizon_steps = Counter("decode_horizon_steps")
        self.horizon_overshoot_tokens = Counter("horizon_overshoot_tokens")
        # zero-bubble pipelined loop (ISSUE 11): planned_ahead_steps
        # counts steps whose host planning ran while a previous launch
        # was still in flight on the device. Where a step's time goes
        # (plan, batch build, launch, drain, commit) is read from the
        # engine's spans against the device trace, not counted here
        self.planned_ahead_steps = Counter("planned_ahead_steps")
        # tiered KV offload (ISSUE 10): offload_spill_pages counts device
        # pages copied to the host tier (preemption spills AND prefix
        # demotions), pagein_pages counts pages restored to device, and
        # pagein_hidden_pages the subset whose device_put was issued in
        # an EARLIER engine step than the fence that consumed it — i.e.
        # the host->device copy had a whole step of device compute to
        # hide behind (pagein_hidden_ratio is the overlap headline).
        # offload_resumes / offload_recompute_fallbacks split resumed
        # requests by path; host_tier_drops counts spills a full tier
        # refused (those resumes degrade to recompute, exactness kept).
        self.offload_spill_pages = Counter("offload_spill_pages")
        self.pagein_pages = Counter("pagein_pages")
        self.pagein_hidden_pages = Counter("pagein_hidden_pages")
        self.offload_resumes = Counter("offload_resumes")
        self.offload_recompute_fallbacks = Counter(
            "offload_recompute_fallbacks")
        self.host_tier_drops = Counter("host_tier_drops")
        self.host_tier_bytes = Gauge("host_tier_bytes")
        self.host_tier_pages_used = Gauge("host_tier_pages_used")
        # prefill/decode split (ISSUE 12): handoffs_out counts requests
        # a prefill-role engine staged for migration after their first
        # sampled token (handoff_pages_out = KV pages spilled for them);
        # handoffs_in counts requests a decode-role engine accepted with
        # a wire-transferred page payload (handoff_pages_in = pages
        # imported, content-hash-verified at receive); a handoff whose
        # pages could not ride along — no host tier, tier full — lands
        # in handoff_recompute_fallbacks and resumes by recompute,
        # token-exact as ever
        self.handoffs_out = Counter("handoffs_out")
        self.handoffs_in = Counter("handoffs_in")
        self.handoff_pages_out = Counter("handoff_pages_out")
        self.handoff_pages_in = Counter("handoff_pages_in")
        self.handoff_recompute_fallbacks = Counter(
            "handoff_recompute_fallbacks")
        # cluster-wide KV store (ISSUE 14): handoff_bytes_out counts
        # raw page-payload bytes a handoff actually serialized (the
        # byte-copy path; slot-reference handoffs over the shared
        # store add ZERO here — the number the bench arms compare);
        # store_hit_pages counts pages this engine paged in from the
        # host-wide content index (a sibling's demotion served this
        # replica), store_dedup_pages counts copies skipped because
        # the chain was already store-resident
        self.handoff_bytes_out = Counter("handoff_bytes_out")
        self.store_hit_pages = Counter("store_hit_pages")
        self.store_dedup_pages = Counter("store_dedup_pages")
        self.decode_steps = Counter("decode_steps")
        self.queue_depth = Gauge("queue_depth")
        self.running = Gauge("running")
        self.prefix_cached_pages = Gauge("prefix_cached_pages")
        # instrumented-pool counters (ISSUE 4), mirrored from the
        # runner's host-side accounting each step: KV-pool bytes the
        # chosen attention path actually touched vs what the gather
        # reference path would have read for the same calls — the
        # CPU-countable form of the ragged kernel's bandwidth win
        self.attn_kv_bytes_read = Gauge("attn_kv_bytes_read")
        self.attn_kv_bytes_gather = Gauge("attn_kv_bytes_gather")
        # blocks of pages the ragged kernel's few-rows walks folded (one
        # layer's walk a launch) and those of them folded in full, as a
        # walk's edge blocks are: 1 - edge / all is how often the lean
        # fold engages; mirrored from the runner like the bytes
        self.ragged_blocks = Gauge("ragged_blocks")
        self.ragged_edge_blocks = Gauge("ragged_edge_blocks")
        # quantized collectives (ISSUE 15), mirrored from the runner's
        # host-side comm accounting each step: wire bytes the
        # row-parallel allreduces moved PER SHARD at the configured
        # comm_dtype (int8 code bytes PLUS the per-(row, chunk) scale
        # bytes — honest accounting) vs the fp32 cost of the same
        # calls; the reduction gauge is their ratio, i.e. the measured
        # interconnect win, CPU-countable like the attention bytes
        self.tp_comm_bytes = Gauge("tp_comm_bytes")
        self.tp_comm_bytes_fp32 = Gauge("tp_comm_bytes_fp32")
        self.tp_comm_bytes_reduction_x = Gauge("tp_comm_bytes_reduction_x")
        # the gather direction (ISSUE 19): wire bytes the column-
        # parallel all-gathers (the lm_head logits path) moved per
        # shard at the configured comm_dtype vs fp32 — same honest
        # scale-bytes-counted accounting as the allreduce gauges
        self.tp_gather_bytes = Gauge("tp_gather_bytes")
        self.tp_gather_bytes_fp32 = Gauge("tp_gather_bytes_fp32")
        self.tp_gather_bytes_reduction_x = Gauge(
            "tp_gather_bytes_reduction_x")
        # weight-ladder accounting (ISSUE 19): logical fp32 weight
        # bytes over resident bytes (packed int4 codes + group scales /
        # fp8 casts, scale bytes counted; 1.0 on fp32 runners) —
        # measured from what the params dict actually stores
        self.weight_bytes_reduction_x = Gauge("weight_bytes_reduction_x")
        # quantized-KV accounting (ISSUE 9): per-page byte reduction of
        # the pool vs storing at the logical dtype (scale bytes counted;
        # 1.0 on fp32 pools), and the matching concurrent-sessions-per-
        # fixed-HBM factor — page count per byte budget scales by the
        # same ratio. Set from KVCachePool geometry, i.e. MEASURED from
        # what the pools actually store, never assumed
        self.kv_bytes_reduction_x = Gauge("kv_bytes_reduction_x")
        self.sessions_per_pool_x = Gauge("sessions_per_pool_x")
        self.pool_used_pages = Gauge("pool_used_pages")
        self.pool_utilization = Gauge("pool_utilization")
        self.batch_occupancy = Histogram("batch_occupancy")
        self.ttft_s = Histogram("ttft_s")
        self.e2e_latency_s = Histogram("e2e_latency_s")
        # gauge writes a step put off (`put_off`), settled by whoever
        # reads or writes a gauge first
        self.owed: Optional[Callable[[], None]] = None
        for inst in vars(self).values():
            if isinstance(inst, Gauge):
                inst.due = self.settle

    def put_off(self, write: Callable[[], None]) -> None:
        """Owe the gauges `write()`: the engine's end-of-step readings,
        taken when the step ended and written while the device runs the
        next one. Whoever reads or sets a gauge first settles what is
        owed, so no reader can tell; one debt at a time."""
        self.settle()
        self.owed = write

    def settle(self) -> None:
        # taken first: the gauge writes below come back here (`due`)
        write, self.owed = self.owed, None
        if write is not None:
            write()

    def spec_acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens (0.0 when nothing proposed)."""
        p = self.spec_proposed_tokens.value
        return self.spec_accepted_tokens.value / p if p > 0 else 0.0

    def pagein_hidden_ratio(self) -> float:
        """Fraction of paged-in pages whose host->device transfer was
        issued at least one engine step before the fence that read them
        (ISSUE 10) — the overlap the async double-buffered page-in
        exists to create. 0.0 when nothing paged in."""
        p = self.pagein_pages.value
        return self.pagein_hidden_pages.value / p if p > 0 else 0.0

    def host_syncs_per_token(self) -> float:
        """Blocking device->host drains per generated token (ISSUE 6) —
        1.0 on the per-step loop, ~1/s with decode_horizon=s."""
        t = self.tokens_generated.value
        return self.host_syncs.value / t if t > 0 else 0.0

    def steps_per_token(self) -> float:
        """Engine steps per generated token — the number speculation
        drives BELOW 1/batch-occupancy: each accepted draft token is a
        token that never paid its own engine step."""
        t = self.tokens_generated.value
        return self.decode_steps.value / t if t > 0 else 0.0

    def snapshot(self) -> Dict[str, float]:
        return {
            "requests_added": self.requests_added.value,
            "requests_finished": self.requests_finished.value,
            "preemptions": self.preemptions.value,
            "requests_timed_out": self.requests_timed_out.value,
            "requests_aborted": self.requests_aborted.value,
            "step_retries": self.step_retries.value,
            "nan_logit_events": self.nan_logit_events.value,
            "shed_requests": self.shed_requests.value,
            "tokens_generated": self.tokens_generated.value,
            "moe_tokens_routed": self.moe_tokens_routed.value,
            "moe_local_pairs": self.moe_local_pairs.value,
            "moe_experts_touched": self.moe_experts_touched.value,
            "latent_copy_groups": self.latent_copy_groups.value,
            "latent_run_groups": self.latent_run_groups.value,
            "dsa_keys_scored": self.dsa_keys_scored.value,
            "dsa_keys_selected": self.dsa_keys_selected.value,
            "delta_decode_seq_steps": self.delta_decode_seq_steps.value,
            "delta_prefill_tokens": self.delta_prefill_tokens.value,
            "delta_prefill_positions": self.delta_prefill_positions.value,
            "state_slot_resets": self.state_slot_resets.value,
            "state_slots_live": self.state_slots_live.value,
            "ssm_decode_seq_steps": self.ssm_decode_seq_steps.value,
            "ssm_prefill_tokens": self.ssm_prefill_tokens.value,
            "cross_rows_skipped": self.cross_rows_skipped.value,
            "window_pages_held": self.window_pages_held.value,
            "window_pages_whole_context":
                self.window_pages_whole_context.value,
            "window_pages_returned": self.window_pages_returned.value,
            "prefill_tokens": self.prefill_tokens.value,
            "prefill_chunks": self.prefill_chunks.value,
            "prefix_hit_tokens": self.prefix_hit_tokens.value,
            "cow_copies": self.cow_copies.value,
            "prefix_cached_pages": self.prefix_cached_pages.value,
            "attn_kv_bytes_read": self.attn_kv_bytes_read.value,
            "attn_kv_bytes_gather": self.attn_kv_bytes_gather.value,
            "ragged_blocks": self.ragged_blocks.value,
            "ragged_edge_blocks": self.ragged_edge_blocks.value,
            "tp_comm_bytes": self.tp_comm_bytes.value,
            "tp_comm_bytes_fp32": self.tp_comm_bytes_fp32.value,
            "tp_comm_bytes_reduction_x":
                self.tp_comm_bytes_reduction_x.value,
            "tp_gather_bytes": self.tp_gather_bytes.value,
            "tp_gather_bytes_fp32": self.tp_gather_bytes_fp32.value,
            "tp_gather_bytes_reduction_x":
                self.tp_gather_bytes_reduction_x.value,
            "weight_bytes_reduction_x":
                self.weight_bytes_reduction_x.value,
            "kv_bytes_reduction_x": self.kv_bytes_reduction_x.value,
            "sessions_per_pool_x": self.sessions_per_pool_x.value,
            "spec_proposed_tokens": self.spec_proposed_tokens.value,
            "spec_accepted_tokens": self.spec_accepted_tokens.value,
            "spec_rollback_pages": self.spec_rollback_pages.value,
            "spec_fused_horizons": self.spec_fused_horizons.value,
            "spec_dead_positions": self.spec_dead_positions.value,
            "spec_acceptance_rate": self.spec_acceptance_rate(),
            "steps_per_token": self.steps_per_token(),
            "host_syncs": self.host_syncs.value,
            "host_syncs_per_token": self.host_syncs_per_token(),
            "decode_horizon_steps": self.decode_horizon_steps.value,
            "horizon_overshoot_tokens": self.horizon_overshoot_tokens.value,
            "planned_ahead_steps": self.planned_ahead_steps.value,
            "offload_spill_pages": self.offload_spill_pages.value,
            "pagein_pages": self.pagein_pages.value,
            "pagein_hidden_pages": self.pagein_hidden_pages.value,
            "pagein_hidden_ratio": self.pagein_hidden_ratio(),
            "offload_resumes": self.offload_resumes.value,
            "offload_recompute_fallbacks":
                self.offload_recompute_fallbacks.value,
            "host_tier_drops": self.host_tier_drops.value,
            "host_tier_bytes": self.host_tier_bytes.value,
            "host_tier_pages_used": self.host_tier_pages_used.value,
            "handoffs_out": self.handoffs_out.value,
            "handoffs_in": self.handoffs_in.value,
            "handoff_pages_out": self.handoff_pages_out.value,
            "handoff_pages_in": self.handoff_pages_in.value,
            "handoff_recompute_fallbacks":
                self.handoff_recompute_fallbacks.value,
            "handoff_bytes_out": self.handoff_bytes_out.value,
            "store_hit_pages": self.store_hit_pages.value,
            "store_dedup_pages": self.store_dedup_pages.value,
            "decode_steps": self.decode_steps.value,
            "queue_depth": self.queue_depth.value,
            "queue_depth_peak": self.queue_depth.peak,
            "running": self.running.value,
            "pool_used_pages": self.pool_used_pages.value,
            "pool_utilization_peak": self.pool_utilization.peak,
            "batch_occupancy_mean": self.batch_occupancy.mean,
            "ttft_s_p50": self.ttft_s.percentile(50),
            "ttft_s_p99": self.ttft_s.percentile(99),
            "ttft_s_mean": self.ttft_s.mean,
            "e2e_latency_s_p50": self.e2e_latency_s.percentile(50),
            "e2e_latency_s_p99": self.e2e_latency_s.percentile(99),
        }
