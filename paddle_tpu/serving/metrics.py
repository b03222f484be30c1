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

    p50 = property(lambda self: self.percentile(50))
    p99 = property(lambda self: self.percentile(99))


# The engine's OWN instruments, a line each: the attribute of EngineMetrics
# (and its key in snapshot()), its kind, and whether a tier of replicas
# SUMS its value (`aggregate_snapshots`: an event count, or a level that
# adds up over engines; the rest are ratios and per-engine levels). What a
# runner's programs count and what a runner or a pool keeps as gauges is
# not here: the engine declares those by the names THEY give (`declare`).
_OWN = (
    ("requests_added", Counter, True),
    ("requests_finished", Counter, True),
    ("preemptions", Counter, True),
    # every abnormal outcome is counted, so an overloaded or faulty
    # deployment shows in snapshot() and not in a stack trace
    ("requests_timed_out", Counter, True),
    ("requests_aborted", Counter, True),
    ("step_retries", Counter, True),
    ("nan_logit_events", Counter, True),
    ("shed_requests", Counter, True),
    ("tokens_generated", Counter, True),
    # prefill_tokens are tokens prefill chunks COMPUTED; a prefix-cache
    # hit skips the compute and lands in prefix_hit_tokens, so computed +
    # hit = total context and the hits ARE the saving
    ("prefill_tokens", Counter, True),
    ("prefill_chunks", Counter, True),
    ("prefix_hit_tokens", Counter, True),
    ("cow_copies", Counter, True),
    ("prefix_cached_pages", Gauge, True),
    # speculative decoding: draft tokens put into verify spans and those
    # the target accepted; pages the rejected tails returned (the leak
    # audit is the guarantee, this the gauge); horizons that carried
    # drafts through decode_multi_spec (one drain each) and proposed-but-
    # rejected verify positions, the waste adaptive-k exists to shrink
    ("spec_proposed_tokens", Counter, True),
    ("spec_accepted_tokens", Counter, True),
    ("spec_rollback_pages", Counter, True),
    ("spec_fused_horizons", Counter, True),
    ("spec_dead_positions", Counter, True),
    # host_syncs: every blocking device->host drain (one a step on the
    # s=1 path, one a HORIZON on the multi-step path, and a counting
    # runner's counts ride it: they add none); device decode steps run
    # inside decode_multi horizons; drained tokens discarded because
    # their request stopped earlier in the horizon (pages reclaimed on
    # the spot)
    ("host_syncs", Counter, True),
    ("decode_horizon_steps", Counter, True),
    ("horizon_overshoot_tokens", Counter, True),
    # steps whose host planning ran while a previous launch was still in
    # flight. Where a step's TIME goes is read from the engine's spans
    # against the device trace, not counted here
    ("planned_ahead_steps", Counter, True),
    # tiered KV offload: device pages copied to the host tier (preemption
    # spills AND prefix demotions), pages restored, and those of them
    # whose device_put was issued in an EARLIER step than the fence that
    # consumed it (a whole step of device compute to hide behind);
    # resumed requests by path; spills a full tier refused (those resumes
    # degrade to recompute, exactness kept)
    ("offload_spill_pages", Counter, True),
    ("pagein_pages", Counter, True),
    ("pagein_hidden_pages", Counter, True),
    ("offload_resumes", Counter, True),
    ("offload_recompute_fallbacks", Counter, True),
    ("host_tier_drops", Counter, True),
    ("host_tier_bytes", Gauge, True),
    ("host_tier_pages_used", Gauge, False),
    # prefill/decode split: requests a prefill-role engine staged for
    # migration after their first token and the KV pages spilled for
    # them; requests a decode-role engine accepted with a wire-transferred
    # payload and the pages imported (content-hash-verified); a handoff
    # whose pages could not ride along resumes by recompute, token-exact
    ("handoffs_out", Counter, True),
    ("handoffs_in", Counter, True),
    ("handoff_pages_out", Counter, True),
    ("handoff_pages_in", Counter, True),
    ("handoff_recompute_fallbacks", Counter, True),
    # cluster-wide KV store: raw page-payload bytes a handoff serialized
    # (slot-reference handoffs over the shared store add ZERO); pages
    # paged in from the host-wide content index; copies skipped because
    # the chain was already store-resident
    ("handoff_bytes_out", Counter, True),
    ("store_hit_pages", Counter, True),
    ("store_dedup_pages", Counter, True),
    ("decode_steps", Counter, True),
    ("queue_depth", Gauge, True),
    ("running", Gauge, True),
    ("pool_used_pages", Gauge, True),
    ("pool_utilization", Gauge, False),
    # MEASURED from what the params dict and the pools actually store,
    # scale bytes counted, never assumed: logical fp32 weight bytes over
    # resident bytes; a page's bytes at the logical dtype over its bytes
    # as stored, and the matching sessions-per-fixed-HBM factor (1.0 on
    # fp32 runners and pools)
    ("weight_bytes_reduction_x", Gauge, False),
    ("kv_bytes_reduction_x", Gauge, False),
    ("sessions_per_pool_x", Gauge, False),
    ("batch_occupancy", Histogram, False),
    # from add_request() to the request's first sampled token (admission
    # wait + prefill), by the injectable clock
    ("ttft_s", Histogram, False),
    ("e2e_latency_s", Histogram, False),
)

# What snapshot() shows of an instrument where that is not its value
# alone, each a property of its kind: key `<name>` for "value",
# `<name>_<stat>` for the rest.
_SHOWN = {"queue_depth": ("value", "peak"), "pool_utilization": ("peak",),
          "batch_occupancy": ("mean",), "ttft_s": ("p50", "p99", "mean"),
          "e2e_latency_s": ("p50", "p99")}

# snapshot() keys made of two others, numerator over denominator (0.0
# over nothing), wherever both are there; a tier makes them again from
# its SUMS (per-replica ratios over different traffic cannot be averaged
# honestly). The last two divide gauges a tensor-parallel runner keeps:
# the fp32 cost of its collectives over the wire bytes they moved.
RATIOS = {
    "spec_acceptance_rate": ("spec_accepted_tokens", "spec_proposed_tokens"),
    "pagein_hidden_ratio": ("pagein_hidden_pages", "pagein_pages"),
    "steps_per_token": ("decode_steps", "tokens_generated"),
    "host_syncs_per_token": ("host_syncs", "tokens_generated"),
    "tp_comm_bytes_reduction_x": ("tp_comm_bytes_fp32", "tp_comm_bytes"),
    "tp_gather_bytes_reduction_x": ("tp_gather_bytes_fp32",
                                    "tp_gather_bytes"),
}


def _key(name: str, stat: str) -> str:
    return name if stat == "value" else f"{name}_{stat}"


def _over(num: float, den: float) -> float:
    return num / den if den > 0 else 0.0


def _with_ratios(out: Dict[str, float]) -> Dict[str, float]:
    for key, (num, den) in RATIOS.items():
        if num in out and den in out:
            out[key] = _over(out[num], out[den])
    return out


# How a tier merges snapshots, told from their keys alone (plain dicts
# cross a process boundary, and a runner's or a pool's keys are not known
# here): a key is SUMMED unless it is named below. Peaks take the max;
# ratios are made again from the sums; the engine's own levels that do not
# add, means and exact percentiles are dropped (scalar snapshots cannot be
# merged into a percentile: tier-level latency lives in the router's own
# histograms).
MAX_KEYS = tuple(_key(name, "peak") for name, stats in _SHOWN.items()
                 if "peak" in stats)
NOT_SUMMED = frozenset(
    [_key(name, stat) for name, stats in _SHOWN.items() for stat in stats
     if stat != "value"]
    + [name for name, _, summed in _OWN if not summed] + list(RATIOS))


def aggregate_snapshots(snaps) -> Dict[str, float]:
    """Merge several EngineMetrics snapshots into one tier-level view
    (`NOT_SUMMED` has the rule): the engine's own sums are there even over
    no snapshot, a runner's and a pool's keys where a snapshot has them."""
    snaps = list(snaps)
    out: Dict[str, float] = {name: 0.0 for name, _, summed in _OWN if summed}
    out.update(dict.fromkeys(MAX_KEYS, 0.0))
    for s in snaps:
        for k, v in s.items():
            if k in MAX_KEYS:
                out[k] = max(out[k], float(v))
            elif k not in NOT_SUMMED:
                out[k] = out.get(k, 0.0) + float(v)
    _with_ratios(out)
    out["replicas"] = float(len(snaps))
    return out


class EngineMetrics:
    """The engine's instrument panel, read through snapshot().

    Counts, gauges and histograms of what the engine did, counted where
    it happens. How long each part of a step took is not here: the
    engine's spans (`paddle_tpu.profiler`) carry that. Every instrument
    is an attribute under its name: the engine's own (`_OWN`), and what
    the engine declared for its runner and its pool (`declare`).
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = clock or time.monotonic
        # gauge writes a step put off (`put_off`), settled by whoever
        # reads or writes a gauge first
        self.owed: Optional[Callable[[], None]] = None
        self._instruments: Dict[str, object] = {}
        for name, kind, _ in _OWN:
            self.declare(kind, (name,))

    def declare(self, kind, names) -> list:
        """The instruments of `kind` under `names`, made where they are
        new: how a runner's counts (`COUNTS`) and a runner's and a pool's
        gauges come to be here, under the names their owner gives and
        nobody else spells. They show in snapshot() from then on, and a
        tier sums them."""
        out = []
        for name in names:
            inst = self._instruments.get(name)
            if inst is None:
                if hasattr(self, name):
                    raise ValueError(f"an instrument cannot be named "
                                     f"{name!r}: EngineMetrics has that")
                inst = self._instruments[name] = kind(name)
                if kind is Gauge:
                    inst.due = self.settle
                setattr(self, name, inst)
            elif type(inst) is not kind:
                raise ValueError(
                    f"{name!r} is a {type(inst).__name__} here and cannot "
                    f"be declared a {kind.__name__}")
            out.append(inst)
        return out

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

    def ratio(self, key: str) -> float:
        """One of `RATIOS` as the instruments stand (0.0 over nothing):
        spec_acceptance_rate, accepted over proposed draft tokens;
        pagein_hidden_ratio, the share of paged-in pages whose
        host->device copy was issued a step before the fence that read
        them; host_syncs_per_token, 1.0 on the per-step loop and ~1/s
        with decode_horizon=s; steps_per_token, which speculation drives
        below 1/batch-occupancy."""
        return _over(*(self._instruments[n].value for n in RATIOS[key]))

    def snapshot(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, inst in self._instruments.items():
            for stat in _SHOWN.get(name, ("value",)):
                out[_key(name, stat)] = getattr(inst, stat)
        return _with_ratios(out)
