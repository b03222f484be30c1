"""ServingEngine: continuous-batching generation over the paged KV pool.

Reference: the serving loop the reference runs above
block_multihead_attention (PaddleNLP llm predictor / fastdeploy): an
admission queue feeds a fixed-slot decode batch; prefill computes a new
request's context in CHUNKS bounded by a per-step token budget,
interleaved with decode so a long prompt never stalls running requests
for more than one budget per step; every step decodes for every
decode-phase request in a single batched call through the paged-attention
kernel; finished requests free their pages and their slot is refilled
from the queue — the batch never drains to refill.

One step is plan (deadlines, admission, prefix matching, page-in staging,
page reservation) / build (the batch's operands) / launch (one runner
call) / drain (the host waits for the device) / commit (tokens appended,
stops and lengths handled, pages released). `EngineConfig` holds every
option and documents it; the options choose WHICH launch a step makes —
one decode step, a device-resident horizon of `decode_horizon` steps, a
fused speculative horizon, or one ragged call over prefill chunks and
decode spans together — and every kind runs through the same skeleton
(`ServingEngine._launch`: build, retry, quarantine, defer or drain). With
`pipelined` the launch of step N stays in flight while step N+1 is
planned; jax's async dispatch makes that a reorder, with ONE launch in
flight and pool updates kept functional.

The engine is deterministic end-to-end: FCFS admission, sorted-free-list
pages, greedy (or seeded per-request) sampling, step-indexed sample keys
that survive preemption. `naive_generate` is the scheduling oracle: the
same runner, one request at a time, no scheduler — continuous batching
under every option must reproduce its tokens exactly.

Every failure mode has a defined outcome; no step() raises for load- or
fault-induced conditions:

  finish_reason   trigger
  "stop"/"length" normal completion
  "timeout"       SamplingParams.timeout_s exceeded (queue wait counts)
  "aborted"       engine.abort(request_id)
  "shed"          bounded queue overflowed under shed_policy="drop_oldest"
  "error"         prefill failed past max_step_retries, a decode batch
                  was quarantined, or NaN/Inf logits under nan_policy
                  "abort" (or with no finite entry at all)

One thing does leave step(): an UnrecoverableStepError — a step the
backend's compiler refuses (StepCompileError, raised by the first call of
a new jit-cache entry) or KV pools a failed launch had already been given
by donation (DonatedPoolError). Retrying either fails the same way for
every request, so the serve stops instead of answering all with "error".

Transient runner failures retry with bounded exponential backoff;
`snapshot()`/`restore()` serialize all request state for crash-safe
relaunch (KV rebuilds through the recompute-on-resume path); the
opt-in invariant auditor (`audit=True` or PADDLE_TPU_SERVING_AUDIT=1)
proves page/slot/block-table consistency after every step.
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import asdict, dataclass, fields, replace
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import profiler as _prof
from paddle_tpu.serving.detokenize import StreamDetokenizer
from paddle_tpu.serving.kv_cache import (
    KVCachePool, OffloadRecord, SCRATCH_PAGE,
)
from paddle_tpu.serving.metrics import Counter, EngineMetrics, Gauge
from paddle_tpu.serving.model_runner import (
    RUNNER_OPTIONS, PagedModelRunner, bucket_len, build_runner,
    require_retryable,
)
from paddle_tpu.serving.resilience import QueueFullError, audit_engine
from paddle_tpu.serving.scheduler import (
    FCFSScheduler, Request, RequestState, SamplingParams,
    ensure_arrival_counter_above,
)
from paddle_tpu.serving.speculate import (AdaptiveK, DraftModelProposer,
                                          NgramProposer, shadow_runner)

logger = logging.getLogger(__name__)


@dataclass
class TokenEvent:
    """One streamed token (the engine's per-step output unit)."""

    request_id: str
    token: int
    index: int                   # position within the generated sequence
    finished: bool = False
    finish_reason: Optional[str] = None


@dataclass
class RequestOutput:
    request_id: str
    prompt_tokens: List[int]
    output_tokens: List[int]
    finish_reason: str
    num_preemptions: int = 0
    ttft_s: Optional[float] = None
    e2e_s: Optional[float] = None


@dataclass(frozen=True)
class EngineConfig:
    """Every serialisable option of a ServingEngine: one field an option.
    `ServingEngine(runner, **options)` builds it, the engine carries each
    field as an attribute of the same name, `snapshot()["config"]` is this
    record as a dict (plus the runner's `recipe()` and the mesh's shape)
    and `restore()` builds it back, a missing key taking its default.
    Every option but `num_blocks` defaults to the plain loop: one decode
    step a launch, one host sync a step, nothing shared or offloaded.
    None of them changes a token: what the options change is which
    launches a step makes and when the host waits.

    The pool
      num_blocks           pages in the device pool (page 0 is scratch)
      block_size           tokens a page; None = the runner's (they share
                           the pool layout, so another value is an error)
      max_batch_size       decode slots
      max_model_len        longest prompt + generation; None = the
                           runner's rope / position table length
    Load and faults
      max_queue_depth      bound on the waiting queue; None = unbounded
      shed_policy          at the bound: "reject" (add_request raises
                           QueueFullError) or "drop_oldest" (the oldest
                           waiting request is shed)
      admission_watermark  pool fraction beyond which admission pauses
      max_step_retries     transient-failure retries of one runner call
      retry_backoff_s      first back-off; doubles with every retry
      nan_policy           "abort" ends a request on NaN/Inf logits;
                           "greedy" argmaxes the finite entries instead
    Prefill
      max_prefill_tokens_per_step
                           per-step prefill token budget: a long prompt is
                           computed in chunks of at most this many tokens,
                           interleaved with decode (None = one chunk)
      enable_prefix_cache  full KV pages are refcounted and hash-indexed;
                           admission maps the longest cached page-aligned
                           prefix into the block table and any write to a
                           shared page forks it first (copy-on-write)
      ragged_batch         a step that has both prefill chunks and decode
                           rows makes ONE `runner.ragged_step` call for all
                           of them (a request completing its prefill in it
                           decodes its first token the step after)
    The decode loop
      decode_horizon       s > 1: a decode batch with no chunk in flight
                           runs up to s steps in ONE `runner.decode_multi`
                           launch (a device-resident scan feeding each
                           token back) and the host drains one [B, s]
                           buffer; the scheduler pre-commits the horizon's
                           pages (trims s, never preempts); overshoot past
                           a stop is discarded and its pages reclaimed
      horizon_sampling     horizons also for temperature > 0: per-request
                           seeded key schedules ride inside the scan,
                           bit-identical to the per-step streams (a batch
                           mixing (top_k, top_p) pairs stays per-step)
      horizon_early_stop   each horizon row carries its stop tokens and
                           remaining budget into the scan; a hit freezes
                           the row's KV writes and marks its tail dead, so
                           overshoot is neither computed nor replayed
      pipelined            a step plans while the PREVIOUS step's launch
                           still runs on the device, commits that launch,
                           then dispatches its own and leaves it in
                           flight; a step returns the previous launch's
                           tokens, run() / flush() drain the tail
    Speculation
      num_speculative_tokens
                           k > 0: up to k draft tokens ride each decode
                           row into one verify launch; the longest draft
                           prefix the target model reproduces (argmax
                           under greedy, the request's seeded sample under
                           temperature > 0) is accepted at once and the
                           rejected tail's KV rolls back through the
                           refcounts. Inside the scan when no chunk shares
                           the step (`runner.decode_multi_spec`), else one
                           full-logits ragged call
      spec_max_ngram / spec_min_ngram
                           suffix n-gram lengths the prompt-lookup
                           proposer matches (longest first, latest wins)
      spec_ngram_window    scan only the last N context tokens (None = all)
      spec_adaptive_k      an EWMA of accepted / proposed clamps each
                           request's k into [0, num_speculative_tokens]
      spec_draft_model     None = n-gram prompt lookup; "shadow[:int8|
                           int4|fp8|fp32]" = a weight-quantized shadow of
                           the target runner proposing greedy chains from
                           a small pool of its own; a runner instance
                           (ServingEngine's argument, recorded here as
                           "custom": a snapshot cannot rebuild it)
      spec_draft_blocks    pages of the draft model's pool (None = its
                           default)
    The host tier
      host_tier_pages      N > 0: preemption spills a victim's own pages
                           to N pages of pinned host RAM and prefix-cache
                           eviction demotes there; resume restores by a
                           page-in staged a step ahead (`device_put`
                           issued at the end of the step before, scatter
                           at the fence after admission); a miss
                           recomputes. Host pages are not in a snapshot
      host_tier_headroom   the admission watermark counts free host slots
                           as near-headroom
      pagein_prefetch      how many queue-head offloaded requests get
                           their pages staged at the end of a step (0 =
                           stage at the fence itself)
      spill_async          the device->host copy of a spill runs on a
                           worker thread against the immutable pool
                           snapshot; every reader of the bytes joins it
      role                 "mixed" | "prefill" (prefill, sample the first
                           token, then stage the request and its pages in
                           the handoff buffer for a sibling) | "decode" (as
                           "mixed"; the router sends it handoffs, and it
                           still prefills for the recompute fallback)
    """

    num_blocks: int
    block_size: Optional[int] = None
    max_batch_size: int = 8
    max_model_len: Optional[int] = None
    max_queue_depth: Optional[int] = None
    shed_policy: str = "reject"
    admission_watermark: float = 1.0
    max_step_retries: int = 2
    retry_backoff_s: float = 0.02
    nan_policy: str = "abort"
    max_prefill_tokens_per_step: Optional[int] = None
    enable_prefix_cache: bool = False
    host_tier_pages: int = 0
    host_tier_headroom: bool = False
    pagein_prefetch: int = 2
    ragged_batch: bool = False
    decode_horizon: int = 1
    pipelined: bool = False
    horizon_sampling: bool = False
    horizon_early_stop: bool = False
    spill_async: bool = False
    role: str = "mixed"
    num_speculative_tokens: int = 0
    spec_max_ngram: int = 3
    spec_min_ngram: int = 1
    spec_adaptive_k: bool = False
    spec_draft_model: Optional[str] = None
    spec_draft_blocks: Optional[int] = None
    spec_ngram_window: Optional[int] = None

    def __post_init__(self):
        put = lambda name, v: object.__setattr__(self, name, v)
        # flags and counts arrive as whatever the caller had (a numpy
        # int, 0 for False); the record holds plain ones, so that two
        # records compare and one serialises
        plain = {"bool": bool, "int": int}
        for f in fields(self):
            if f.type in plain:
                put(f.name, plain[f.type](getattr(self, f.name)))
        for name in ("spec_ngram_window", "spec_draft_blocks"):
            put(name, int(getattr(self, name) or 0) or None)
        if self.shed_policy not in ("reject", "drop_oldest"):
            raise ValueError(f"shed_policy={self.shed_policy!r}; expected "
                             "'reject' or 'drop_oldest'")
        if self.nan_policy not in ("abort", "greedy"):
            raise ValueError(f"nan_policy={self.nan_policy!r}; expected "
                             "'abort' or 'greedy'")
        if self.max_queue_depth is not None and self.max_queue_depth < 1:
            raise ValueError("max_queue_depth must be >= 1 (None = unbounded)")
        if self.host_tier_pages < 0:
            raise ValueError("host_tier_pages must be >= 0 (0 = no host "
                             "tier)")
        if self.pagein_prefetch < 0:
            raise ValueError("pagein_prefetch must be >= 0")
        if self.decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1 (1 = sync with "
                             "the host every step)")
        if self.role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"role={self.role!r}; expected 'mixed', "
                             "'prefill', or 'decode'")
        if self.num_speculative_tokens < 0:
            raise ValueError("num_speculative_tokens must be >= 0 (0 = "
                             "speculation off)")

    def for_runner(self, recipe: dict) -> "EngineConfig":
        """This record with the two fields a runner decides filled in
        from its `recipe()`."""
        block_size = self.block_size or recipe["block_size"]
        if block_size != recipe["block_size"]:
            raise ValueError(
                f"engine block_size={block_size} != runner.block_size="
                f"{recipe['block_size']} — they share the pool layout")
        max_model_len = self.max_model_len or recipe["max_model_len"]
        if max_model_len > recipe["max_model_len"]:
            raise ValueError("max_model_len exceeds the runner's rope/pos "
                             f"table length {recipe['max_model_len']}")
        return replace(self, block_size=block_size,
                       max_model_len=max_model_len)


def seeded_sample(logits_row, seed: int, step: int, temperature: float,
                  top_k, top_p) -> int:
    """THE host-side seeded sampler (temperature > 0): one [V] row drawn
    with fold_in(key(seed), step). The in-scan horizon sampler
    (model_runner._sampled_rows) and the test stubs reproduce exactly
    this math, which is what makes temperature>0 horizons bit-identical
    to the per-step streams."""
    from paddle_tpu.models.generation import _sample

    key = jax.random.fold_in(jax.random.key(int(seed)), int(step))
    tok = _sample(jnp.asarray(logits_row)[None], key, temperature,
                  top_k, top_p)
    return int(np.asarray(tok)[0])


def sample_token(logits_row: np.ndarray, sampling: SamplingParams,
                 step: int, fallback_seed: int) -> int:
    """Sample the next token from one [V] logits row, host-side.

    Per-request keys are step-indexed (fold_in by generated-token index),
    so a preempted request resumes the identical sample stream."""
    if sampling.temperature == 0.0:
        return int(np.argmax(logits_row))
    seed = sampling.seed if sampling.seed is not None else fallback_seed
    return seeded_sample(logits_row, seed, step, sampling.temperature,
                         sampling.top_k, sampling.top_p)


def _to_host(x) -> np.ndarray:
    """THE device->host sync boundary: every blocking drain the engine
    performs funnels through here (greedy_grid's packed pull, the lazy
    full-logits row fetch, the multi-step horizon drain), so a test can
    monkeypatch this one symbol and count exactly how many times a step
    blocked on the device (the one-sync-per-step pin). Its `drain.fetch`
    span ends when the host holds the values: the later of the two edges
    that bound the device's clock (bench/README-idle.md)."""
    with _prof.span("drain.fetch"):
        return np.asarray(x)


def greedy_grid(logits):
    """Vectorized device-side greedy pass: ONE argmax and ONE finiteness
    reduction over a [..., V] logits array, computed where the logits
    live, then ONE tiny host transfer — the argmax ids and finite flags
    ride a single packed int32 array (two separate pulls would be two
    blocking syncs a decode step). The full array only crosses to host
    afterwards when a row actually needs it — temperature > 0 sampling,
    or a NaN rescue under nan_policy="greedy". Tie-breaking matches
    np.argmax (first max wins), which the batched-sampling pin test
    asserts against the host path `sample_token` / `naive_generate`
    use."""
    with _prof.span("drain.enqueue"):     # the host dispatching the pass
        stacked = jnp.stack(
            [jnp.argmax(logits, axis=-1).astype(jnp.int32),
             jnp.all(jnp.isfinite(logits), axis=-1).astype(jnp.int32)])
    packed = _to_host(stacked)
    return packed[0], packed[1].astype(bool)


@dataclass
class _LaunchKind:
    """One kind of device launch, as the data `ServingEngine._launch` runs
    it from; what the kinds share is written there, once.

    A ROW is `(request, slot, start, end, fed, draft)`: the row's batch
    slot as of launch, the position of its first fed token, one past the
    last position it may WRITE before the next drain (what copy-on-write
    must make private: 1 for a decode step, the horizon's span, a chunk's
    length), the token(s) fed, and, for the rows of a ragged step, the
    draft riding a decode span (`[]` = none) or None for a prefill
    chunk."""

    name: str
    # () -> rows, from live scheduler state: rebuilt on every attempt,
    # because page reservation may have preempted and a quarantine removed
    rows: Callable[[], list]
    # (operands, extra) -> (result, new_pools): the runner entry, looked
    # up on the runner at call time
    call: Callable
    drain: Callable              # result -> host arrays: the blocking pull
    # (_InflightLaunch, drained or None) -> events; None = not drained yet
    commit: Callable
    # rows -> what `call` takes besides the batch operands (built under
    # the same `engine.build_batch` span)
    extras: Optional[Callable] = None
    ragged: bool = False         # tokens [B, T] with q_lens, not [B]
    s: int = 1                   # scan steps before the next drain
    launched: Optional[Callable[[], None]] = None   # the kind's counters
    # fused speculative horizons: {id(request): tokens its pages were
    # funded for}, so that the auditor's over-provision check credits
    # exactly what plan_spec_horizon committed (`s` alone under-counts a
    # row with drafts)
    upcoming: Optional[dict] = None


@dataclass
class _InflightLaunch:
    """One dispatched launch: what its commit reads, and what the
    pipelined loop keeps while it is in flight. `batch` pins the rows as
    of launch time — a member aborted or expired before the commit is
    skipped at replay; `prev_pools` is the functional pool snapshot the
    launch consumed, kept so that a drain-time device error can roll back
    and rerun the step through the normal retry path."""

    kind: _LaunchKind
    batch: list
    result: object               # logits [B, V] / [B, T, V], or packed
    prev_pools: list
    extra: object = None         # what `kind.extras` built for the call

    @property
    def s(self) -> int:
        return self.kind.s

    @property
    def upcoming(self) -> Optional[dict]:
        return self.kind.upcoming


def _youngest(rows) -> Request:
    """The quarantine victim of a batch: the latest admission."""
    return max((row[0] for row in rows), key=lambda r: r.admission_index)


class ServingEngine:
    """Continuous-batching LLM serving over a paged KV cache.

    engine = ServingEngine(runner, num_blocks=64, block_size=16,
                           max_batch_size=8, max_model_len=256)
    rid = engine.add_request([1, 2, 3], SamplingParams(max_tokens=8))
    for events in iter(engine.step, []): ...   # streaming
    outputs = engine.run()                     # or drain to completion

    `options` are the fields of `EngineConfig`, which documents them; an
    unknown name is a TypeError. What a snapshot cannot hold stays a
    plain argument:
      metrics          an EngineMetrics to count into (None = a new one)
      tokenizer        id_to_bytes(tok) or decode([tok]); enables
                       stream_text()
      sleep_fn         the retry back-off's sleep (tests pass a recorder)
      audit            run resilience.audit_engine after every step
                       (None = the PADDLE_TPU_SERVING_AUDIT variable)
      kv_store         a SharedKVStore (or the process backend's
                       SharedKVStoreClient) behind the host tier in place
                       of private buffers: capacity is the store's, spills
                       and prefix demotions publish tier-wide
                       (content-addressed), admission resolves its prefix
                       chain against every replica's demotions, handoffs
                       move slot references instead of page bytes.
                       Usually wired by ServingRouter(shared_kv_pages=)
      kv_store_owner   tags this engine incarnation's references, so that
                       a dead replica's slots are reaped by refcount
      spec_draft_model the EngineConfig field, or a runner instance (same
                       tokenizer) to draft with

    Tensor parallelism and quantization are RUNNER properties, not engine
    options: pass a sharded runner (`runner.shard(mesh)`, or
    `create_engine(model, mesh=...)`) and the engine builds its K/V pools
    kv-head-sharded over the runner's mesh, in the runner's `kv_dtype`.
    Everything host-side — scheduler, block tables, refcounts, prefix
    cache, retries, snapshots — is mesh-blind, and token streams are
    identical to the single-device engine.
    """

    def __init__(self, runner: PagedModelRunner, *,
                 metrics: Optional[EngineMetrics] = None,
                 tokenizer=None,
                 sleep_fn: Optional[Callable[[float], None]] = None,
                 audit: Optional[bool] = None,
                 kv_store=None,
                 kv_store_owner: Optional[str] = None,
                 spec_draft_model=None,
                 **options):
        self.runner = runner
        recipe = runner.recipe()
        self.config = EngineConfig(
            spec_draft_model=(spec_draft_model
                              if isinstance(spec_draft_model, str)
                              else None if spec_draft_model is None
                              else "custom"),
            **options).for_runner(recipe)
        # every option under its own name: eng.decode_horizon, eng.role, ...
        vars(self).update(vars(self.config))
        # a sharded runner (runner.shard(mesh)) brings its mesh along: the
        # K/V pools are then born split on the kv-head axis over the
        # model axis — everything host-side (allocator, block tables,
        # scheduler, PrefixCache) stays replicated and mesh-blind
        self.mesh = getattr(runner, "mesh", None)
        # quantized serving is a RUNNER property like the mesh: a
        # kv_dtype="int8" runner quantizes at append time, so the engine
        # births int8 code pools + the parallel scale pools
        self.kv_dtype = recipe["kv_dtype"]
        self.pool = KVCachePool.for_runner(
            runner, self.num_blocks, mesh=self.mesh,
            model_axis=getattr(runner, "model_axis", "model"),
            state_slots=self.max_batch_size,
            window_span=max(1, self.decode_horizon))
        if self.pool.state_layers or self.pool.window is not None:
            self._refuse_state_copies(kv_store)
        if self.enable_prefix_cache:
            self.pool.enable_prefix_cache()
        # handoff buffer: requests a prefill-role engine has finished
        # prefilling (first token sampled), staged for migration —
        # request id -> OffloadRecord of its spilled pages (None = pages
        # could not ride; the receiver recomputes). The requests stay in
        # self._requests until extract_handoff()
        self._handoffs: Dict[str, Optional["OffloadRecord"]] = {}
        # the pipelined loop's single in-flight launch: dispatched at the
        # end of one step, drained + replayed at the next step's commit
        # phase (or by flush())
        self._inflight: Optional[_InflightLaunch] = None
        # the proposer validates the n-gram range; built here so that a
        # bad combination of options fails at construction time
        self.proposer = None
        if self.num_speculative_tokens:
            if spec_draft_model is not None:
                if isinstance(spec_draft_model, str):
                    base, _, dt = spec_draft_model.partition(":")
                    if base != "shadow":
                        raise ValueError(
                            f"spec_draft_model={spec_draft_model!r}; "
                            "expected a runner instance or "
                            "'shadow[:int8|int4|fp8|fp32]'")
                    draft = shadow_runner(runner, dt or "int8")
                else:
                    draft = spec_draft_model
                self.proposer = DraftModelProposer(
                    draft, num_blocks=self.spec_draft_blocks,
                    max_model_len=self.max_model_len)
            else:
                self.proposer = NgramProposer(
                    self.spec_max_ngram, self.spec_min_ngram,
                    scan_window=self.spec_ngram_window)
        self.adaptive_k = (AdaptiveK(self.num_speculative_tokens)
                          if self.num_speculative_tokens
                          and self.spec_adaptive_k else None)
        self.tokenizer = tokenizer
        self._detoks: Dict[str, StreamDetokenizer] = {}
        self.max_pages_per_seq = self.pool.blocks_for_tokens(
            self.max_model_len)
        self.scheduler = FCFSScheduler(self.pool, self.max_batch_size,
                                       self.max_pages_per_seq,
                                       self.admission_watermark,
                                       self.max_prefill_tokens_per_step,
                                       count_host_headroom=(
                                           self.host_tier_headroom))
        self._sleep = sleep_fn or time.sleep
        if audit is None:
            audit = os.environ.get("PADDLE_TPU_SERVING_AUDIT",
                                   "") not in ("", "0")
        self.audit = audit
        self.metrics = metrics or EngineMetrics()
        # what the runner's steps count on the device (`runner.COUNTS`),
        # under the names the runner gives: each launch hands its counts
        # over here, and the step's one drain reads them with its tokens.
        # The same for the gauges a runner keeps on the host
        # (`runner.GAUGES`: attributes of it) and the pool's own
        self.metrics.declare(Counter, getattr(runner, "COUNTS", ()))
        self._runner_gauges = self.metrics.declare(
            Gauge, getattr(runner, "GAUGES", ()))
        self._pool_gauges = self.metrics.declare(Gauge, self.pool.gauges(0))
        self._step_counts: list = []
        # static per-pool ratios: the measured page-byte reduction (scale
        # bytes counted) and the matching sessions-per-fixed-HBM factor —
        # 1.0 on fp32 pools
        self.metrics.kv_bytes_reduction_x.set(
            self.pool.kv_bytes_reduction_x())
        self.metrics.sessions_per_pool_x.set(
            self.pool.kv_bytes_reduction_x())
        # weight-ladder HBM ratio: logical fp32 bytes over resident bytes
        # (packed codes + group scales counted) — 1.0 on fp32 runners or
        # runners without the accessor
        wbx = getattr(runner, "weight_bytes_reduction_x", None)
        if callable(wbx):
            self.metrics.weight_bytes_reduction_x.set(float(wbx()))
        # host-RAM KV tier: built after the metrics so the tier mirrors
        # its spill/drop accounting straight into them. With `kv_store`
        # the tier is a facade over the host-wide SharedKVStore instead
        # of private buffers
        self.kv_store = kv_store
        self.kv_store_owner = (str(kv_store_owner) if kv_store_owner
                               else f"eng-{id(self):x}")
        if kv_store is not None:
            self.pool.enable_host_tier(kv_store.max_pages,
                                       metrics=self.metrics,
                                       async_spill=self.spill_async,
                                       store=kv_store,
                                       owner=self.kv_store_owner)
        elif self.host_tier_pages:
            self.pool.enable_host_tier(self.host_tier_pages,
                                       metrics=self.metrics,
                                       async_spill=self.spill_async)
        # async page-in double buffer: (slot, generation) -> (step the
        # device_put was issued, staged per-layer device arrays). The
        # generation key makes a staged transfer self-invalidating when
        # its slot is freed/reused before the fence consumes it.
        self._pagein_staged: Dict[tuple, tuple] = {}
        self._step_count = 0
        self._requests: Dict[str, Request] = {}
        self._outputs: Dict[str, RequestOutput] = {}
        # the default path's launch has no state of its own: described once
        self._decode_kind = _LaunchKind(
            "decode", self._decode_rows,
            lambda ops, _: self.runner.decode(*ops, self.pool.pools),
            greedy_grid, self._commit_logits)

    def _refuse_state_copies(self, kv_store) -> None:
        """A runner with recurrent state (`state_layout`) keeps, beside a
        request's pages, a state that only ever moves forward at the
        request's decode slot. The options below need a COPY of it (a
        shared prefix, a spill to the host, a handoff) or a ROLLBACK
        (rejected drafts), or feed several rows of several sequences to
        one launch; none of that is built, and pages without their state
        would serve wrong tokens in silence. A runner's WINDOW GROUP (a
        ring of the last positions' pages, `pool.window`) is refused with
        them for the same reason: its pages are a state that moves forward
        and gives back what fell behind, so a shared prefix, a spilled or
        handed-off sequence and a rolled-back draft would each need pages
        it no longer has. Refused by name, here."""
        asked = {
            "enable_prefix_cache": self.enable_prefix_cache,
            "host_tier_pages": self.host_tier_pages,
            "kv_store": kv_store is not None,
            "num_speculative_tokens": self.num_speculative_tokens,
            "ragged_batch": self.ragged_batch,
            "role": self.role != "mixed",
        }
        for name, on in asked.items():
            if on:
                raise ValueError(
                    f"{name} is not built for a runner with recurrent "
                    f"state ({type(self.runner).__name__}): a state slot "
                    "cannot be shared, spilled, handed off or rolled back "
                    "yet (nor can a window group's ring of pages, for the "
                    "same reason: what fell behind the window is gone), "
                    "and its pages alone would serve wrong tokens. "
                    "Supported: max_prefill_tokens_per_step, "
                    "decode_horizon, horizon_early_stop, pipelined")

    # ----------------------------------------------------------- intake

    def _check_kv_dtype(self, sampling: SamplingParams) -> None:
        """Per-request KV precision gate: a homogeneous pool
        only serves its own rung; "mixed" pools serve fp32 AND fp8
        tenants side by side (pages tagged at alloc). Loud at intake —
        a silently widened/narrowed tenant would break the byte
        accounting AND the accuracy story."""
        want = sampling.kv_dtype
        if want is None:
            return
        allowed = ({"fp32", "fp8"} if self.kv_dtype == "mixed"
                   else {self.pool.native_kv_tag()})
        if want not in allowed:
            raise ValueError(
                f"SamplingParams.kv_dtype={want!r} is not servable by "
                f"this engine's kv_dtype={self.kv_dtype!r} pool "
                f"(allowed: {sorted(allowed)}) — build the engine with "
                "kv_dtype='mixed' to serve mixed-precision tenants "
                "from one pool geometry")

    def add_request(self, prompt_tokens: Sequence[int],
                    sampling: Optional[SamplingParams] = None,
                    request_id: Optional[str] = None) -> str:
        sampling = sampling or SamplingParams()
        req = Request(prompt_tokens=list(map(int, prompt_tokens)),
                      sampling=sampling, request_id=request_id or "")
        if len(req.prompt_tokens) + sampling.max_tokens > self.max_model_len:
            raise ValueError(
                f"prompt({len(req.prompt_tokens)}) + max_tokens"
                f"({sampling.max_tokens}) exceeds max_model_len="
                f"{self.max_model_len}")
        self._check_kv_dtype(sampling)
        if (self.max_queue_depth is not None
                and self.scheduler.queue_depth >= self.max_queue_depth):
            self.metrics.shed_requests.inc()
            if self.shed_policy == "reject":
                raise QueueFullError(
                    f"admission queue full ({self.scheduler.queue_depth} "
                    f"waiting >= max_queue_depth={self.max_queue_depth}); "
                    "shed_policy='reject'")
            # drop-oldest-waiting: the queue head is shed to admit the new
            # arrival — freshness beats age under overload
            self._finish_abnormal(self.scheduler.waiting[0], "shed",
                                  counted=True)
        req.arrival_time = self.metrics.clock()
        req.queued_ns = _prof.stamp()
        self._requests[req.request_id] = req
        self.scheduler.add(req)
        self.metrics.requests_added.inc()
        self.metrics.queue_depth.set(self.scheduler.queue_depth)
        return req.request_id

    def abort(self, request_id: str, reason: str = "aborted") -> bool:
        """Cancel an in-flight request: its pages/slot are freed and the
        output surfaces with finish_reason="aborted". Returns False if the
        request is unknown or already finished."""
        req = self._requests.get(request_id)
        if req is None or req.done:
            return False
        self._finish_abnormal(req, reason)
        self.metrics.queue_depth.set(self.scheduler.queue_depth)
        return True

    def has_work(self) -> bool:
        # an in-flight launch IS work: the pipelined loop's last horizon
        # still needs its commit step even after the queue drains
        return self.scheduler.has_work() or self._inflight is not None

    def _drain(self, fn):
        """Run one blocking device->host drain under an `engine.drain`
        span — the host waiting for the device — and count it as a host
        sync once it came back."""
        with _prof.span("engine.drain"):
            out = fn()
            if self._step_counts:
                # of the launches drained just now, or of earlier ones
                # whose logits nobody read: their copies set out when the
                # launches did (`_take_counts`) and those are done, so
                # they are in host memory and reading them waits for
                # nothing
                counts, self._step_counts = self._step_counts, []
                counters = self.metrics.declare(Counter, self.runner.COUNTS)
                for c in counts:
                    for counter, n in zip(counters, np.asarray(c).tolist()):
                        counter.inc(n)
        self.metrics.host_syncs.inc()
        return out

    def _take_counts(self, counts) -> None:
        """A counting runner's hand-over (`on_step_counts`), as a
        launch's jitted call returns: the counts are an output of the
        program that has just been queued, so their copy to the host is
        queued behind it here and arrives with the step's end. The
        drain that reads them after the tokens then pays no device
        round trip of its own (0.5 ms a step with the device standing
        still, PERF.md PR 40)."""
        counts.copy_to_host_async()
        self._step_counts.append(counts)

    # ------------------------------------------------- failure plumbing

    def _finish_abnormal(self, req: Request, reason: str,
                         counted: bool = False) -> None:
        """Terminate a request on a non-token path (timeout / abort / shed
        / error): release whatever it holds, record the RequestOutput with
        the partial generation, bump the matching failure counter."""
        now = self.metrics.clock()
        if req.request_id in self._handoffs:
            # staged for handoff: not in the waiting queue —
            # release the spilled host slots and finish in place
            rec = self._handoffs.pop(req.request_id)
            if rec is not None and self.pool.host_tier is not None:
                self.pool.host_tier.free_slots(rec.slots)
            req.state = RequestState.FINISHED
            req.finish_reason = reason
        elif req.state is RequestState.RUNNING:
            self.scheduler.finish(req, reason)
        elif req.state is RequestState.WAITING:
            self.scheduler.remove_waiting(req)
            req.state = RequestState.FINISHED
            req.finish_reason = reason
        else:                                    # pragma: no cover
            return
        self._release_spec_state(req)
        req.finish_time = now
        if not counted:        # shed is pre-counted at the add_request gate
            counter = {"timeout": self.metrics.requests_timed_out,
                       "shed": self.metrics.shed_requests}.get(
                           reason, self.metrics.requests_aborted)
            counter.inc()
        self._outputs[req.request_id] = RequestOutput(
            request_id=req.request_id,
            prompt_tokens=list(req.prompt_tokens),
            output_tokens=list(req.output_tokens),
            finish_reason=reason,
            num_preemptions=req.num_preemptions,
            ttft_s=(req.first_token_time - req.arrival_time
                    if req.first_token_time is not None else None),
            e2e_s=now - req.arrival_time)

    def _expire_deadlines(self) -> None:
        """Time out every request (queued or running) past its deadline —
        queue wait counts against timeout_s, exactly like a client-side
        deadline would."""
        now = self.metrics.clock()
        for req in (*self.scheduler.running, *self.scheduler.waiting):
            t = req.sampling.timeout_s
            if t is not None and now - req.arrival_time >= t:
                self._finish_abnormal(req, "timeout")

    def _resolve_token(self, req: Request, step: int, greedy_tok, finite,
                       row_fn: Callable[[], np.ndarray]) -> Optional[int]:
        """NaN/Inf-guarded token for ONE logits row, fed from a
        `greedy_grid` pass over the whole batch (the greedy/finite-guard
        path is vectorized device-side; `row_fn` lazily fetches the
        actual [V] row only for temperature > 0 sampling or a NaN
        rescue). Returns None when the request must be
        aborted (nan_policy="abort", or no finite logit exists). The
        seeded temperature path is untouched — per-request step-indexed
        streams stay bit-identical."""
        if not finite:
            self.metrics.nan_logit_events.inc()
            if self.nan_policy == "greedy":
                row = np.asarray(row_fn())
                ok = np.isfinite(row)
                if ok.any():
                    return int(np.argmax(np.where(ok, row, -np.inf)))
            return None
        if req.sampling.temperature == 0.0:
            return int(greedy_tok)
        return sample_token(np.asarray(row_fn()), req.sampling, step,
                            req.arrival_index)

    def _guarded_sample(self, logits_row, req: Request,
                        step: Optional[int] = None) -> Optional[int]:
        """Single-row spelling of the guarded sampler (the completing-
        chunk call site): same greedy_grid pass, scalar-shaped."""
        am, fin = self._drain(lambda: greedy_grid(logits_row))
        if step is None:
            step = len(req.output_tokens)
        return self._resolve_token(req, step, am, fin,
                                   lambda: np.asarray(logits_row))

    # --------------------------------------------------- async page-in

    def _stage_slot(self, tier, slot):
        """Issue the host->device transfer for one host-tier slot: one
        jax.device_put over the slot's per-layer page arrays, through
        the runner's staging hook when it has one (sharded runners
        place the slice kv-head-sharded so the fence scatter never
        reshards). Returns the staged device pytree; nothing blocks —
        the transfer runs while the device keeps computing."""
        data = tier.read_slot(slot)
        stage = getattr(self.runner, "stage_host_pages", None)
        if stage is not None:
            return stage(data)
        return jax.device_put(data)

    def _fence_pagein(self, admitted: Sequence[Request]) -> None:
        """Apply every pending page-in of this step's admissions to the
        pools — THE fence: after this, the restored pages are ordinary
        pool state that this step's prefill/decode reads. Prefetched
        transfers (staged in an earlier step, keyed by (slot,
        generation)) resolve here and count as HIDDEN — their copy had
        a whole step of device compute to overlap; everything else
        stages now. Consumed slots return to the tier."""
        tier = self.pool.host_tier
        pending = [r for r in admitted if r.pending_pagein]
        if tier is None or not pending:
            return
        pages: List[int] = []
        slots: List[int] = []
        staged_list = []
        hidden = 0
        for req in pending:
            for page, slot in req.pending_pagein:
                entry = self._pagein_staged.pop(
                    (slot, tier.generation(slot)), None)
                if entry is not None:
                    issued_step, staged = entry
                    if issued_step < self._step_count:
                        hidden += 1
                else:
                    staged = self._stage_slot(tier, slot)
                pages.append(page)
                slots.append(slot)
                staged_list.append(staged)
            req.pending_pagein = []
        # stack per (layer, array) and scatter once — one functional
        # pool update for the whole step's restores
        layer_data = []
        for li, layer in enumerate(self.pool.pools):
            layer_data.append(tuple(
                jnp.stack([s[li][j] for s in staged_list])
                for j in range(len(layer))))
        self.pool.write_pages(pages, layer_data)
        tier.free_slots(slots)
        self.metrics.pagein_pages.inc(len(pages))
        if hidden:
            self.metrics.pagein_hidden_pages.inc(hidden)

    def _prefetch_pagein(self) -> None:
        """Stage the host pages of the next `pagein_prefetch` offloaded
        waiters at the END of a step — ahead of the admission that will
        map them — so their host->device copies run while the device is
        busy with this step's launches (the async double buffer). Best-
        effort and safe by construction: a staged entry keyed by a slot
        generation that moved on (the waiter was shed, the slot reused)
        simply never resolves and is pruned here."""
        tier = self.pool.host_tier
        if tier is None or self.pagein_prefetch <= 0:
            return
        for key in list(self._pagein_staged):
            slot, gen = key
            if tier.generation(slot) != gen:
                del self._pagein_staged[key]
        seen = 0
        for req in self.scheduler.waiting:
            if seen >= self.pagein_prefetch:
                break
            if req.offload is None:
                continue
            seen += 1
            for slot in req.offload.slots:
                key = (slot, tier.generation(slot))
                if key not in self._pagein_staged:
                    self._pagein_staged[key] = (
                        self._step_count, self._stage_slot(tier, slot))

    # ------------------------------------------------------------- step

    def step(self) -> List[TokenEvent]:
        """One engine iteration: expire deadlines, admit new requests
        (mapping cached prefixes), run this step's prefill chunks under
        the token budget, reserve decode pages (preempting if needed),
        run one batched decode step over the decode-phase requests.
        Returns the tokens produced this step (streaming surface). Load-
        and fault-induced failures never escape: they end requests with
        an explicit finish_reason."""
        if not self.has_work():
            return []
        self._step_count += 1
        if getattr(self.runner, "COUNTS", ()):
            # this step's launches report here (a runner may serve more
            # than one engine in turn)
            self.runner.on_step_counts = self._take_counts
        # the step's root span; whether this step's sites record at all
        # is decided here, once (a profiler session is live or not)
        with _prof.step_span("engine.step", self._step_count):
            return self._step()

    def _plan(self):
        """PLAN phase of a step, under the caller's `engine.plan` span
        (pure host work; with `pipelined` this runs while the PREVIOUS
        step's launch is still executing on device — jax's async
        dispatch means nothing here blocks on it; `planned_ahead_steps`
        counts those steps, and the spans show `engine.plan` ahead of
        `engine.drain`). Returns (admitted, prefill plan)."""
        # 0. deadlines first: an expired request must not win admission
        self._expire_deadlines()

        # 1. admission: slot + pages (the longest cached prefix maps in
        #    for free — those tokens never reach the prefill chunks;
        #    host-restored coverage counts separately — those tokens are
        #    paged-in bytes, not cache hits). Planning against a
        #    scheduler snapshot that predates the in-flight launch's
        #    tokens is safe: the commit only ever FREES resources
        #    (finish/stop), so a plan made here is at worst conservative
        admitted = self.scheduler.admit()
        for req in admitted:
            if req.admit_prefix_tokens:
                self.metrics.prefix_hit_tokens.inc(req.admit_prefix_tokens)
            if req.queued_ns is not None:
                # add_request -> first admission
                if _prof.recording:
                    _prof.record("request.queue", req.queued_ns,
                                 request_id=req.request_id)
                req.queued_ns = None
        if not self.pipelined:
            # 1b. page-in fence: every host-resident page an
            #     admission mapped must be IN the pools before anything
            #     this step computes reads it — prefetched transfers
            #     resolve here (their copy overlapped the previous
            #     step), the rest stage now; the scatter itself
            #     dispatches async like every other pool write
            self._fence_pagein(admitted)
        return admitted, self.scheduler.prefill_plan()

    def _reserve_decode(self) -> None:
        """Decode-page reservation; pool pressure preempts
        youngest-first. Planning, wherever in the step it falls."""
        with _prof.span("engine.plan"):
            for _ in self.scheduler.reserve_decode():
                self.metrics.preemptions.inc()

    def _step(self) -> List[TokenEvent]:
        events: List[TokenEvent] = []
        with _prof.span("engine.plan"):
            admitted, plan = self._plan()

        # 2-4. compute this step's spans. ragged_batch mode collapses the
        # chunk-then-decode sequencing: when the step has BOTH prefill
        # chunks and decode-phase requests, pages are reserved first and
        # one mixed ragged runner call computes every span at once (the
        # only timing difference vs sequential: a request completing its
        # prefill inside the fused call decodes its first token NEXT
        # step, since sampling needs this call's logits — token values
        # are unchanged). Otherwise: chunks oldest-first under the token
        # budget, then page reservation, then one batched decode.
        #
        # num_speculative_tokens > 0 reroutes the decode half through
        # verify spans: each decode request feeds its last token PLUS a
        # draft (q_len = 1+k), accepting the longest draft prefix the
        # target model reproduces — several tokens per engine step when
        # drafts hit. Chunks fuse into the same launch under
        # ragged_batch, otherwise they keep the sequential
        # chunk-then-decode sequencing.
        if self._inflight is not None:
            # the whole planning interval above ran under an in-flight
            # launch — host time the device no longer waits for (the
            # zero-bubble overlap planned_ahead_steps counts)
            self.metrics.planned_ahead_steps.inc()
        if self.pipelined:
            # ---- COMMIT phase: drain + replay the previous step's
            # launch (stop/length/NaN handling, page release — all the
            # per-step bookkeeping, one step deferred), THEN apply the
            # page-in fence: the fence's pool writes must stay on the
            # committed side so a drain-failure rollback to the
            # pre-launch pools can never lose them
            events.extend(self._commit_inflight())
            # re-slice the prefill plan AFTER the commit: a committed
            # fused ragged launch advanced chunk coverage (planning
            # from the stale slice would recompute — and double-sample
            # — the same chunk), and a commit quarantine can end a
            # planned request. The pre-commit plan's only job was to
            # overlap host work; identical by construction when the
            # commit was a plain decode/horizon
            with _prof.span("engine.plan"):
                self._fence_pagein(admitted)
                plan = self.scheduler.prefill_plan()

        if self.role == "prefill":
            # disaggregated serving: every request that
            # finished its prefill (phase flipped to decode, first
            # token sampled) leaves the running set here — pages
            # spilled to the host tier, request parked in the handoff
            # buffer for the router to ship to a decode replica. Runs
            # AFTER the commit (a pipelined launch's members are fully
            # replayed, nothing is in flight) and BEFORE this step's
            # dispatch, so a staged request never joins a new launch.
            self._stage_handoffs()

        # ---- EXECUTE phase: this step's launches
        fused = bool(self.ragged_batch and plan
                     and self.scheduler.decode_ready())
        if self.num_speculative_tokens > 0 and self.scheduler.decode_ready():
            chunk_tokens = sum(end - start for _, start, end in plan)
            if not plan and self._spec_horizon_ready():
                # fused verify-in-scan: drafts ride the device-resident
                # horizon — accept/reject on device, ONE drain per
                # horizon, defers like any horizon
                self._reserve_decode()
                events.extend(self._launch_spec_horizon(self.pipelined))
            else:
                # per-step verify fallback: prefill chunks this step
                # (they fuse into the ragged launch under ragged_batch)
                # or a batch outside the in-scan sampler's envelope
                if not fused:
                    for req, start, end in plan:
                        ev = self._prefill_chunk_with_recovery(req, start,
                                                               end)
                        if ev is not None:
                            events.append(ev)
                self._reserve_decode()
                proposals = self._plan_speculation(chunk_tokens)
                events.extend(self._launch_ragged(
                    proposals, include_chunks=fused))
        elif fused:
            self._reserve_decode()
            # pipelined + ragged_batch compose: the fused launch defers
            # exactly like a decode launch
            events.extend(self._launch_ragged(defer=self.pipelined))
        else:
            for req, start, end in plan:
                ev = self._prefill_chunk_with_recovery(req, start, end)
                if ev is not None:
                    events.append(ev)
            self._reserve_decode()
            # one batched decode step over every decode-phase sequence —
            # or, when the batch qualifies (decode_horizon > 1, no chunks
            # in flight, sampling inside the envelope), one device-
            # resident multi-step horizon draining s tokens per host sync
            if self.scheduler.running:
                events.extend(self._launch_decode(
                    self._plan_horizon(chunks_in_flight=bool(plan)),
                    self.pipelined))
        self.metrics.decode_steps.inc()

        if self.pool.host_tier is not None:
            # stage the NEXT resumable requests' host pages while this
            # step's compute is still in flight on the device — the
            # double buffer the pagein_hidden_ratio metric measures
            self._prefetch_pagein()
        # the gauges show the state this step's commit left. It is read
        # here, where it is that state; the writes wait until the next
        # launch has the device busy (`_call_retrying`), or until
        # somebody looks (`EngineMetrics.settle`)
        self.metrics.put_off(partial(self._write_gauges,
                                     *self._read_gauges()))
        if self.audit:
            audit_engine(self)
        return events

    def _read_gauges(self) -> tuple:
        """What a step's gauges mirror, as the engine's state has it
        now (`_write_gauges` takes it in this order): the host-side
        counters the runner said it keeps, then scheduler, pool and host
        tier."""
        r, a, tier = self.runner, self.pool.allocator, self.pool.host_tier
        return ([getattr(r, g.name) for g in self._runner_gauges],
                self.scheduler.queue_depth, len(self.scheduler.running),
                a.num_usable - a.num_free, self.pool.utilization(),
                (len(self.pool.prefix_cache)
                 if self.pool.prefix_cache is not None else None),
                tier.bytes_used if tier is not None else None,
                tier.used_count if tier is not None else None)

    def _write_gauges(self, runners, queued, running, used, utilization,
                      cached, tier_bytes, tier_used) -> None:
        m = self.metrics
        for gauge, v in zip(self._runner_gauges, runners):
            gauge.set(v)
        m.queue_depth.set(queued)
        m.running.set(running)
        m.pool_used_pages.set(used)
        m.pool_utilization.set(utilization)
        # the pool's own, as it has them when this is written
        for gauge, v in zip(self._pool_gauges,
                            self.pool.gauges(running).values()):
            gauge.set(v)
        if cached is not None:
            m.prefix_cached_pages.set(cached)
        if tier_bytes is not None:
            m.host_tier_bytes.set(tier_bytes)
            m.host_tier_pages_used.set(tier_used)

    def _settle(self) -> None:
        """What the last step owes its gauges, written now that a launch
        has the device busy: off the interval in which the device waits
        for the host."""
        if self.metrics.owed is not None:
            with _prof.span("engine.settle"):
                self.metrics.settle()

    # ---------------------------------------------- the launch skeleton

    def _call_retrying(self, build, call, victim):
        """THE failure policy of a runner call. `build()` gives what
        `call` takes, or None once nothing is left to compute. A failure
        is first checked retryable (an UnrecoverableStepError leaves
        step()), then retried `max_step_retries` times with a doubling
        back-off; after that `victim(built)` is quarantined
        (finish_reason="error") and the call is rebuilt without it. The
        loop is bounded: each quarantine shrinks the batch, so at worst
        the batch drains and the step yields no tokens — never an
        exception. Returns (built, result, new_pools), or None.

        A retried call is exact, not approximate: a failed attempt either
        never reached the device (injected/raised before compute) or
        re-writes the same K/V values through the same block tables
        (copy-on-write forks happen before the call and are idempotent),
        and `self.pool.pools` is only assigned by the caller after a
        success — a failed attempt never half-commits."""
        attempts, delay = 0, self.retry_backoff_s
        while True:
            built = build()
            if built is None:
                return None
            try:
                out = call(built)
            except Exception as e:
                require_retryable(e, self.pool.pools)
                if attempts < self.max_step_retries:
                    attempts += 1
                    self.metrics.step_retries.inc()
                    self._sleep(delay)
                    delay *= 2
                    continue
                self._finish_abnormal(victim(built), "error")
                attempts, delay = 0, self.retry_backoff_s
                continue
            # the device has the launch: book-keeping goes here, not
            # between a drain and the next dispatch
            self._settle()
            return (built, *out)

    def _build_batch(self, rows, ragged: bool = False) -> tuple:
        """The operands every launch kind shares, in the order the
        runner's entries take them: (tokens[B], tables[B, P], pos[B]), or
        for a ragged step (tokens[B, T], tables, starts[B], q_lens[B]).
        A slot no row sits in carries an all-scratch table and
        self-neutralizes (a request mid-way through its chunked prefill
        has no token to feed a decode step yet)."""
        B, P = self.max_batch_size, self.max_pages_per_seq
        tables = np.full((B, P), SCRATCH_PAGE, np.int32)
        pos = np.zeros((B,), np.int32)
        if ragged:
            tokens = np.zeros(
                (B, bucket_len(max(len(row[4]) for row in rows))), np.int32)
            q_lens = np.zeros((B,), np.int32)
        else:
            tokens = np.zeros((B,), np.int32)
        for req, sl, start, end, fed, _ in rows:
            # no write may land on a shared page, so every page the row
            # may write before the next drain is private BEFORE launch
            # (idempotent: a forked page is already private on a retry)
            cow = req.kv.ensure_writable(start, end)
            if cow:
                self.metrics.cow_copies.inc(cow)
            if ragged:
                tokens[sl, :len(fed)] = fed
                q_lens[sl] = len(fed)
            else:
                tokens[sl] = fed
            row = req.kv.pages_array()
            tables[sl, :len(row)] = row
            pos[sl] = start                  # position of the first fed token
        if self.pool.window is not None:
            # a second group of pages rides in the same table: its live
            # pages and their base, by slot (`WindowGroup.extend_tables`)
            tables = self.pool.window.extend_tables(
                tables, [(sl, start, end) for _, sl, start, end, _, _ in rows])
        return (tokens, tables, pos, q_lens) if ragged else (tokens, tables,
                                                             pos)

    def _launch(self, kind: _LaunchKind, defer: bool = False
                ) -> List[TokenEvent]:
        """One device launch of any kind: build the batch from live
        scheduler state, call the runner under `_call_retrying` (the
        youngest row is the victim), assign the pools, then either leave
        the launch IN FLIGHT (`defer`, the pipelined loop: the next
        step's commit phase, or flush(), drains and replays it) or drain
        and commit it now."""
        def build():
            rows = kind.rows()
            if not rows:
                return None
            with _prof.span("engine.build_batch"):
                ops = self._build_batch(rows, kind.ragged)
                extra = kind.extras(rows) if kind.extras else None
            return rows, ops, extra

        out = self._call_retrying(build, lambda b: kind.call(b[1], b[2]),
                                  lambda b: _youngest(b[0]))
        if out is None:
            return []
        (rows, _, extra), result, new_pools = out
        prev, self.pool.pools = self.pool.pools, new_pools
        self.metrics.batch_occupancy.observe(len(rows))
        if kind.launched is not None:
            kind.launched()
        launch = _InflightLaunch(kind, rows, result, prev, extra)
        if defer:
            self._inflight = launch
            return []
        return kind.commit(launch, None)

    def _commit_inflight(self) -> List[TokenEvent]:
        """COMMIT phase of the pipelined loop: drain the in-flight launch
        and replay it through the standard per-step bookkeeping. The plan
        phase that just ran (admission, chunk slicing, page-in staging)
        overlapped this launch's device time — that ordering IS the
        optimization. A drain-time device error rolls the pools back to
        the pre-launch snapshot (no pool write has happened since the
        launch: the fence deliberately runs after this commit) and
        reruns the launch synchronously through the normal retry /
        quarantine path from live state — nothing the launch computed was
        committed (chunk coverage advances at commit, drafts are
        deterministic given the unchanged context), so the rerun writes
        identical K/V through the same block tables and streams stay
        exact."""
        inf, self._inflight = self._inflight, None
        if inf is None:
            return []
        try:
            drained = self._drain(lambda: inf.kind.drain(inf.result))
        except Exception as e:
            require_retryable(e, inf.prev_pools)
            self.metrics.step_retries.inc()
            self._sleep(self.retry_backoff_s)
            self.pool.pools = inf.prev_pools
            return self._launch(inf.kind)
        return inf.kind.commit(inf, drained)

    def flush(self) -> List[TokenEvent]:
        """Fence the pipeline: commit any in-flight launch and return its
        events. No-op on an unpipelined engine (or with nothing in
        flight). Router workers call this on a graceful stop so
        committed-but-undelivered tokens reach the delivery registry;
        tests and tools use it before inspecting engine state mid-run."""
        events = self._commit_inflight()
        self.metrics.settle()
        return events

    # ------------------------------------------------------ prefill chunk

    def _prefill_chunk_with_recovery(self, req: Request, start: int,
                                     end: int) -> Optional[TokenEvent]:
        """Compute context positions [start, end) of one request's
        (re-)prefill under `_call_retrying`, with the request itself as
        the victim. The chunk that completes the context (end ==
        num_context) samples the request's next token and flips it into
        the decode phase."""
        with _prof.span("request.prefill", request_id=req.request_id,
                        start=start, end=end):
            return self._prefill_chunk(req, start, end)

    def _prefill_chunk(self, req: Request, start: int,
                       end: int) -> Optional[TokenEvent]:
        with _prof.span("engine.build_batch"):
            cow = req.kv.ensure_writable(start, end)
            if cow:
                self.metrics.cow_copies.inc(cow)
            table = self.pool.pad_table(req.kv.pages, self.max_pages_per_seq)
            ops = (req.context_tokens[start:end], start, table)
            # recurrent state lives at the request's decode slot
            at = {"slot": req.slot} if self.pool.state_layers else {}
            if self.pool.window is not None:
                # the window group's ring as the chunk finds and leaves it
                at["ring"] = self.pool.window.advance(req.slot, end)
        out = self._call_retrying(
            lambda: None if req.done else ops,
            lambda ops: self.runner.prefill_chunk(*ops, self.pool.pools,
                                                  **at),
            lambda _: req)
        if out is None:
            return None
        _, logits, self.pool.pools = out
        with _prof.span("engine.commit"):
            req.kv.num_tokens = end
            self.metrics.prefill_tokens.inc(end - start)
            self.metrics.prefill_chunks.inc()
            if self.pool.prefix_cache is not None:
                self.pool.prefix_cache.register_seq(req.kv,
                                                    req.context_tokens)
            if end < req.num_context:
                return None          # intermediate chunk: logits unread
            tok = self._guarded_sample(logits, req)
            if tok is None:
                self._finish_abnormal(req, "error")
                return None
            req.phase = "decode"
            return self._append_token(req, tok)

    # ------------------------------- decode steps and ragged steps: logits

    def _decode_rows(self) -> list:
        """One decode step's rows: only decode-phase requests join the
        batch, each feeding its last token at its last position."""
        return [(r, r.slot, r.num_context - 1, r.num_context,
                 r.output_tokens[-1], ())
                for r in self.scheduler.decode_ready()]

    def _launch_decode(self, s: int, defer: bool = False
                       ) -> List[TokenEvent]:
        """One batched decode step over every decode-phase sequence (`s`
        == 1, through `runner.decode`), or one device-resident horizon:
        the batch's next `s` decode steps in ONE `runner.decode_multi`
        launch — a lax.scan that feeds each step's token back as the
        next input — of which the host drains ONE packed buffer
        (host_syncs += 1, not += s) and replays it through the per-step
        bookkeeping (`_replay_horizon`)."""
        if s <= 1:
            return self._launch(self._decode_kind, defer)
        early = self.horizon_early_stop

        def rows():
            # early-stop rows freeze their writes past their own
            # remaining budget, so only that span needs forking
            return [(r, r.slot, r.num_context - 1, r.num_context - 1
                     + (min(s, self._row_remaining(r)) if early else s),
                     r.output_tokens[-1], ())
                    for r in self.scheduler.decode_ready()]

        def extras(rows):
            ctx = self._horizon_ctx(rows, stops=early)
            if early:
                ctx["early_stop"] = True
            return ctx

        return self._launch(_LaunchKind(
            "decode_multi", rows,
            lambda ops, ctx: self.runner.decode_multi(
                *ops, self.pool.pools, s, **ctx),
            _to_host, self._replay_horizon, extras=extras, s=s,
            launched=lambda: self.metrics.decode_horizon_steps.inc(s)),
            defer)

    def _release_spec_state(self, req: Request) -> None:
        """Drop per-request proposer/adaptive-k state on ANY terminal
        path (normal finish and abnormal alike): the incremental n-gram
        suffix index, a draft model's shadow KV pages, and the
        acceptance-rate EWMA all key on request_id and would otherwise
        leak across a long-lived engine."""
        if self.num_speculative_tokens <= 0:
            return
        release = getattr(self.proposer, "release", None)
        if release is not None:
            release(req.request_id)
        if self.adaptive_k is not None:
            self.adaptive_k.release(req.request_id)

    def _plan_speculation(self, chunk_tokens: int) -> Dict[Request,
                                                           List[int]]:
        """Draft proposals for this step's decode batch, capped in
        admission order by (a) the request's own remaining-token headroom
        (at most max_tokens - generated - 1 drafts: the bonus/corrected
        token always fits) and model-length headroom, (b) the scheduler's
        leftover per-step token budget — verify spans count against
        max_prefill_tokens_per_step exactly like prefill chunks — and (c)
        best-effort page reservation: under pool pressure a proposal
        shrinks instead of preempting anyone."""
        budget = self.scheduler.speculation_budget(chunk_tokens)
        proposals: Dict[Request, List[int]] = {}
        for req in self.scheduler.decode_ready():      # admission order
            k = self.num_speculative_tokens
            if self.adaptive_k is not None:
                k = min(k, self.adaptive_k.k_for(req.request_id))
            k = min(k, req.sampling.max_tokens - len(req.output_tokens) - 1)
            k = min(k, self.max_model_len - req.num_context)
            if budget is not None:
                k = min(k, budget)
            if k <= 0:
                continue
            prop = self.proposer.propose(req.context_tokens, k,
                                         request_id=req.request_id)
            if not prop:
                continue
            if budget is not None:
                budget -= len(prop)
            proposals[req] = prop
        self.scheduler.reserve_speculation(proposals)
        return proposals

    def _launch_ragged(
            self, proposals: Optional[Dict[Request, List[int]]] = None,
            include_chunks: bool = True,
            defer: bool = False) -> List[TokenEvent]:
        """ONE mixed ragged runner call for this step: every planned
        prefill chunk and every decode-phase request rides its batch
        slot as a (start, q_len) span into runner.ragged_step, which the
        ragged paged-attention kernel serves in a single launch. With
        `proposals` (speculation's per-step fallback: chunks in flight,
        or a batch outside the in-scan sampler's envelope) each decode
        span stretches to q_len = 1 + k — the fed last token plus its
        draft — and the call asks the runner for FULL per-position
        logits so `_accept_verify` can score every draft position off
        the single launch; such a call never defers. A deferred fused
        launch is replayed by the next step's commit, and the next
        step's prefill plan is re-sliced AFTER that commit, so no chunk
        is ever computed twice."""
        full = proposals is not None

        def rows():
            # the slot is captured at launch time: the commit of a
            # deferred launch must index the drained logits by the slots
            # the launch actually used
            out = []
            if include_chunks:
                out += [(req, req.slot, start, end,
                         req.context_tokens[start:end], None)
                        for req, start, end in self.scheduler.prefill_plan()]
            for req in self.scheduler.decode_ready():
                prop = proposals.get(req, []) if full else []
                out.append((req, req.slot, req.num_context - 1,
                            req.num_context + len(prop),
                            req.output_tokens[-1:] + list(prop), prop))
            return out

        kw = {"full_logits": True} if full else {}
        return self._launch(_LaunchKind(
            "ragged", rows,
            lambda ops, _: self.runner.ragged_step(
                *ops, self.pool.pools, **kw),
            greedy_grid, self._commit_logits, ragged=True), defer)

    def _commit_logits(self, launch: _InflightLaunch,
                       grid=None) -> List[TokenEvent]:
        """Resolve one launch that returned LOGITS — a decode step's or
        a ragged step's [B, V], or [B, T, V] where verify spans asked
        for every position: one vectorized greedy/finite pass for the
        whole batch (the array itself only reaches the host for
        temperature > 0 or NaN-rescue rows), then per row the
        bookkeeping of what it was: a prefill chunk's coverage advance
        and, if it completed the context, its first sample; a decode
        span's append; a verify span's acceptance. Shared by the
        synchronous path and the pipelined commit (which passes the
        already-drained grid); a row whose request finished while the
        launch was in flight (pipelined abort/deadline) is skipped — its
        drained logits are discarded, never half-committed."""
        with _prof.span("engine.commit"):
            logits = launch.result
            if grid is None:
                grid = self._drain(lambda: greedy_grid(logits))
            am, fin = grid
            full = am.ndim == 2                       # [B, T]: every position
            host: Dict[str, np.ndarray] = {}

            def _rows() -> np.ndarray:
                if "l" not in host:
                    host["l"] = self._drain(lambda: _to_host(logits))
                return host["l"]

            events: List[TokenEvent] = []
            for req, s, start, end, _, prop in launch.batch:
                if req.done:
                    continue
                if full and prop is not None:       # verify span
                    self._accept_verify(
                        req, prop, am[s], fin[s],
                        lambda i, s=s: _rows()[s, i], events)
                    continue
                if prop is None:                    # prefill chunk span
                    req.kv.num_tokens = end
                    self.metrics.prefill_tokens.inc(end - start)
                    self.metrics.prefill_chunks.inc()
                else:                               # plain decode span
                    req.kv.num_tokens = req.num_context
                if self.pool.prefix_cache is not None:
                    self.pool.prefix_cache.register_seq(req.kv,
                                                        req.context_tokens)
                if prop is None and end < req.num_context:
                    continue             # intermediate chunk: logits unread
                if full:
                    r = end - start - 1             # the chunk's last row
                    tok = self._resolve_token(
                        req, len(req.output_tokens), am[s, r], fin[s, r],
                        lambda s=s, r=r: _rows()[s, r])
                else:
                    tok = self._resolve_token(
                        req, len(req.output_tokens), am[s], fin[s],
                        lambda s=s: _rows()[s])
                if tok is None:
                    self._finish_abnormal(req, "error")
                    continue
                if prop is None:          # the chunk completed the context
                    req.phase = "decode"
                events.append(self._append_token(req, tok))
            return events

    def _accept_verify(self, req: Request, prop: List[int], row_am,
                       row_fin, row_fn, events: List[TokenEvent]) -> None:
        """Token-exact accept loop for one verify span. Span position i
        scored the logits for the token AFTER
        context + prop[:i]; the target token there is resolved with the
        request's own step-indexed sampler — argmax under greedy, the
        seeded per-step sample stream under temperature > 0, exactly the
        keys naive_generate uses — so acceptance means "the draft token
        IS the token the target model would have emitted". The longest
        matching draft prefix is accepted, then the first divergent
        position contributes its corrected token (or the bonus token
        after a fully-accepted draft). The rejected tail's KV state is
        rolled back before any append can finish the request: coverage
        truncates to the accepted prefix and pages grown only for the
        rejected span are decref'd — a speculated page never survives
        its rejection (the auditor's over-provision check pins it)."""
        k = len(prop)
        o = len(req.output_tokens)
        C = req.num_context
        toks: List[int] = []
        accepted = 0
        aborted = False
        for i in range(k + 1):
            tok = self._resolve_token(req, o + i, row_am[i], row_fin[i],
                                      lambda i=i: row_fn(i))
            if tok is None:
                aborted = True
                break
            toks.append(tok)
            matched = i < k and int(prop[i]) == tok
            if matched:
                accepted += 1
            done = (tok in req.sampling.stop_token_ids
                    or o + len(toks) >= req.sampling.max_tokens)
            if done or not matched:
                break
        self.metrics.spec_proposed_tokens.inc(k)
        self.metrics.spec_accepted_tokens.inc(accepted)
        self.metrics.spec_dead_positions.inc(max(k - accepted, 0))
        if self.adaptive_k is not None:
            self.adaptive_k.update(req.request_id, k, accepted)
        # positions C..C+accepted-1 hold accepted-draft KV; the rejected
        # tail [C+accepted, C+k) is dead weight — roll it back through
        # the refcount machinery, then register/append
        req.kv.num_tokens = C + accepted
        dropped = req.kv.truncate(C + accepted)
        if dropped:
            self.metrics.spec_rollback_pages.inc(dropped)
        if self.pool.prefix_cache is not None:
            self.pool.prefix_cache.register_seq(
                req.kv, req.context_tokens + toks[:accepted])
        for t in toks:
            events.append(self._append_token(req, t))
            if req.done:
                break
        if aborted and not req.done:
            self._finish_abnormal(req, "error")

    # ------------------------------------------ horizons: packed buffers

    def _horizon_envelope(self, batch: List[Request]) -> bool:
        """Whether this decode batch can ride a device-resident scan.
        The in-scan sampler bakes ONE (top_k, top_p) pair per jit entry
        and carries seeds as int32, so a batch whose sampled rows mix
        pairs, or hold a wider seed, takes the per-step path; so does
        any sampled row with `horizon_sampling` off, and a batch holding
        a request that a mid-horizon NaN deferred (the per-step path
        refetches real logits to rescue from)."""
        deferred = False
        for r in batch:
            if r.defer_horizon:
                r.defer_horizon = False
                deferred = True
        if deferred:
            return False
        sampled = [r for r in batch if r.sampling.temperature != 0.0]
        if not sampled:
            return True
        return (self.horizon_sampling
                and len({(r.sampling.top_k, r.sampling.top_p)
                         for r in sampled}) == 1
                and all((r.sampling.seed if r.sampling.seed is not None
                         else r.arrival_index) < 2 ** 31 for r in sampled))

    def _plan_horizon(self, chunks_in_flight: bool) -> int:
        """Effective multi-step horizon for THIS step's decode batch —
        the fallback matrix in one place. Returns 1 (the per-step path)
        whenever the batch can't ride a device-resident horizon:
        decode_horizon off, prefill chunks in flight this step (their
        completing logits need per-step sampling), or a batch outside
        `_horizon_envelope`. Otherwise caps s at the batch's token
        headroom (never scan past every request's max_tokens, never
        write a K/V position past max_model_len — overshoot past a STOP
        token is fine and rolled back, the cap is about provable waste)
        and lets the scheduler pre-commit the horizon's pages, trimming
        further under pool pressure."""
        s = self.decode_horizon
        if s <= 1:
            return 1
        batch = self.scheduler.decode_ready()
        if (not batch or chunks_in_flight
                or not self._horizon_envelope(batch)):
            return 1
        if self.horizon_early_stop:
            # rows self-freeze on device at their own stop/budget, so
            # only the LONGEST row's remaining budget caps s, and each
            # row funds pages for just min(s, its remaining) tokens
            rem = {r: self._row_remaining(r) for r in batch}
            s = min(s, max(rem.values()))
            if s <= 1:
                return 1
            return self.scheduler.plan_decode_horizon(s, row_caps=rem)
        s = min(s, max(r.sampling.max_tokens - len(r.output_tokens)
                       for r in batch))
        s = min(s, min(self.max_model_len - r.num_context + 1
                       for r in batch))
        if s <= 1:
            return 1
        return self.scheduler.plan_decode_horizon(s)

    def _row_remaining(self, req: Request) -> int:
        """Tokens this request may still emit before a length finish or
        the model-length wall — the on-device early-stop budget and the
        per-row page-funding cap."""
        return min(req.sampling.max_tokens - len(req.output_tokens),
                   self.max_model_len - req.num_context + 1)

    def _horizon_ctx(self, rows, stops: bool) -> dict:
        """Extension operands of one horizon launch: the per-row seeded
        key schedule where a row samples (seeds, generated-token base
        indices, temperatures, plus the batch's single static (top_k,
        top_p)) and, with `stops`, the on-device stop state (-1-padded
        stop-token sets and remaining-token budgets). Empty dict = the
        classic pure-greedy [2, B, s] scan."""
        B = self.max_batch_size
        ctx: dict = {}
        if any(row[0].sampling.temperature != 0.0 for row in rows):
            seeds = np.zeros((B,), np.int32)
            base = np.zeros((B,), np.int32)
            temps = np.zeros((B,), np.float32)
            top_k = top_p = None
            for r, sl, *_ in rows:
                sp = r.sampling
                seeds[sl] = (sp.seed if sp.seed is not None
                             else r.arrival_index)
                base[sl] = len(r.output_tokens)
                temps[sl] = sp.temperature
                if sp.temperature != 0.0:
                    top_k, top_p = sp.top_k, sp.top_p
            ctx.update(seeds=seeds, base_steps=base, temps=temps,
                       top_k=top_k, top_p=top_p)
        if stops:
            S = max([1] + [len(row[0].sampling.stop_token_ids)
                           for row in rows])
            stop_ids = np.full((B, S), -1, np.int32)
            remaining = np.ones((B,), np.int32)
            for r, sl, *_ in rows:
                ids = tuple(r.sampling.stop_token_ids)
                stop_ids[sl, :len(ids)] = ids
                remaining[sl] = self._row_remaining(r)
            ctx.update(stop_ids=stop_ids, remaining=remaining)
        return ctx

    def _replay_horizon(self, launch: _InflightLaunch,
                        drained=None) -> List[TokenEvent]:
        """Replay one drained horizon buffer through the per-step
        bookkeeping: _append_token's stop/length handling, prefix-cache
        registration at each coverage point, the NaN policy — so token
        streams, finish reasons, and metrics match the s=1 loop
        verbatim. `drained` is [2, B, s] (tokens, finite) or, on the
        extended scan, [3, B, s] with a LIVE plane: entries past a row's
        on-device done bit are dead and never replayed (overshoot -> ~0
        by construction). A request that stops mid-horizon discards its
        overshoot tail (horizon_overshoot_tokens); its pre-committed
        pages go back via the normal finish release. A batch member that
        finished while the launch was in flight (pipelined
        abort/deadline) is skipped — its drained tokens are discarded,
        never half-committed."""
        if drained is None:                 # the horizon's ONE host sync
            drained = self._drain(lambda: _to_host(launch.result))
        s = launch.s
        with _prof.span("engine.commit"):
            toks, fins = drained[0], drained[1]
            live = drained[2] if drained.shape[0] > 2 else None
            events: List[TokenEvent] = []
            for req, sl, *_ in launch.batch:
                if req.done:
                    continue
                C = req.num_context
                accepted = 0
                for j in range(s):
                    if live is not None and not live[sl, j]:
                        break      # row froze on device: tail is dead
                    if not fins[sl, j]:
                        self._horizon_nan(req, C, accepted)
                        break
                    req.kv.num_tokens = C + j
                    if self.pool.prefix_cache is not None:
                        self.pool.prefix_cache.register_seq(
                            req.kv, req.context_tokens)
                    events.append(
                        self._append_token(req, int(toks[sl, j])))
                    accepted += 1
                    if req.done:
                        tail = (s - accepted if live is None
                                else int(np.sum(live[sl, accepted:] != 0)))
                        self.metrics.horizon_overshoot_tokens.inc(tail)
                        break
            return events

    def _horizon_nan(self, req: Request, C: int, accepted: int) -> None:
        """Non-finite logits surfaced mid-horizon: the device loop kept
        no [V] row to rescue from, so under nan_policy="abort" the
        request ends exactly like an unrescuable per-step row; under
        "greedy" the horizon tail is rolled back (coverage truncated,
        over-committed pages decref'd on the spot) and the request is
        deferred to the per-step path next step, which refetches the
        real logits and applies the normal finite-entry rescue."""
        self.metrics.nan_logit_events.inc()
        if self.nan_policy == "abort":
            self._finish_abnormal(req, "error")
            return
        req.kv.truncate(max(C + accepted - 1, 1))
        req.defer_horizon = True

    # ------------------------------------------------ fused verify-in-scan

    def _spec_horizon_ready(self) -> bool:
        """Gate for the fused verify-in-scan path: True when this step's
        decode batch can ride drafts inside the device-resident scan
        (`_horizon_envelope`). Unlike _plan_horizon there is no
        decode_horizon >= 2 requirement: a fused verify span wins even
        at s == 1 (one drain resolves k+1 tokens instead of a
        full-logits pull)."""
        batch = self.scheduler.decode_ready()
        return bool(batch) and self._horizon_envelope(batch)

    def _launch_spec_horizon(self, defer: bool = False
                             ) -> List[TokenEvent]:
        """One fused speculative horizon: the batch's next `s` scan
        steps each carry a per-row draft span — k proposed tokens,
        -1-padded to the batch's bucketed K — through
        runner.decode_multi_spec, where accept/reject is resolved ON
        DEVICE per position and the corrected/bonus token feeds back
        into the scan. The host drains ONE packed [3, B, s, K+1] buffer
        per horizon (not one full-logits pull per verify span) and
        replays acceptance through _replay_spec_horizon.

        Drafts come from ONE proposer chain per row per horizon
        (s*(k+1)-1 tokens — the continuation under full acceptance),
        sliced at fixed (k+1)-strides: after a rejection the remaining
        slices usually stop matching and the row degrades to plain
        multi-step decode for the horizon's tail. Exactness never
        depends on draft quality — a wrong draft is simply rejected
        and the device emits the target model's own token.

        Page funding goes through scheduler.plan_spec_horizon: up to
        min(s*(k+1), remaining+k) tokens per row, trimming s first and
        then per-row k under pool pressure, never preempting. The
        on-device stop plane ALWAYS runs in this mode (stop_ids +
        remaining budgets) — it is what bounds kept emissions by
        `remaining` and makes that funding formula a true worst case.

        The plan is made once: it is deterministic given request state,
        and acceptance is deterministic given the seeded streams, so a
        retried or rebuilt launch commits the identical token stream."""
        batch = self.scheduler.decode_ready()
        if not batch:
            return []
        rem = {r: self._row_remaining(r) for r in batch}
        s = max(1, min(self.decode_horizon, max(rem.values())))
        budget = self.scheduler.speculation_budget(0)
        row_k: Dict[Request, int] = {}
        chains: Dict[Request, List[int]] = {}
        for req in batch:
            k = self.num_speculative_tokens
            if self.adaptive_k is not None:
                k = min(k, self.adaptive_k.k_for(req.request_id))
            k = min(k, max(rem[req] - 1, 0))
            if budget is not None:
                k = min(k, budget)
            chain: List[int] = []
            if k > 0:
                chain = list(self.proposer.propose_chain(
                    req.context_tokens, s * (k + 1) - 1,
                    request_id=req.request_id))
                if not chain:
                    k = 0
            if k > 0 and budget is not None:
                budget -= k
            row_k[req] = k
            chains[req] = chain
        s = self.scheduler.plan_spec_horizon(s, row_k, rem)
        kmax = max(row_k.values())
        if kmax <= 0:
            # every draft shrank away (cold proposer / pool pressure /
            # adaptive-k at 0): ride the plain horizon machinery. The
            # fused funding (min(s, rem) per row) is NOT enough for a
            # plain scan without early stop — decode_multi writes all
            # s positions per row (overshoot) — so re-plan through
            # _plan_horizon, which applies the overshoot caps and
            # funds the difference (grow is incremental)
            return self._launch_decode(self._plan_horizon(False), defer)
        K = bucket_len(1 + kmax) - 1
        # mirrors plan_spec_horizon's funding formula exactly (the
        # auditor's over-provision credit) — including the block-table
        # wall clamp on the +k rejected-draft slack
        wall = self.max_pages_per_seq * self.pool.block_size
        upc = {r: max(1, min(s * (row_k[r] + 1), rem[r] + row_k[r],
                             wall - r.kv.num_tokens))
               for r in batch}

        def rows():
            return [(r, r.slot, r.num_context - 1,
                     r.num_context - 1 + upc[r], r.output_tokens[-1], ())
                    for r in self.scheduler.decode_ready() if r in row_k]

        def extras(rows):
            drafts = np.full((self.max_batch_size, s, K), -1, np.int32)
            for req, sl, *_ in rows:
                k, chain = row_k[req], chains[req]
                for t in range(s):
                    piece = chain[t * (k + 1):t * (k + 1) + k]
                    if piece:
                        drafts[sl, t, :len(piece)] = piece
            return drafts, self._horizon_ctx(rows, stops=True)

        def launched():
            self.metrics.decode_horizon_steps.inc(s)
            self.metrics.spec_fused_horizons.inc()

        return self._launch(_LaunchKind(
            "decode_spec", rows,
            lambda ops, x: self.runner.decode_multi_spec(
                *ops, self.pool.pools, x[0], **x[1]),
            _to_host, self._replay_spec_horizon, extras=extras, s=s,
            launched=launched,
            upcoming={id(r): n for r, n in upc.items()}), defer)

    def _replay_spec_horizon(self, launch: _InflightLaunch,
                             drained=None) -> List[TokenEvent]:
        """Replay one drained fused speculative horizon. `drained` is
        [3, B, s, K+1]: per scan step, the span's emitted tokens, a
        finiteness plane, and the KEEP plane — the device's accepted
        prefix (position 0 = the fed token's emission, positions 1..m-1
        = accepted-draft continuations, all gated by the row's live
        bit). Per kept position this applies exactly _accept_verify's
        bookkeeping — acceptance counting, coverage advance + prefix
        registration before each append, _append_token's stop/length
        handling, the NaN policy via _horizon_nan — so token streams,
        finish reasons, and spec_* metrics match the per-step verify
        path verbatim. An unfinished row then truncates its KV back to
        the per-step invariant (num_tokens = num_context - 1): pages
        grown only for rejected/unreached span positions are decref'd
        on the spot — a speculated page never survives its rejection,
        and the auditor's over-provision check pins it. A batch member
        that finished while the launch was in flight is skipped."""
        if drained is None:                 # the horizon's ONE host sync
            drained = self._drain(lambda: _to_host(launch.result))
        drafts = launch.extra[0]
        with _prof.span("engine.commit"):
            toks, fins, keeps = drained[0], drained[1], drained[2]
            s = toks.shape[1]
            events: List[TokenEvent] = []
            for req, sl, *_ in launch.batch:
                if req.done:
                    continue
                C = req.num_context
                emitted = 0
                proposed = 0
                accepted = 0
                halted = False
                for t in range(s):
                    krow = keeps[sl, t]
                    if not krow[0]:
                        break      # row froze on device: tail is dead
                    row_draft = drafts[sl, t]
                    ndraft = int(np.sum(row_draft >= 0))
                    proposed += ndraft
                    m = int(np.sum(krow != 0))
                    for i in range(m):
                        if not fins[sl, t, i]:
                            self._horizon_nan(req, C, emitted)
                            halted = True
                            break
                        tok = int(toks[sl, t, i])
                        if i < ndraft and int(row_draft[i]) == tok:
                            accepted += 1
                        req.kv.num_tokens = C + emitted
                        if self.pool.prefix_cache is not None:
                            self.pool.prefix_cache.register_seq(
                                req.kv, req.context_tokens)
                        events.append(self._append_token(req, tok))
                        emitted += 1
                        if req.done:
                            halted = True
                            break
                    if halted:
                        break
                self.metrics.spec_proposed_tokens.inc(proposed)
                self.metrics.spec_accepted_tokens.inc(accepted)
                self.metrics.spec_dead_positions.inc(
                    max(proposed - accepted, 0))
                if self.adaptive_k is not None:
                    self.adaptive_k.update(req.request_id, proposed,
                                           accepted)
                if not req.done and emitted > 0:
                    # rejected/unreached tail: drop back to the per-step
                    # invariant and decref pages grown past it (NaN rows
                    # already truncated via _horizon_nan)
                    dropped = req.kv.truncate(C + emitted - 1)
                    if dropped:
                        self.metrics.spec_rollback_pages.inc(dropped)
            return events

    def _append_token(self, req: Request, tok: int) -> TokenEvent:
        now = self.metrics.clock()
        if req.first_token_time is None:
            req.first_token_time = now
            self.metrics.ttft_s.observe(now - req.arrival_time)
        req.output_tokens.append(tok)
        self.metrics.tokens_generated.inc()
        reason = None
        if tok in req.sampling.stop_token_ids:
            reason = "stop"
        elif len(req.output_tokens) >= req.sampling.max_tokens:
            reason = "length"
        if reason is not None:
            req.finish_time = now
            self.scheduler.finish(req, reason)
            self._release_spec_state(req)
            self.metrics.requests_finished.inc()
            self.metrics.e2e_latency_s.observe(now - req.arrival_time)
            self._outputs[req.request_id] = RequestOutput(
                request_id=req.request_id,
                prompt_tokens=list(req.prompt_tokens),
                output_tokens=list(req.output_tokens),
                finish_reason=reason,
                num_preemptions=req.num_preemptions,
                ttft_s=req.first_token_time - req.arrival_time,
                e2e_s=req.finish_time - req.arrival_time)
        return TokenEvent(req.request_id, tok,
                          len(req.output_tokens) - 1,
                          finished=reason is not None, finish_reason=reason)

    # -------------------------------------------------------- streaming

    def stream_text(self, request_id: str) -> str:
        """Incremental detokenized text of a request's generation so far
        : every output token up to the last byte-
        complete UTF-8 boundary — a multi-byte character split across
        tokens stays buffered until its continuation bytes arrive — and
        the fully-flushed text (dangling bytes replaced) once the
        request finished. Requires the engine's `tokenizer` knob
        (id_to_bytes(tok) -> bytes preferred; decode([tok]) fallback).
        Safe to call at any time, including between steps and after a
        restore: the per-request detokenizer replays from the request's
        token history, so no TokenEvent may be missed or double-fed."""
        if self.tokenizer is None:
            raise ValueError("stream_text() needs ServingEngine("
                             "tokenizer=...) — an object exposing "
                             "id_to_bytes(tok) or decode([tok])")
        req = self._requests.get(request_id)
        if req is None:
            raise KeyError(f"unknown request {request_id!r}")
        d = self._detoks.get(request_id)
        if d is None:
            d = self._detoks[request_id] = StreamDetokenizer(self.tokenizer)
        while not d.finished and d.consumed < len(req.output_tokens):
            d.push(req.output_tokens[d.consumed])
        if req.done and not d.finished:
            d.finish()
        return d.text

    # -------------------------------------------------------------- run

    def run(self) -> Dict[str, RequestOutput]:
        """Drain the engine; returns every finished RequestOutput.
        has_work() counts an in-flight pipelined launch, so the loop's
        last iteration commits the tail of the pipeline."""
        while self.has_work():
            self.step()
        self.metrics.settle()
        return dict(self._outputs)

    def outputs(self) -> Dict[str, RequestOutput]:
        return dict(self._outputs)

    # --------------------------------------------- migration (router tier)

    # --- prefill/decode handoff: the KV-carrying migration. A
    # preemption's OffloadRecord + inject_request were already a
    # migration primitive WITHIN one engine; these methods stretch the
    # same machinery across an engine boundary: spill -> serialize
    # slots (raw page bytes + scale rows + content hashes) -> import
    # into the sibling's tier -> inject with the record attached, after
    # which the sibling's ordinary admission page-in path takes over.

    def _request_state(self, req: Request, now: float) -> dict:
        """One request as migration and snapshots carry it (with its
        live SamplingParams object)."""
        return {
            "request_id": req.request_id,
            "prompt_tokens": list(req.prompt_tokens),
            "output_tokens": list(req.output_tokens),
            "sampling": req.sampling,
            "arrival_index": req.arrival_index,
            "num_preemptions": req.num_preemptions,
            "elapsed_s": now - req.arrival_time,
            "first_token_elapsed_s": (
                req.first_token_time - req.arrival_time
                if req.first_token_time is not None else None),
        }

    def _park_for_handoff(self, req: Request) -> None:
        """Move one running decode-phase request into the handoff
        buffer: KV pages spill to the host tier from page 0 (shared
        prefix pages included — the record must be self-contained on a
        sibling), device pages and the batch slot are released. Coverage
        is clamped to context-1 exactly like preemption, so the
        receiving replica always has at least one token to compute — the
        position whose logits it samples the next token from."""
        tier = self.pool.host_tier
        rec = None
        if tier is not None:
            covered = min(req.kv.num_tokens, req.num_context - 1)
            rec = tier.spill_sequence(req.kv, covered,
                                      include_registered=True)
        self.scheduler.release_running(req)
        req.phase = "handoff"
        req.offload = None
        self._handoffs[req.request_id] = rec
        self.metrics.handoffs_out.inc()
        if rec is not None:
            self.metrics.handoff_pages_out.inc(len(rec.slots))

    def _stage_handoffs(self) -> None:
        """Park every request that completed its prefill this step
        (decode phase, >= 1 sampled token) in the handoff buffer."""
        for req in [r for r in self.scheduler.running
                    if r.phase == "decode" and r.output_tokens
                    and not r.done]:
            self._park_for_handoff(req)

    def stage_migration(self, request_id: str) -> bool:
        """Park ONE RUNNING decode-phase request in the handoff buffer
        on demand — the graceful-drain primitive, role-agnostic:
        `router.drain_replica` stages a draining replica's running
        requests so their KV pages ride to a sibling via
        extract_handoff/import_handoff instead of being recomputed.
        Returns False when the request is not in a stageable state
        (waiting, finished, still prefilling, or no sampled token yet) —
        the caller then falls back to extract_request / registry
        recompute, which is always correct."""
        req = self._requests.get(request_id)
        if (req is None or req.done
                or req.state is not RequestState.RUNNING
                or req.phase != "decode" or not req.output_tokens):
            return False
        self._park_for_handoff(req)
        return True

    def handoff_ready(self) -> List[str]:
        """Request ids staged for handoff, oldest first — what the
        router polls after each step on a prefill replica."""
        return list(self._handoffs)

    def extract_handoff(self, request_id: str):
        """Remove a staged handoff and return (state, payload): the
        standard migration state dict plus the page payload — per-layer
        stacked page arrays (raw bytes, scale rows included on int8
        pools) and per-slot CRC content hashes for receive-time
        verification. payload is None when no pages rode along (no
        tier / tier full); the receiver then recomputes. The host
        slots are freed here — the payload owns the bytes now."""
        if request_id not in self._handoffs:
            raise KeyError(f"request {request_id!r} is not staged for "
                           "handoff")
        rec = self._handoffs.pop(request_id)
        state = self._request_state(self._requests[request_id],
                                    self.metrics.clock())
        payload = None
        tier = self.pool.host_tier
        if rec is not None and tier is not None:
            payload = {
                "start_page": rec.start_page,
                "covered_tokens": rec.covered_tokens,
                "hashes": [tier.slot_hash(s) for s in rec.slots],
            }
            if tier.store is not None:
                # slot-REFERENCE handoff: the pages already live in the
                # host-wide store — ownership moves to a transfer tag
                # and only slot ids + generations + CRCs cross the wire;
                # the receiving replica adopts the same bytes by
                # reference. Page bytes cross the wire ZERO times on the
                # same host.
                xfer = f"xfer:{request_id}"
                tier.retag_out(rec.slots, xfer)
                payload.update(
                    slot_refs=list(rec.slots),
                    gens=[tier.generation(s) for s in rec.slots],
                    xfer_owner=xfer)
            else:
                payload["layers"] = tier.export_slots(rec.slots)
                self.metrics.handoff_bytes_out.inc(sum(
                    int(a.nbytes) for layer in payload["layers"]
                    for a in layer))
                tier.free_slots(rec.slots)
        del self._requests[request_id]
        self._detoks.pop(request_id, None)
        return state, payload

    def import_handoff(self, state: dict, payload: Optional[dict]) -> str:
        """Accept a handed-off request: write the page payload into
        this engine's host tier (content hashes RE-VERIFIED against
        the written bytes — a corrupted transfer raises, it is never
        served) and inject the request with the reconstructed
        OffloadRecord attached. Admission then takes the ordinary
        offload page-in path — fresh device pages, staged device_put,
        fence before compute — and the continued stream is token-exact
        including int8 codes because the pages are copies, not
        recompute. A payload that cannot land (no tier here, tier
        full) degrades to the recompute path, counted."""
        rec = None
        tier = self.pool.host_tier
        if (payload is not None and payload.get("slot_refs") is not None
                and (tier is None or tier.store is None)):
            # loud, not a silent recompute: the sender moved ownership
            # to a transfer tag — the router's fallback path reaps it
            raise ValueError(
                "received a slot-reference handoff but this engine has "
                "no shared KV store — sender and receiver must share "
                "one host store")
        if payload is not None and tier is not None:
            if payload.get("slot_refs") is not None:
                slots = tier.adopt_slots(
                    payload["slot_refs"], payload["gens"],
                    payload["hashes"], payload["xfer_owner"])
            else:
                slots = tier.import_slots(payload["layers"],
                                          payload["hashes"])
            if slots is not None:
                rec = OffloadRecord(
                    start_page=int(payload["start_page"]),
                    covered_tokens=int(payload["covered_tokens"]),
                    slots=slots)
        if rec is None:
            self.metrics.handoff_recompute_fallbacks.inc()
        else:
            self.metrics.handoff_pages_in.inc(len(rec.slots))
        self.metrics.handoffs_in.inc()
        return self.inject_request(
            state["prompt_tokens"], state["sampling"],
            request_id=state["request_id"],
            output_tokens=state["output_tokens"],
            arrival_index=(int(state["arrival_index"])
                           if state.get("arrival_index") is not None
                           else None),
            num_preemptions=int(state.get("num_preemptions", 0)),
            elapsed_s=float(state.get("elapsed_s", 0.0)),
            first_token_elapsed_s=state.get("first_token_elapsed_s"),
            offload=rec)

    def inject_request(self, prompt_tokens: Sequence[int],
                       sampling: Optional[SamplingParams] = None, *,
                       request_id: Optional[str] = None,
                       output_tokens: Sequence[int] = (),
                       arrival_index: Optional[int] = None,
                       num_preemptions: int = 0,
                       elapsed_s: float = 0.0,
                       first_token_elapsed_s: Optional[float] = None,
                       offload: Optional[OffloadRecord] = None) -> str:
        """Admit a request WITH prior generation state — the restore /
        migration primitive. The request re-enters the queue
        carrying its prompt AND partial `output_tokens`; admission
        re-prefills the full context (the normal recompute-on-resume
        path) and the step-indexed sample keys make the continued stream
        token-exact, on THIS engine or any sibling replica. Preserving
        `arrival_index` keeps seedless sampling streams and auto ids
        stable across the move (the counter is advanced past it so new
        arrivals never collide). Deliberately bypasses the bounded-queue
        shed gate: recovered requests must never be shed by their own
        restore."""
        sampling = sampling or SamplingParams()
        if arrival_index is not None:
            ensure_arrival_counter_above(int(arrival_index))
            req = Request(prompt_tokens=list(map(int, prompt_tokens)),
                          sampling=sampling, request_id=request_id or "",
                          arrival_index=int(arrival_index))
        else:
            req = Request(prompt_tokens=list(map(int, prompt_tokens)),
                          sampling=sampling, request_id=request_id or "")
        if len(req.prompt_tokens) + sampling.max_tokens > self.max_model_len:
            raise ValueError(
                f"prompt({len(req.prompt_tokens)}) + max_tokens"
                f"({sampling.max_tokens}) exceeds max_model_len="
                f"{self.max_model_len}")
        if req.request_id in self._requests:
            raise ValueError(f"request {req.request_id!r} already present")
        req.output_tokens = list(map(int, output_tokens))
        req.num_preemptions = int(num_preemptions)
        now = self.metrics.clock()
        req.arrival_time = now - float(elapsed_s)
        if first_token_elapsed_s is not None:
            req.first_token_time = req.arrival_time + \
                float(first_token_elapsed_s)
        if offload is not None:
            # a handed-off request arrives with its KV already resident
            # in THIS engine's host tier (import_handoff): admission
            # connects the record and pages in instead of recomputing
            req.offload = offload
            req.phase = "offloaded"
        self._requests[req.request_id] = req
        self.scheduler.add(req)
        self.metrics.requests_added.inc()
        self.metrics.queue_depth.set(self.scheduler.queue_depth)
        return req.request_id

    def extract_request(self, request_id: str) -> dict:
        """Remove a WAITING request and return its serialized state (the
        snapshot per-request shape, with a live SamplingParams object) —
        the drain/redistribute half of migration: the router tier
        extracts queued requests from a restored replica and
        `inject_request`s them into siblings. RUNNING requests hold
        device pages and cannot move; FINISHED ones have nothing to."""
        req = self._requests.get(request_id)
        if req is None:
            raise KeyError(f"unknown request {request_id!r}")
        if req.state is not RequestState.WAITING:
            raise ValueError(
                f"request {request_id!r} is {req.state.value}; only "
                "WAITING requests can be extracted")
        self.scheduler.remove_waiting(req)
        del self._requests[request_id]
        self._detoks.pop(request_id, None)
        self.metrics.queue_depth.set(self.scheduler.queue_depth)
        return self._request_state(req, self.metrics.clock())

    # ------------------------------------------------ snapshot / restore

    def release_prefix_cache(self) -> int:
        """Drop the prefix cache's index and its page references: cached
        -free pages return to the free list; pages still mapped by running
        sequences stay live (they just lose the cache pin). Returns the
        number of pages released. The teardown/leak-audit hook — after a
        drain plus this call, check_no_leaks() must hold again."""
        if self.pool.prefix_cache is None:
            return 0
        return self.pool.prefix_cache.clear()

    def _mesh_axes(self) -> Optional[dict]:
        return ({str(a): int(s) for a, s in self.mesh.shape.items()}
                if self.mesh is not None else None)

    def snapshot(self) -> dict:
        """Crash-safe serialization of ALL request state: prompts,
        generated tokens, sampling params, arrival order, plus finished
        outputs. JSON-serializable; device state is deliberately excluded
        — restore() rebuilds KV via the recompute-on-resume path, which
        the step-indexed sample keys make token-exact. What that leaves
        out, and why nothing is lost:

        - the prefix cache's hash index: it points at device pages whose
          KV does not survive the crash, so a restored engine starts with
          an empty cache and rebuilds it as the recompute-on-resume
          prefills register their pages — after which the still-queued
          siblings hit it again. A snapshot taken mid-chunked-prefill
          serializes the same way: the resumed request re-prefills from
          its (possibly cached) prefix;
        - host-tier PAGES (the tier's options ride along, so a restored
          engine keeps offloading): pinned host RAM has no crash story,
          so every restored request re-enters through the recompute path
          and the tier refills from fresh spills. Handoff-staged requests
          ride along as plain waiters for the same reason, and on a
          restored prefill-role engine they simply re-stage;
        - an in-flight launch's drained-but-unreplayed buffer:
          output_tokens hold only COMMITTED tokens, so the snapshot is
          always pipeline-consistent and the buffer is regenerated by
          recompute (never half-committed);
        - a caller-built draft-model INSTANCE: it snapshots as "custom"
          and restores as the n-gram proposer (logged); only the
          "shadow[:dtype]" string round-trips.

        "config" is the engine's `EngineConfig` as a dict, the runner's
        `recipe()` (dtypes, the int4 group geometry) and the mesh's
        shape. The runner's keys and `mesh_axes` ride along for the
        record: restore() follows the NEW runner, because recompute-on-
        resume rebuilds KV from tokens and is agnostic to quantization
        and sharding (a tp=2 snapshot restores token-exactly on tp=1/2/4;
        streams only stay identical when the dtypes match, and a
        difference is logged)."""
        now = self.metrics.clock()

        def req_state(req: Request) -> dict:
            sp = asdict(req.sampling)
            sp["stop_token_ids"] = list(sp["stop_token_ids"])
            return {**self._request_state(req, now), "sampling": sp}

        # resume priority: running requests first (in admission order —
        # they are the oldest in flight), then the waiting queue left to
        # right (its head already encodes preempted-first recycle order)
        reqs = [req_state(r) for r in (*self.scheduler.running,
                                       *self.scheduler.waiting)]
        reqs += [req_state(self._requests[rid]) for rid in self._handoffs]
        return {
            "version": 1,
            "config": {**self.runner.recipe(), **asdict(self.config),
                       "mesh_axes": self._mesh_axes()},
            "requests": reqs,
            "finished": [asdict(o) for o in self._outputs.values()],
        }

    @classmethod
    def restore(cls, runner: PagedModelRunner, state: dict, *,
                metrics: Optional[EngineMetrics] = None,
                tokenizer=None,
                kv_store=None, kv_store_owner: Optional[str] = None,
                sleep_fn: Optional[Callable[[float], None]] = None,
                audit: Optional[bool] = None) -> "ServingEngine":
        """Rebuild an engine from snapshot() on a fresh runner. Every
        in-flight request re-enters the queue with its prompt AND partial
        generation; admission re-prefills the full context (the normal
        recompute-on-resume path), so the continued token stream is
        identical to an uninterrupted run. A key the snapshot lacks (an
        older one) takes its `EngineConfig` default."""
        if state.get("version") != 1:
            raise ValueError(f"unknown snapshot version {state.get('version')}")
        cfg = state["config"]
        names = {f.name for f in fields(EngineConfig)}
        options = {k: v for k, v in cfg.items() if k in names}
        if options.get("spec_draft_model") == "custom":
            # a caller-built draft-runner instance can't be rebuilt from
            # JSON; token streams stay exact either way (acceptance
            # never depends on draft quality), only the speedup differs
            logger.info("restore: snapshot used a custom draft-model "
                        "instance; restoring with the n-gram proposer")
            options["spec_draft_model"] = None
        eng = cls(runner, tokenizer=tokenizer, kv_store=kv_store,
                  kv_store_owner=kv_store_owner, metrics=metrics,
                  sleep_fn=sleep_fn, audit=audit, **options)
        for r in state["requests"]:
            sp = dict(r["sampling"])
            sp["stop_token_ids"] = tuple(sp.get("stop_token_ids", ()))
            eng.inject_request(
                r["prompt_tokens"], SamplingParams(**sp),
                request_id=r["request_id"],
                output_tokens=r["output_tokens"],
                arrival_index=int(r["arrival_index"]),
                num_preemptions=int(r.get("num_preemptions", 0)),
                elapsed_s=float(r.get("elapsed_s", 0.0)),
                first_token_elapsed_s=r.get("first_token_elapsed_s"))
        for o in state.get("finished", []):
            eng._outputs[o["request_id"]] = RequestOutput(**o)
        eng.metrics.queue_depth.set(eng.scheduler.queue_depth)
        # both legal (recompute-on-resume is token-exact on any mesh, and
        # rebuilds KV in the NEW runner's quantization) but worth a
        # breadcrumb: capacity and throughput differ, and the continued
        # stream follows the new dtypes
        was = {k: cfg.get(k) for k in cfg if k not in names}
        here = {**{k: v for k, v in runner.recipe().items()
                  if k not in names}, "mesh_axes": eng._mesh_axes()}
        if was != here:
            logger.info("restore: snapshot runner %s -> this runner %s",
                        was, here)
        return eng



def naive_generate(runner: PagedModelRunner, prompt_tokens: Sequence[int],
                   sampling: Optional[SamplingParams] = None,
                   max_model_len: Optional[int] = None,
                   fallback_seed: int = 0) -> List[int]:
    """Sequential single-request generation — the scheduling oracle.

    Same runner, same page layout (a private identity-mapped pool), no
    scheduler, no batching, no preemption. ServingEngine must match this
    token-for-token for every request."""
    sampling = sampling or SamplingParams()
    max_model_len = max_model_len or runner.max_model_len
    max_pages = -(-max_model_len // runner.block_size)
    pool = KVCachePool.for_runner(runner, max_pages + 1)
    pages = pool.allocator.alloc(max_pages)
    # per-request KV precision (ISSUE 15): the oracle's pages carry the
    # request's effective tag, so a mixed-pool fp8 tenant's oracle
    # writes through the same fp8 round-trip the engine does
    pool.tag_pages(pages,
                   getattr(sampling, "kv_dtype", None)
                   or pool.native_kv_tag())
    table = pool.pad_table(pages, max_pages)
    tokens = list(map(int, prompt_tokens))
    ring, at = pool.window, {}
    if ring is not None:
        # a window group beside the pages: the one sequence is slot 0's
        at = {"slot": 0, "ring": ring.advance(0, len(tokens))}
    logits, pools = runner.prefill_chunk(tokens, 0, table, pool.pools, **at)
    out: List[int] = []
    tok = sample_token(np.asarray(logits), sampling, 0, fallback_seed)
    out.append(tok)
    tables = np.asarray(table, np.int32)[None]
    while len(out) < sampling.max_tokens and tok not in \
            sampling.stop_token_ids:
        pos = np.asarray([len(tokens) + len(out) - 1], np.int32)
        logits, pools = runner.decode(
            np.asarray([tok], np.int32),
            tables if ring is None else ring.extend_tables(
                tables, [(0, int(pos[0]), int(pos[0]) + 1)]), pos, pools)
        tok = sample_token(np.asarray(logits)[0], sampling, len(out),
                           fallback_seed)
        out.append(tok)
    return out


def create_engine(model, *, num_blocks: int = 128, **kw) -> ServingEngine:
    """Build a ServingEngine for a supported decoder Layer: `build_runner`
    from the names it knows in `kw` (the runner's options, `mesh`,
    `data_axis`, `model_axis`, `comm_dtype`), the engine from the rest.
    `inference.create_serving_engine`, the public entry, adds the cast to
    a serving dtype and documents the options."""
    runner = build_runner(model, **{
        k: kw.pop(k) for k in (*RUNNER_OPTIONS, "mesh", "data_axis",
                               "model_axis", "comm_dtype") if k in kw})
    return ServingEngine(runner, num_blocks=num_blocks, **kw)
