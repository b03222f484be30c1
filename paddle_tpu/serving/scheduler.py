"""Continuous-batching scheduler: FCFS admission, decode reservation,
LIFO preemption.

Reference: the reference's serving deployments drive
block_multihead_attention with exactly this loop (PaddleNLP llm
serving / fastdeploy scheduler): new requests wait in an admission
queue, prefill joins them to the running batch, every decode step first
reserves the KV pages the step will write, and when the pool runs dry
the *youngest* running sequence is preempted — its pages freed, the
request recycled to the FRONT of the queue for recompute-on-resume.

Determinism contract (the equivalence test leans on every clause):
  * admission is strict FCFS with head-of-line blocking — requests are
    admitted in arrival order and a request that does not fit blocks the
    ones behind it (no out-of-order fill);
  * pages come from a sorted free list (kv_cache.BlockAllocator), so the
    same trace of events always yields the same block tables;
  * preemption victims are chosen youngest-first (last admitted), and a
    preempted request resumes with its full context (prompt + generated
    so far) re-prefilled — recompute, not cache migration (with the
    prefix cache on, the recompute is mostly cache hits: the victim's
    full pages survive at refcount 1 and re-match at re-admission).

ISSUE 3 adds chunked prefill: `max_prefill_tokens_per_step` bounds the
prefill tokens computed per engine step, and `prefill_plan()` slices the
running requests' outstanding context into chunks under that budget
(oldest-first), so a long-prompt arrival never stalls running decodes
for more than one chunk budget per step. Admission maps the longest
cached page-aligned prefix from the pool's PrefixCache before
allocating the remainder.

ISSUE 6 adds multi-step decode planning: `plan_decode_horizon(s)`
pre-commits the KV pages the next `s` decode tokens of EVERY
decode-phase request will write, so the engine can run `s` device steps
back-to-back (`runner.decode_multi`) without touching the host. The
horizon degrades, never thrashes: when the free list or the admission
watermark can't fund the extra pages, `s` is trimmed down (to 1 in the
worst case) instead of preempting anyone — preemption stays the
exclusive business of `reserve_decode()`, which must have run first.

ISSUE 10 tiers the preemption story: with the pool's HostKVTier on,
`_preempt` SPILLS the victim's exclusively-owned pages to pinned host
buffers instead of just dropping them (the request waits with
phase="offloaded" and an OffloadRecord), and `admit()` plans the
resume: the tiered prefix match (device pages free, host-demoted pages
staged for page-in) is connected to the offload record's page range,
fresh device pages are allocated for everything host-resident, and the
engine pages the bytes in before the step that reads them — restore
becomes an O(bytes) copy instead of an O(prefill) recompute. Any hole
(evicted-and-dropped prefix page, tier cap overflow, crash) falls back
to the existing recompute-on-resume path, so token exactness is
untouched by construction. `count_host_headroom=True` additionally
lets the admission watermark treat free host-tier slots as
near-headroom: growth overflow now degrades to a cheap spill/page-in
round-trip rather than a full recompute, so the same pool sustains
more concurrent sessions.
"""

from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from paddle_tpu.serving.kv_cache import (
    KVCachePool, OffloadRecord, SequenceKV,
)


@dataclass
class SamplingParams:
    """Per-request sampling controls (reference: generation config of the
    reference's serving API; greedy by default so runs are reproducible)."""

    max_tokens: int = 16
    temperature: float = 0.0          # 0.0 = greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: Optional[int] = None        # None -> derived from request id
    stop_token_ids: Tuple[int, ...] = ()
    timeout_s: Optional[float] = None   # deadline from arrival; None = never
    # multi-turn chat affinity (ISSUE 10 satellite): the router pins
    # every request carrying the same session_id to one replica AHEAD of
    # prefix-affinity, so repeat turns land where the session's KV pages
    # (device prefix cache + host tier) already live. None = stateless.
    session_id: Optional[str] = None
    # per-request KV precision (ISSUE 15): None = the pool's own rung.
    # On a kv_dtype="mixed" engine, "fp8" tenants get fp8-rounded pages
    # (tagged at alloc, bit-identical to a native fp8 pool) beside
    # "fp32" tenants in ONE pool geometry; on homogeneous pools only
    # the pool's own dtype is accepted (the engine validates loudly).
    kv_dtype: Optional[str] = None

    def __post_init__(self):
        if self.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if self.timeout_s is not None and self.timeout_s <= 0:
            raise ValueError("timeout_s must be positive (None = no deadline)")
        if self.kv_dtype not in (None, "fp32", "fp8", "int8"):
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r}; expected None, 'fp32', "
                "'fp8', or 'int8'")


class RequestState(Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"


_req_counter = itertools.count()


def ensure_arrival_counter_above(n: int) -> None:
    """Advance the global arrival counter past ``n``.

    Restore-time hook (ServingEngine.restore): restored requests keep
    their original arrival_index — it seeds seedless sampling and names
    auto request ids — so requests added AFTER a restore must start
    beyond every restored index or streams/ids would collide."""
    global _req_counter
    current = next(_req_counter)
    _req_counter = itertools.count(max(current + 1, n + 1))


@dataclass(eq=False)          # identity semantics: the scheduler tracks
class Request:                # requests by object, never by field value
    """One in-flight generation request."""

    prompt_tokens: List[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    request_id: str = ""
    arrival_index: int = field(default_factory=lambda: next(_req_counter))
    state: RequestState = RequestState.WAITING
    output_tokens: List[int] = field(default_factory=list)
    finish_reason: Optional[str] = None    # "stop" | "length"
    kv: Optional[SequenceKV] = None
    slot: Optional[int] = None
    # "prefill" until the chunk that completes the context samples its
    # token, then "decode"; reset at every (re-)admission
    phase: str = "prefill"
    # set when a multi-step horizon hit non-finite logits it could not
    # rescue without the row (nan_policy="greedy"): the next engine step
    # takes the per-step path once, which refetches real logits
    defer_horizon: bool = False
    # host-tier state (ISSUE 10): while WAITING with phase="offloaded",
    # `offload` names the host slots holding this request's spilled KV;
    # admission converts it into `pending_pagein` (device page, host
    # slot) pairs the engine's fence restores before this step's
    # compute, and stamps the admit_* token splits for the metrics
    offload: Optional[OffloadRecord] = None
    pending_pagein: List[Tuple[int, int]] = field(default_factory=list)
    admit_prefix_tokens: int = 0
    admit_pagein_tokens: int = 0
    admission_index: int = -1              # set fresh at every admission
    num_preemptions: int = 0
    arrival_time: float = 0.0
    # when add_request queued it, on the spans' clock (paddle_tpu.profiler);
    # the `request.queue` span closes at the first admission and clears it
    queued_ns: Optional[int] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None

    def __post_init__(self):
        if not self.prompt_tokens:
            raise ValueError("empty prompt")
        if not self.request_id:
            self.request_id = f"req-{self.arrival_index}"

    @property
    def context_tokens(self) -> List[int]:
        """Prompt plus everything generated — what a (re-)prefill runs."""
        return self.prompt_tokens + self.output_tokens

    @property
    def num_context(self) -> int:
        return len(self.prompt_tokens) + len(self.output_tokens)

    @property
    def done(self) -> bool:
        return self.state is RequestState.FINISHED


class FCFSScheduler:
    """Admission queue + running set over one KVCachePool."""

    def __init__(self, pool: KVCachePool, max_batch_size: int,
                 max_pages_per_seq: int, admission_watermark: float = 1.0,
                 max_prefill_tokens_per_step: Optional[int] = None,
                 count_host_headroom: bool = False):
        if max_pages_per_seq > pool.allocator.num_usable:
            raise ValueError(
                f"max_pages_per_seq={max_pages_per_seq} exceeds the pool's "
                f"{pool.allocator.num_usable} usable pages — one sequence "
                "could never fit; enlarge num_blocks")
        if not 0.0 < admission_watermark <= 1.0:
            raise ValueError("admission_watermark must be in (0, 1]")
        if (max_prefill_tokens_per_step is not None
                and max_prefill_tokens_per_step < 1):
            raise ValueError("max_prefill_tokens_per_step must be >= 1 "
                             "(None = whole context in one chunk)")
        self.max_prefill_tokens_per_step = max_prefill_tokens_per_step
        self.pool = pool
        self.max_batch_size = max_batch_size
        self.max_pages_per_seq = max_pages_per_seq
        self.admission_watermark = admission_watermark
        # pool high watermark: admission stops once allocation would cross
        # this many pages, leaving headroom for running sequences to GROW —
        # overload then degrades throughput instead of thrashing preemptions
        self._watermark_pages = int(admission_watermark
                                    * pool.allocator.num_usable)
        # knob-gated (ISSUE 10): free host-tier slots count as NEAR-
        # headroom above the watermark — overflow then degrades to a
        # spill/page-in round-trip instead of a recompute, so admission
        # can afford to run the pool hotter
        self.count_host_headroom = bool(count_host_headroom)
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []     # kept in admission order
        self._admission_counter = itertools.count()
        self._free_slots = list(range(max_batch_size))  # ascending

    # ------------------------------------------------------------- queue

    def add(self, req: Request) -> None:
        req.state = RequestState.WAITING
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    # --------------------------------------------------------- admission

    def _effective_watermark(self) -> int:
        """The admission high watermark in pages. With the host tier on
        and `count_host_headroom` set, free host slots count as NEAR-
        headroom (capped at the pool size): running the pool past the
        bare watermark is now safe-ish because a growth overflow spills
        to host and pages back in instead of recomputing (ISSUE 10)."""
        wm = self._watermark_pages
        tier = self.pool.host_tier
        if tier is not None and self.count_host_headroom:
            wm = min(self.pool.allocator.num_usable, wm + tier.free_count)
        return wm

    def admit(self) -> List[Request]:
        """Admit queue-head requests while a slot and enough pages exist
        for their full context PLUS one decode token (so every admitted
        request is guaranteed its first generated token without an
        immediate self-preemption). Strict FCFS: stop at the first
        request that does not fit.

        With the pool's PrefixCache enabled, the longest cached
        page-aligned prefix of the request's context is mapped (shared,
        increfed) into its block table before the remainder is allocated
        — those tokens are already live KV, so prefill starts after them
        and the pool only has to fund the unmatched tail.

        With the HostKVTier enabled (ISSUE 10) the match extends into
        the host: demoted prefix pages and the request's own
        OffloadRecord map onto FRESH device pages whose contents the
        engine pages in before this step's compute (`pending_pagein`),
        so a preempted request resumes by copy instead of recompute.
        The offload record must CONNECT to the matched prefix (its
        start_page covered by device+host matches); a hole — an evicted
        prefix page the tier dropped, a partial spill, a crash — falls
        back to the recompute path, exactness untouched."""
        admitted: List[Request] = []
        alloc = self.pool.allocator
        cache = self.pool.prefix_cache
        tier = self.pool.host_tier
        bs = self.pool.block_size
        while self.waiting and self._free_slots:
            req = self.waiting[0]
            need = self.pool.blocks_for_tokens(req.num_context + 1)
            if need > self.max_pages_per_seq:
                raise ValueError(
                    f"request {req.request_id} needs {need} pages > "
                    f"max_pages_per_seq={self.max_pages_per_seq}")
            # the request's effective kv-dtype tag (ISSUE 15): every
            # page it allocates is stamped with it, and its prefix
            # chain is seeded by it (mixed-precision tenants can never
            # share pages — their KV bytes for equal tokens differ)
            tag = req.sampling.kv_dtype or self.pool.native_kv_tag()
            if cache is not None:
                matched, host_matched = cache.match_tiered(
                    req.context_tokens, tag=tag)
            else:
                matched, host_matched = [], []
            if matched:
                # pin the match BEFORE any allocation: an incref lifts
                # the pages above refcount 1, so eviction (which alloc
                # may trigger) cannot reclaim them mid-admission
                cache.acquire(matched)
            need_new = need - len(matched)
            # live = pages some sequence actually maps; cached-free pages
            # are reclaimable, so they count as headroom, not pressure
            used_live = (alloc.num_usable - alloc.num_free
                         - alloc.num_evictable)
            over_watermark = (used_live + need_new
                              > self._effective_watermark()
                              and (self.running or admitted))
            if not alloc.can_alloc(need_new) or over_watermark:
                if matched:
                    cache.unacquire(matched)
                # over the high watermark: stop admitting — unless nothing
                # is running at all (progress guarantee: a request larger
                # than the watermark must still be servable alone)
                break
            self.waiting.popleft()
            req.kv = SequenceKV(self.pool, kv_tag=tag)
            if matched:
                req.kv.adopt_prefix(matched, bs)
            # host-demoted prefix pages: a fresh device page per hash,
            # content restored by the engine's fence before this step's
            # compute; the page re-enters the device index (promotion).
            # With a shared store (ISSUE 14) promote() takes a tier-wide
            # reference and may MISS — a sibling's recomputed
            # registration dropped the entry between match and promote —
            # in which case the chain truncates here and the remaining
            # tokens recompute (exactness untouched)
            promoted = 0
            for h in host_matched:
                slot = tier.promote(h)
                if slot is None:
                    break
                page = alloc.alloc(1)[0]
                self.pool.tag_pages([page], tag)
                cache.register_page(page, h)
                req.kv.pages.append(page)
                req.kv.hash_chain.append(h)
                req.kv.registered_pages += 1
                req.kv.num_tokens = len(req.kv.pages) * bs
                req.pending_pagein.append((page, slot))
                promoted += 1
            req.admit_prefix_tokens = req.kv.num_tokens
            req.admit_pagein_tokens = 0
            m_total = len(matched) + promoted
            off, req.offload = req.offload, None
            if off is not None and tier is not None:
                connected = (m_total >= off.start_page
                             and off.covered_tokens > req.kv.num_tokens)
                if connected:
                    for j, slot in enumerate(off.slots):
                        idx = off.start_page + j
                        if idx < m_total:
                            # the prefix match already covers this page
                            # (same tokens -> same KV); the host copy is
                            # redundant — drop it
                            tier.free_slots([slot])
                            continue
                        page = alloc.alloc(1)[0]
                        self.pool.tag_pages([page], tag)
                        req.kv.pages.append(page)
                        req.pending_pagein.append((page, slot))
                    req.admit_pagein_tokens = (off.covered_tokens
                                               - req.kv.num_tokens)
                    req.kv.num_tokens = off.covered_tokens
                    tier.note_resume()
                else:
                    # recompute fallback: a hole in the restorable prefix
                    # (or the prefix match already covers everything) —
                    # release the host copies and re-prefill as before
                    tier.free_slots(off.slots)
                    if m_total < off.start_page:
                        tier.note_fallback()
            req.kv.grow(req.num_context + 1 - req.kv.num_tokens)
            req.slot = self._free_slots.pop(0)
            req.admission_index = next(self._admission_counter)
            req.state = RequestState.RUNNING
            req.phase = "prefill"
            self.running.append(req)
            admitted.append(req)
        return admitted

    # ---------------------------------------------------- chunked prefill

    def prefill_plan(self) -> List[Tuple[Request, int, int]]:
        """Slice the running requests' outstanding context into prefill
        chunks for THIS step, oldest-first, spending at most
        `max_prefill_tokens_per_step` tokens total (None = unbounded, one
        chunk per request). Returns (request, start, end) token ranges;
        `end == request.num_context` marks the completing chunk whose
        logits the engine samples from."""
        budget = self.max_prefill_tokens_per_step
        plan: List[Tuple[Request, int, int]] = []
        for req in self.running:               # admission order = oldest
            if req.phase != "prefill" or req.kv is None:
                continue
            remaining = req.num_context - req.kv.num_tokens
            if remaining <= 0:                 # pragma: no cover — a
                continue                       # prefill-phase req always
            take = remaining                   # has outstanding tokens
            if budget is not None:
                take = min(take, budget)
                if take <= 0:
                    break
            plan.append((req, req.kv.num_tokens, req.kv.num_tokens + take))
            if budget is not None:
                budget -= take
                if budget <= 0:
                    break
        return plan

    def decode_ready(self) -> List[Request]:
        """Decode-phase running requests in admission order — the spans
        the batched decode step feeds, and the decode half of a fused
        ragged step (engine ragged_batch mode: this step's prefill
        chunks and these decodes ride ONE runner.ragged_step call)."""
        return [r for r in self.running if r.phase == "decode"]

    # ------------------------------------------------------- speculation

    def speculation_budget(self, chunk_tokens: int) -> Optional[int]:
        """Per-step token budget left for speculative (verify-span)
        tokens after this step's prefill chunks (ISSUE 5): verify spans
        count against `max_prefill_tokens_per_step` exactly like chunk
        tokens do, so the fused launch's live-row count stays bounded by
        the same knob that bounds chunked prefill. Only the EXTRA
        speculative tokens are budgeted — the mandatory one-token decode
        feed per request always runs, budget or not (a decode step was
        never budget-gated). None = unbounded."""
        if self.max_prefill_tokens_per_step is None:
            return None
        return max(0, self.max_prefill_tokens_per_step - chunk_tokens)

    def reserve_speculation(self, proposals: Dict[Request, List[int]]) -> int:
        """Best-effort page reservation for this step's verify spans,
        admission order: each decode request's proposal is trimmed (in
        place) until the pages its whole `1+k`-token span will write can
        be funded WITHOUT preempting — speculation never evicts a running
        sequence's pages; under pool pressure it degrades to a plain
        decode (k=0) instead. Runs after reserve_decode(), which already
        funded the mandatory decode token the hard way. Returns the
        total number of reserved speculative tokens."""
        total = 0
        for req in self.running:
            prop = proposals.get(req)
            if req.phase != "decode" or not prop:
                continue
            k = len(prop)
            while k:
                short = req.kv.pages_short(1 + k)
                if short == 0 or self.pool.allocator.can_alloc(short):
                    break
                k -= 1
            del prop[k:]
            if k:
                req.kv.grow(1 + k)
                total += k
        return total

    def plan_spec_horizon(self, s: int, row_k: Dict[Request, int],
                          row_rem: Dict[Request, int]) -> int:
        """Page funding for the fused verify-in-scan horizon (ISSUE 18):
        a speculative horizon of `s` scan steps writes, per decode-ready
        row, up to min(s * (k+1), remaining + k) tokens beyond its
        current coverage — full acceptance moves k+1 tokens per step,
        while the on-device stop plane bounds kept emissions by
        `remaining`, so the worst-case overhang past the last kept token
        is one span's k draft writes. Like `plan_decode_horizon` this
        NEVER preempts: first `s` is trimmed toward 1 under free-list /
        watermark pressure; at s == 1 each row's k is then shrunk in
        place (the `reserve_speculation` degradation — speculation
        collapses to plain decode before anyone is evicted).
        `row_k` is mutated to the funded per-row draft lengths. Returns
        the effective horizon (0 with no decode-ready requests)."""
        batch = self.decode_ready()
        if not batch:
            return 0
        s = max(1, int(s))
        alloc = self.pool.allocator

        cap = self.max_pages_per_seq * self.pool.block_size

        def up(r, n, k=None):
            # rem is wall-capped but the +k rejected-draft slack is
            # not: clamp at the block-table width or a near-wall row
            # funds (and tables) a page past max_pages_per_seq that
            # the kernel's wall mask would never write
            k = row_k.get(r, 0) if k is None else k
            return max(1, min(n * (k + 1), row_rem.get(r, 1) + k,
                              cap - r.kv.num_tokens))

        while s > 1:
            short = sum(r.kv.pages_short(up(r, s)) for r in batch)
            if short == 0:
                break
            used_live = (alloc.num_usable - alloc.num_free
                         - alloc.num_evictable)
            if (alloc.can_alloc(short)
                    and used_live + short <= self._effective_watermark()):
                break
            s -= 1
        if s == 1:
            # shrink-and-grow per row IN ORDER: the grow must land
            # before the next row's can_alloc check, or N rows each
            # "fit" against the same last free page and the batch-wide
            # grow below blows past the pool
            for r in batch:
                k = row_k.get(r, 0)
                while k:
                    short = r.kv.pages_short(up(r, 1, k))
                    if short == 0 or alloc.can_alloc(short):
                        break
                    k -= 1
                row_k[r] = k
                r.kv.grow(up(r, 1, k))
            return 1
        for r in batch:
            r.kv.grow(up(r, s))
        return s

    # ------------------------------------------------- multi-step decode

    def plan_decode_horizon(self, s: int, row_caps=None) -> int:
        """Pre-commit pages for up to `s` future decode tokens per
        decode-ready request (ISSUE 6): the multi-step device loop
        writes K/V for its whole horizon against block tables that are
        FIXED at launch, so every page must exist before the call.
        Trims `s` down — NEVER preempting — whenever the free list or
        the admission watermark cannot fund the extra pages: a tight
        pool degrades the horizon back toward per-step decode instead
        of evicting anyone. Assumes reserve_decode() already funded
        step one (s=1 needs no new pages by that invariant). Grows
        every decode-ready sequence to the returned effective horizon
        and returns it (0 with no decode-ready requests).

        `row_caps` (ISSUE 11, on-device early stop): an optional
        {request: max_upcoming_tokens} map — a row that will provably
        freeze after its remaining-token budget only funds pages for
        min(s, cap) tokens, so a near-finished or near-model-length
        row neither blocks a long horizon nor over-allocates pages its
        frozen KV writes would never touch."""
        batch = self.decode_ready()
        if not batch:
            return 0
        s = max(1, int(s))
        alloc = self.pool.allocator

        def up(r, n):
            return min(n, row_caps[r]) if row_caps else n

        while s > 1:
            short = sum(r.kv.pages_short(up(r, s)) for r in batch)
            if short == 0:
                break
            used_live = (alloc.num_usable - alloc.num_free
                         - alloc.num_evictable)
            if (alloc.can_alloc(short)
                    and used_live + short <= self._effective_watermark()):
                break
            s -= 1
        if s > 1:
            for r in batch:
                r.kv.grow(up(r, s))
        return s

    # -------------------------------------------------------- preemption

    def reserve_decode(self) -> List[Request]:
        """Reserve the KV page each running sequence's next token will
        write, preempting youngest-first when the pool runs dry. Returns
        the victims (already recycled to the queue front). Called before
        every decode step."""
        victims: List[Request] = []
        for req in list(self.running):      # admission order = oldest first
            if victims and req not in self.running:
                continue                    # already preempted this pass
            while True:
                short = req.kv.pages_short(1)
                if short == 0:              # most steps: nothing to grow
                    break
                if self.pool.allocator.can_alloc(short):
                    req.kv.grow(1)
                    break
                victim = self.running[-1]   # youngest
                if victim is req and len(self.running) == 1:
                    raise MemoryError(
                        f"request {req.request_id} cannot grow even with "
                        "the pool to itself — num_blocks too small for "
                        "max_model_len")
                self._preempt(victim)
                victims.append(victim)
                if victim is req:
                    break
        # queue-front recycle in arrival order: oldest victim resumes first
        for v in sorted(victims, key=lambda r: r.arrival_index, reverse=True):
            self.waiting.appendleft(v)
        return victims

    def _preempt(self, req: Request) -> None:
        tier = self.pool.host_tier
        if tier is not None and req.kv is not None:
            # spill the victim's exclusively-owned pages to host BEFORE
            # release() sends them back to the free list (ISSUE 10):
            # resume then restores them by copy instead of recompute.
            # Coverage is clamped to context-1 so the resumed request
            # always has at least one token to compute (admission's
            # first-token guarantee, and the logits it samples from).
            covered = min(req.kv.num_tokens, req.num_context - 1)
            req.offload = tier.spill_sequence(req.kv, covered)
        req.kv.release()
        req.kv = None
        self._release_slot(req)
        self.running.remove(req)
        req.state = RequestState.WAITING
        if req.offload is not None:
            req.phase = "offloaded"
        req.num_preemptions += 1

    def _drop_offload(self, req: Request) -> None:
        """Release a request's host-tier state (abort/timeout/shed/
        extract of an offloaded waiter): the slots return to the tier,
        the request reverts to a plain recompute-on-resume waiter."""
        if req.offload is not None:
            tier = self.pool.host_tier
            if tier is not None:
                tier.free_slots(req.offload.slots)
            req.offload = None
            if req.phase == "offloaded":
                req.phase = "prefill"

    def release_running(self, req: Request) -> None:
        """Release a RUNNING request's device resources WITHOUT
        finishing it — the handoff-staging path (ISSUE 12): pages and
        slot are freed (the pages were already spilled to the host
        tier by the caller) and the request leaves the running set in
        state WAITING, but does NOT rejoin the waiting queue:
        ownership passes to the engine's handoff buffer, from which
        the router extracts it for migration to a decode replica."""
        req.kv.release()
        req.kv = None
        self._release_slot(req)
        self.running.remove(req)
        req.state = RequestState.WAITING

    # ---------------------------------------------------------- finish

    def remove_waiting(self, req: Request) -> None:
        """Drop a queued (never-admitted or preempted) request — the
        deadline/abort/shed path. Holds no device pages or slot by
        invariant; host-tier slots (an offloaded waiter) are released
        here so a shed request never pins host memory."""
        self.waiting.remove(req)      # identity match (Request is eq=False)
        self._drop_offload(req)

    def finish(self, req: Request, reason: str) -> None:
        req.kv.release()
        req.kv = None
        self._release_slot(req)
        self.running.remove(req)
        req.state = RequestState.FINISHED
        req.finish_reason = reason

    def _release_slot(self, req: Request) -> None:
        ring = getattr(self.pool, "window", None)
        if ring is not None:
            # the window group's pages go back with the slot that held them
            ring.release(req.slot)
        self._free_slots.append(req.slot)
        self._free_slots.sort()            # lowest slot reused first
        req.slot = None

    # ------------------------------------------------------------ views

    def running_in_order(self) -> Sequence[Request]:
        return tuple(self.running)
