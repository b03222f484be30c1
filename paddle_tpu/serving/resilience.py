"""Fault tolerance for the serving engine: injected faults + invariants.

Reference: production TPU serving stacks treat failure as a first-class
input — admission control, request deadlines, and graceful degradation
rather than crash-or-hang (the Ragged-Paged-Attention serving line and
the reference's fastdeploy health/recovery loop). This module holds the
pieces the engine's hardening leans on:

  FaultInjector      a drop-in PagedModelRunner wrapper that raises
                     simulated device errors, corrupts logits with
                     NaN/Inf, or stalls the clock on chosen calls —
                     the test harness for every recovery path;
  audit_engine       the invariant auditor: page accounting, slot
                     assignment, and block tables must be mutually
                     consistent after every step (zero leaks);
  InjectedDeviceError / QueueFullError / InvariantViolation
                     the failure vocabulary the engine surfaces.

Everything here is deterministic: fault schedules are keyed by call
index (never wall time or RNG), so a failing trace replays exactly.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Iterable, Optional

import numpy as np

from paddle_tpu.serving.kv_cache import SCRATCH_PAGE

logger = logging.getLogger(__name__)


class InjectedDeviceError(RuntimeError):
    """A simulated transient device failure (FaultInjector's default)."""


class QueueFullError(RuntimeError):
    """add_request rejected: bounded queue full under shed_policy='reject'."""


class InvariantViolation(AssertionError):
    """Engine state is internally inconsistent (leak / double-own / slot
    corruption). Raised by audit_engine; always a bug, never load."""


class ReplicaCrashError(BaseException):
    """A simulated WHOLE-REPLICA failure (ISSUE 8 fault class).

    Derives from BaseException ON PURPOSE: the engine's transient-fault
    recovery catches `Exception`, so this error cannot be absorbed by
    step retries or quarantine — it escapes engine.step() and kills the
    replica's worker thread, which is exactly the contract a real
    replica death has (OOM kill, device loss, segfaulted process). The
    router tier's Supervisor, not the engine, owns this failure mode:
    it must fence the dead replica, restore from the last crash-safe
    snapshot, and resubmit anything the snapshot missed."""


class ReplicaGoneError(ReplicaCrashError):
    """A replica PROCESS is unreachable (ISSUE 12): its command socket
    hit EOF/reset/timeout, or waitpid reported an exit. Subclasses
    ReplicaCrashError on purpose — the process-backend analogue of a
    crashed thread rides the exact same uncatchable-by-the-engine
    contract, so the router worker fences the replica and the
    Supervisor respawns a fresh process."""


class FaultInjector:
    """Wrap a PagedModelRunner and inject faults on selected calls.

    Drop-in: exposes the runner's attributes (block_size, num_layers,
    dtype, ...) by delegation, so ``ServingEngine(FaultInjector(runner,
    ...), ...)`` behaves exactly like the bare runner except on the
    scheduled calls. Sharded runners (runner.shard(mesh), ISSUE 7) are
    wrapped the same way — `mesh`/`model_axis`/`tp_size` delegate
    through, so the engine still builds kv-head-sharded pools, injected
    errors hit the sharded launch before any device work (retry exact),
    and NaN corruption happens on the replicated host-side logits. Call indices are 1-based and counted PER OP, so
    ``decode_error_every=5`` fails decode calls 5, 10, 15, ... — the
    engine's retry makes the very next attempt (a new call) succeed.

    Fault classes (each with ``*_every`` periodic and ``*_calls`` exact
    schedules, and a target op "prefill" | "decode" | "both"):

      error  raise InjectedDeviceError BEFORE touching the real runner
             (the KV pool is untouched, so a retry is exact);
      nan    run the real step, then overwrite the leading
             ``nan_fraction`` of the vocab with NaN (the KV write has
             happened; decode re-writes identical values, so both retry
             and greedy-fallback stay token-deterministic);
      stall  call ``on_stall`` (default: time.sleep(stall_s)) before the
             step — with the engine's injectable clock this simulates a
             stuck device step that pushes requests past their deadline.
    """

    def __init__(self, runner, *,
                 error_every: int = 0, error_calls: Iterable[int] = (),
                 error_target: str = "decode",
                 nan_every: int = 0, nan_calls: Iterable[int] = (),
                 nan_target: str = "decode", nan_fraction: float = 1.0,
                 stall_every: int = 0, stall_calls: Iterable[int] = (),
                 stall_target: str = "decode", stall_s: float = 0.0,
                 on_stall: Optional[Callable[[], None]] = None,
                 crash_every: int = 0, crash_calls: Iterable[int] = (),
                 crash_target: str = "decode"):
        self._runner = runner
        for t in (error_target, nan_target, stall_target, crash_target):
            if t not in ("prefill", "decode", "both"):
                raise ValueError(f"fault target {t!r}")
        if not 0.0 < nan_fraction <= 1.0:
            raise ValueError("nan_fraction must be in (0, 1]")
        self._error = (error_every, frozenset(error_calls), error_target)
        self._nan = (nan_every, frozenset(nan_calls), nan_target)
        self._stall = (stall_every, frozenset(stall_calls), stall_target)
        # crash (ISSUE 8): raise ReplicaCrashError — a BaseException the
        # engine's retry loop can NOT catch, so the scheduled call kills
        # the whole replica (the supervisor drill's fault class)
        self._crash = (crash_every, frozenset(crash_calls), crash_target)
        self.nan_fraction = nan_fraction
        self._on_stall = on_stall or (lambda: time.sleep(stall_s))
        self.calls = {"prefill": 0, "decode": 0}
        self.injected = {"error": 0, "nan": 0, "stall": 0, "crash": 0}

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_runner"), name)

    @staticmethod
    def _hits(schedule, op: str, n: int) -> bool:
        every, calls, target = schedule
        if target not in (op, "both"):
            return False
        return (every > 0 and n % every == 0) or n in calls

    def _corrupt(self, logits):
        arr = np.array(logits, np.float32, copy=True)
        k = max(1, int(round(arr.shape[-1] * self.nan_fraction)))
        arr[..., :k] = np.nan
        return arr

    def _pre(self, op: str) -> int:
        self.calls[op] += 1
        n = self.calls[op]
        if self._hits(self._stall, op, n):
            self.injected["stall"] += 1
            self._on_stall()
        if self._hits(self._crash, op, n):
            self.injected["crash"] += 1
            raise ReplicaCrashError(
                f"injected replica crash: {op} call {n}")
        if self._hits(self._error, op, n):
            self.injected["error"] += 1
            raise InjectedDeviceError(f"injected device error: {op} call {n}")
        return n

    def prefill(self, tokens, table, pools):
        n = self._pre("prefill")
        logits, pools = self._runner.prefill(tokens, table, pools)
        if self._hits(self._nan, "prefill", n):
            self.injected["nan"] += 1
            logits = self._corrupt(logits)
        return logits, pools

    def prefill_chunk(self, tokens, start_pos, table, pools):
        # chunks share the "prefill" op counter: a chunked engine sees
        # the same per-prefill-call fault schedule as a monolithic one
        n = self._pre("prefill")
        logits, pools = self._runner.prefill_chunk(tokens, start_pos, table,
                                                   pools)
        if self._hits(self._nan, "prefill", n):
            self.injected["nan"] += 1
            logits = self._corrupt(logits)
        return logits, pools

    def decode(self, tokens, tables, pos, pools):
        n = self._pre("decode")
        logits, pools = self._runner.decode(tokens, tables, pos, pools)
        if self._hits(self._nan, "decode", n):
            self.injected["nan"] += 1
            logits = self._corrupt(logits)
        return logits, pools

    def decode_multi(self, tokens, tables, pos, pools, num_steps, **kw):
        # the multi-step horizon (ISSUE 6) IS the step's decode call
        # site — it shares the "decode" op counter like ragged_step, so
        # a decode fault schedule keeps firing when the engine batches s
        # steps per launch. NaN injection can't reach the logits inside
        # the device-resident scan, so it drops the packed finiteness
        # flags instead (every step of the call): the engine sees the
        # horizon "go NaN" at step one, exactly like a full-vocab
        # corruption of the first step's logits on the per-step path.
        # The extended-horizon operands (ISSUE 11: seeded sampling /
        # early stop) pass through untouched; plane 1 is the finiteness
        # plane on both the [2, B, s] and [3, B, s] layouts.
        n = self._pre("decode")
        packed, pools = self._runner.decode_multi(tokens, tables, pos,
                                                  pools, num_steps, **kw)
        if self._hits(self._nan, "decode", n):
            self.injected["nan"] += 1
            arr = np.array(packed, np.int32, copy=True)
            arr[1] = 0
            packed = arr
        return packed, pools

    def decode_multi_spec(self, tokens, tables, pos, pools, drafts, **kw):
        # the fused speculative horizon (ISSUE 18) IS the step's decode
        # call site — same "decode" op counter as decode_multi and
        # ragged_step, so every fault schedule keeps firing when verify
        # spans ride the scan. NaN injection zeroes the packed
        # finiteness plane (plane 1 on the [3, B, s, K+1] layout, same
        # index as the horizon layouts): the engine sees the whole
        # horizon "go NaN" at its first kept position, exercising
        # _horizon_nan's truncate + per-step deferral under speculation.
        n = self._pre("decode")
        packed, pools = self._runner.decode_multi_spec(
            tokens, tables, pos, pools, drafts, **kw)
        if self._hits(self._nan, "decode", n):
            self.injected["nan"] += 1
            arr = np.array(packed, np.int32, copy=True)
            arr[1] = 0
            packed = arr
        return packed, pools

    def ragged_step(self, tokens, tables, start_pos, q_lens, pools,
                    full_logits: bool = False):
        # the fused chunk+decode call (engine ragged_batch mode, ISSUE 4)
        # IS the step's decode call site — it shares the "decode" op
        # counter, so a decode fault schedule keeps firing when the
        # engine collapses its sequencing into one ragged launch. The
        # speculative verify call (ISSUE 5, full_logits=True) rides the
        # same wrapper: error/nan/stall schedules cover verification too
        # (_corrupt NaNs the leading vocab fraction of EVERY span row)
        n = self._pre("decode")
        if full_logits:
            logits, pools = self._runner.ragged_step(
                tokens, tables, start_pos, q_lens, pools, full_logits=True)
        else:
            logits, pools = self._runner.ragged_step(tokens, tables,
                                                     start_pos, q_lens, pools)
        if self._hits(self._nan, "decode", n):
            self.injected["nan"] += 1
            logits = self._corrupt(logits)
        return logits, pools


class WireFaultInjector:
    """Deterministic WIRE fault schedules for the process tier
    (ISSUE 13): attached to an `EngineClient` (`client.wire_faults =
    WireFaultInjector(...)`), consulted once per RPC attempt, and keyed
    by call index over the RPCs the `target` matches — never wall time
    or RNG, so a failing trace replays exactly (the FaultInjector
    discipline, moved from the device to the socket).

    Fault classes (each with ``*_every`` periodic and ``*_calls`` exact
    schedules; call indices are 1-based over TARGET-matched RPCs):

      drop      the request's framed bytes never leave the host — the
                client's per-RPC deadline trips cleanly (idempotent
                RPCs retry, mutating ones escalate to the supervisor);
      corrupt   one payload byte of the outbound request is flipped
                AFTER framing — the replica's CRC must reject it and
                NAK (never parse it as a command);
      truncate  only the first half of the framed bytes are sent — the
                replica blocks mid-frame, the client's deadline trips,
                and any retry desyncs into a loud connection error,
                never a silent mis-parse;
      delay     the request is sent, then the client sleeps `delay_s`
                before reading — the gray-failure class: a
                slow-but-alive replica whose reply lands after the
                deadline (the late reply is seq-matched as stale and
                discarded by the retry);
      reset     the client's half of the connection is shut down under
                the RPC — EOF/EPIPE both ways, always fatal, the
                supervisor respawns.

    `target` picks which RPCs the schedule counts: "all", "idempotent"
    (the retry-safe set), "mutating", or an exact command name / tuple
    of names (e.g. "step").
    """

    ACTIONS = ("reset", "truncate", "corrupt", "drop", "delay")

    def __init__(self, *, drop_every: int = 0,
                 drop_calls: Iterable[int] = (),
                 corrupt_every: int = 0,
                 corrupt_calls: Iterable[int] = (),
                 truncate_every: int = 0,
                 truncate_calls: Iterable[int] = (),
                 delay_every: int = 0, delay_calls: Iterable[int] = (),
                 delay_s: float = 0.5,
                 reset_every: int = 0, reset_calls: Iterable[int] = (),
                 target="all"):
        from paddle_tpu.serving.wire import IDEMPOTENT_RPCS

        self._idempotent = IDEMPOTENT_RPCS
        if isinstance(target, str) and target not in ("all",
                                                      "idempotent",
                                                      "mutating"):
            target = (target,)
        self.target = target
        self.delay_s = float(delay_s)
        self._sched = {
            "drop": (drop_every, frozenset(drop_calls)),
            "corrupt": (corrupt_every, frozenset(corrupt_calls)),
            "truncate": (truncate_every, frozenset(truncate_calls)),
            "delay": (delay_every, frozenset(delay_calls)),
            "reset": (reset_every, frozenset(reset_calls)),
        }
        self.calls = 0
        self.injected = {a: 0 for a in self.ACTIONS}

    def _matches(self, cmd: str) -> bool:
        if self.target == "all":
            return True
        if self.target == "idempotent":
            return cmd in self._idempotent
        if self.target == "mutating":
            return cmd not in self._idempotent
        return cmd in self.target

    def action(self, cmd: str) -> Optional[str]:
        """The fault to inject on this RPC attempt, or None. Counts
        only target-matched attempts; the first scheduled class in
        ACTIONS order wins when several match one index."""
        if not self._matches(cmd):
            return None
        self.calls += 1
        n = self.calls
        for act in self.ACTIONS:
            every, calls = self._sched[act]
            if (every > 0 and n % every == 0) or n in calls:
                self.injected[act] += 1
                return act
        return None


def audit_engine(engine) -> None:
    """Assert page accounting, slot assignment, and block tables are
    mutually consistent — the opt-in post-step invariant check
    (ServingEngine(..., audit=True) or PADDLE_TPU_SERVING_AUDIT=1).

    With the prefix cache enabled, page sharing is refcount-audited: a
    page's refcount must equal the number of sequences mapping it plus
    one if the cache's index holds it, the index must be a bijection,
    and a page may appear at most once within ONE sequence's table
    (cross-sequence sharing is the feature; intra-sequence aliasing is
    always a bug).

    Raises InvariantViolation listing every broken invariant; returns
    None on a clean state. O(pool + batch) host work, no device calls.
    """
    alloc = engine.pool.allocator
    sched = engine.scheduler
    cache = engine.pool.prefix_cache
    problems = []

    # pipelined loop (ISSUE 11): the auditor must hold with ONE launch
    # in flight — map its batch members to their undrained horizon
    # length so the over-provision check can credit their pre-committed
    # pages (and pin that at most one launch is ever outstanding)
    inflight = getattr(engine, "_inflight", None)
    inflight_horizon = ({id(row[0]): inflight.s for row in inflight.batch}
                        if inflight is not None else {})
    # a fused speculative launch (ISSUE 18) pre-commits pages for up to
    # min(s*(k+1), remaining+k) tokens per row — the launch records the
    # exact funded count per request, which overrides the plain-horizon
    # `s` credit below
    inflight_upcoming = (dict(inflight.upcoming)
                         if inflight is not None
                         and getattr(inflight, "upcoming", None) else {})

    # -- allocator self-consistency -------------------------------------
    free_list = list(alloc._free)
    fset, aset = set(free_list), set(alloc._ref)
    if len(free_list) != len(fset):
        problems.append("duplicate pages in the free list")
    if fset & aset:
        problems.append(f"pages both free and allocated: {sorted(fset & aset)}")
    if SCRATCH_PAGE in (fset | aset):
        problems.append("scratch page entered the allocator")
    expected = set(range(1, alloc.num_blocks))
    if (fset | aset) != expected:
        problems.append(
            f"page accounting broken: lost={sorted(expected - fset - aset)} "
            f"foreign={sorted((fset | aset) - expected)}")
    if any(rc < 1 for rc in alloc._ref.values()):
        problems.append("allocated page with refcount < 1")

    # -- ownership: allocated pages == running sequences' pages (counted
    #    with sharing multiplicity) + the prefix cache's registrations ---
    owner_counts: dict = {}
    for req in sched.running:
        if req.kv is None:
            problems.append(f"{req.request_id} RUNNING without kv state")
            continue
        if SCRATCH_PAGE in req.kv.pages:
            problems.append(f"{req.request_id} block table maps the scratch "
                            "page")
        if len(set(req.kv.pages)) != len(req.kv.pages):
            problems.append(f"{req.request_id} maps the same page twice")
        if req.kv.num_tokens > req.num_context:
            problems.append(f"{req.request_id} kv covers {req.kv.num_tokens}"
                            f" tokens > context {req.num_context}")
        if req.phase not in ("prefill", "decode"):
            problems.append(f"{req.request_id} unknown phase {req.phase!r}")
        elif (req.phase == "decode"
                and req.kv.num_tokens < req.num_context - 1):
            # a decode-phase request may lag its context by exactly the
            # token sampled this step (fused ragged steps flip the phase
            # before the first decode), never by more
            problems.append(
                f"{req.request_id} decode-phase but kv covers only "
                f"{req.kv.num_tokens} of {req.num_context} context tokens")
        need = engine.pool.blocks_for_tokens(max(1, req.kv.num_tokens))
        if len(req.kv.pages) < need:
            problems.append(
                f"{req.request_id} under-provisioned: {len(req.kv.pages)} "
                f"pages < {need} needed for {req.kv.num_tokens} tokens")
        if len(req.kv.pages) > engine.max_pages_per_seq:
            problems.append(f"{req.request_id} holds {len(req.kv.pages)} "
                            f"pages > max_pages_per_seq")
        # no over-committed page survives its step (ISSUE 5 + 6):
        # between steps a sequence may hold at most the pages its full
        # context plus one upcoming token needs — a verify span's
        # rejected tail AND a decode horizon's pre-committed pages must
        # both have been reclaimed (truncate / finish-release) by the
        # time the step ends, whether the tokens were rejected, the
        # request stopped mid-horizon, or a NaN cut the horizon short.
        # EXCEPTION (ISSUE 11): a pipelined engine audits with one
        # launch legitimately in flight — its batch members hold pages
        # pre-committed for the whole undrained horizon until the next
        # step's commit replays (and finish-releases / truncates) them
        upcoming = inflight_upcoming.get(
            id(req), 1 + inflight_horizon.get(id(req), 0))
        cap = engine.pool.blocks_for_tokens(req.num_context + upcoming)
        if len(req.kv.pages) > cap:
            problems.append(
                f"{req.request_id} holds {len(req.kv.pages)} pages > "
                f"{cap} needed for context+{upcoming} — speculative/"
                "horizon pages survived rejection")
        for p in req.kv.pages:
            owner_counts[p] = owner_counts.get(p, 0) + 1
    cached = set(cache.pages()) if cache is not None else set()
    oset = set(owner_counts)
    if cache is None and len(owner_counts) != sum(owner_counts.values()):
        dupes = sorted(p for p, c in owner_counts.items() if c > 1)
        problems.append(f"pages owned by two sequences: {dupes}")
    if oset | cached != aset:
        problems.append(
            f"page leak: allocated-but-unowned={sorted(aset - oset - cached)}"
            f" owned-but-not-allocated={sorted((oset | cached) - aset)}")
    for p in aset:
        expected_rc = owner_counts.get(p, 0) + (1 if p in cached else 0)
        if alloc._ref.get(p) != expected_rc:
            problems.append(
                f"page {p} refcount {alloc._ref.get(p)} != "
                f"{owner_counts.get(p, 0)} owners + "
                f"{int(p in cached)} cache refs")

    # -- prefix-cache index consistency ----------------------------------
    if cache is not None:
        if SCRATCH_PAGE in cached:
            problems.append("scratch page registered in the prefix cache")
        if cached & fset:
            problems.append(
                f"cached pages on the free list: {sorted(cached & fset)}")
        index_pages = list(cache._index.values())
        if len(index_pages) != len(set(index_pages)):
            problems.append("prefix-cache index maps two hashes to one page")
        if {cache._index[h] for h in cache._index} != cached or any(
                cache._index.get(cache._page_hash.get(p)) != p
                for p in cached):
            problems.append("prefix-cache hash index and page index disagree")

    # -- quantized pools (ISSUE 9 + 15): an int8 pool's layer tuples
    #    must carry the parallel scale pools — ONE scale per page per
    #    kv-head — and the code pools must actually be int8; an fp8
    #    pool must store float8 pages and carry NO scale rows (fp8
    #    casts are scale-free per element — a scale pool appearing on
    #    an fp8 pool means someone reintroduced the int8 lifecycle); a
    #    "mixed" pool carries the per-page tag plane; an fp32 pool
    #    must carry the plain (k, v) pairs
    pool = engine.pool
    kv_dtype = getattr(pool, "kv_dtype", "fp32")
    # what the pool said it stores (`kv_cache.page_arrays`) is what
    # every layer tuple holds, array for array
    want = pool.page_arrays
    for li, layer in enumerate(pool.page_pools):
        if len(layer) != len(want):
            problems.append(
                f"layer {li} pool tuple has {len(layer)} entries != "
                f"{len(want)} for kv_dtype={kv_dtype}")
            continue
        for (what, shape, dt, _), arr in zip(want, layer):
            full = (pool.num_blocks,) + tuple(shape)
            if tuple(arr.shape) != full or arr.dtype != dt:
                problems.append(
                    f"layer {li} {what}: pool array {tuple(arr.shape)}/"
                    f"{arr.dtype} != {full}/{dt} on a kv_dtype={kv_dtype} "
                    "pool")

    # -- per-request kv-dtype tag bijection (ISSUE 15): every page a
    #    running sequence owns carries exactly its owner's effective
    #    kv_dtype tag, tagged pages are a subset of allocated pages,
    #    the scratch page is never tagged, and on a "mixed" pool the
    #    DEVICE tag planes agree with the host tag map on every
    #    allocated page (and with each other across layers)
    tags = dict(alloc._tags)
    if SCRATCH_PAGE in tags:
        problems.append("scratch page carries a kv-dtype tag")
    stray = sorted(set(tags) - aset)
    if stray:
        problems.append(f"kv-dtype tags on unallocated pages: {stray}")
    for req in sched.running:
        if req.kv is None:
            continue
        want_tag = getattr(req.kv, "kv_tag", None)
        bad = [p for p in req.kv.pages if tags.get(p) != want_tag]
        if want_tag is not None and bad:
            problems.append(
                f"{req.request_id} (kv_tag={want_tag!r}) owns pages "
                f"with mismatched tags: "
                f"{[(p, tags.get(p)) for p in bad[:8]]}")
    if kv_dtype == "mixed" and pool.page_pools:
        planes = [np.asarray(layer[2]) for layer in pool.page_pools]
        if any(not np.array_equal(planes[0], pl) for pl in planes[1:]):
            problems.append("mixed-pool tag planes disagree across layers")
        plane = planes[0]
        for p in sorted(aset):
            want8 = tags.get(p) == "fp8"
            if bool(plane[p]) != want8:
                problems.append(
                    f"page {p} device tag bit {bool(plane[p])} != host "
                    f"tag {tags.get(p)!r}")
                break
        if bool(plane[SCRATCH_PAGE]):
            problems.append("scratch page tagged fp8 on the device plane")

    # -- sharded pools (ISSUE 7): per-shard shapes must agree with the
    #    replicated block tables — every model shard holds EVERY page's
    #    kv-head slice (pages replicated across shards, only kv-heads
    #    split), or a page id in a block table would dangle on some shard.
    #    Int8 scale pools shard along the same kv-head axis (ISSUE 9).
    if getattr(pool, "mesh", None) is not None:
        expect = (pool.num_blocks, pool.block_size,
                  pool.n_kv_heads // pool.tp_size, pool.head_dim)
        s_expect = (pool.num_blocks, pool.n_kv_heads // pool.tp_size)
        for li, layer in enumerate(pool.page_pools):
            named = [("k", layer[0], expect), ("v", layer[1], expect)]
            if len(layer) == 4:
                named += [("k-scale", layer[2], s_expect),
                          ("v-scale", layer[3], s_expect)]
            for nm, arr, want in named:
                shards = getattr(arr, "addressable_shards", None)
                if not shards:
                    problems.append(
                        f"layer {li} {nm}-pool is not a sharded device "
                        "array on a mesh-backed pool")
                    continue
                shapes = {tuple(s.data.shape) for s in shards}
                if shapes != {want}:
                    problems.append(
                        f"layer {li} {nm}-pool per-shard shapes "
                        f"{sorted(shapes)} != {want} — block tables are "
                        "replicated, so every shard must hold all "
                        f"{pool.num_blocks} pages sharded only on the "
                        "kv-head axis")

    # -- packed weights (ISSUE 19): a quantized runner's params dict
    #    must honor the weight-ladder storage contract. int4: every
    #    quantized weight is an int8 packed-code matrix whose companion
    #    "<name>::scale" tensor is fp32 of shape [out, ceil(in/g)]
    #    (in = 2 * packed rows, g = the runner's group size). int8:
    #    the scale is the 1-D per-output-channel vector. fp8: the
    #    weight itself is pinned float8 and carries NO scale entry (a
    #    scale on an fp8 weight means someone reintroduced the int
    #    lifecycle). At tp > 1 the same formula must hold PER SHARD —
    #    column-parallel splits codes and scales on out, row-parallel
    #    splits codes on in and scales on the group axis, and in both
    #    cases scale_shard == (code_out_local, ceil(code_in_local/g)).
    runner = engine.runner
    w_dtype = getattr(runner, "weight_dtype", "fp32")
    qnames = getattr(runner, "_quantized_names", frozenset())
    params = getattr(runner, "params", None)
    if w_dtype != "fp32" and params is not None:
        gs = int(getattr(runner, "weight_group_size", 128))
        suffix = "::scale"
        for name in sorted(qnames):
            w = params.get(name)
            s = params.get(name + suffix)
            if w is None:
                problems.append(f"quantized weight {name} missing from "
                                "params")
                continue
            if w_dtype == "fp8":
                if not str(w.dtype).startswith("float8"):
                    problems.append(
                        f"{name} dtype {w.dtype} is not a float8 type on "
                        "an fp8 runner")
                if s is not None:
                    problems.append(
                        f"{name} carries a scale tensor on an fp8 runner "
                        "— fp8 weights are scale-free casts")
                continue
            if str(w.dtype) != "int8":
                problems.append(f"{name} code dtype {w.dtype} != int8 on "
                                f"a {w_dtype} runner")
            if s is None:
                problems.append(f"{name} has no {suffix} tensor on a "
                                f"{w_dtype} runner")
                continue
            if str(s.dtype) != "float32":
                problems.append(f"{name}{suffix} dtype {s.dtype} != "
                                "float32")
            if w_dtype == "int4":
                k = 2 * int(w.shape[0])
                g = min(gs, k)
                want = (int(w.shape[1]), -(-k // g))
                if tuple(s.shape) != want:
                    problems.append(
                        f"{name}{suffix} shape {tuple(s.shape)} != {want}"
                        f" — one fp32 scale per output channel per "
                        f"{g}-row reduction group")
                shards = getattr(w, "addressable_shards", None)
                s_shards = getattr(s, "addressable_shards", None)
                if shards and s_shards and len(shards) > 1:
                    w_shapes = {tuple(sh.data.shape) for sh in shards}
                    s_shapes = {tuple(sh.data.shape) for sh in s_shards}
                    want_s = {(n_loc, -(-(2 * k2_loc) // g))
                              for k2_loc, n_loc in w_shapes}
                    if s_shapes != want_s:
                        problems.append(
                            f"{name}{suffix} per-shard shapes "
                            f"{sorted(s_shapes)} != {sorted(want_s)} — "
                            "codes and scales must split on the same "
                            "axis (out column-parallel, groups row-"
                            "parallel) or replicate together")
            else:  # int8: 1-D per-output-channel scale
                if s.ndim != 1 or int(s.shape[0]) != int(w.shape[1]):
                    problems.append(
                        f"{name}{suffix} shape {tuple(s.shape)} != "
                        f"({int(w.shape[1])},) — one scale per output "
                        "channel")

    # -- host KV tier (ISSUE 10): every page is device-live XOR host-
    #    resident XOR free. Host-slot accounting mirrors the device
    #    allocator's (free/used partition, single ownership: one
    #    OffloadRecord or the tier's own prefix index per slot), a chain
    #    hash may be indexed on at most ONE tier, and a rotating sample
    #    of spilled slots is content-hash spot-checked so a corrupted
    #    host buffer is caught before it is ever paged back in.
    tier = getattr(engine.pool, "host_tier", None)
    if tier is not None and getattr(tier, "store", None) is not None:
        # cluster-wide store mode (ISSUE 14): slot populations are
        # TIER-WIDE (audit_store checks the partition/refcount/index
        # invariants and runs the rotating CRC spot check); here we
        # check THIS engine's view — every slot an offload record, a
        # pending page-in, or a staged handoff names must carry at
        # least the matching number of this engine's owner refs, and
        # no pending page-in survives the step fence. The per-engine
        # device-XOR-host check is deliberately GONE: the shared index
        # legitimately mirrors device-live hashes (promotion keeps the
        # store copy serving siblings).
        if hasattr(tier, "sync"):
            tier.sync()
        store = tier.store
        need: dict = {}
        for req in sched.waiting:
            off = getattr(req, "offload", None)
            if off is not None:
                if req.phase != "offloaded":
                    problems.append(
                        f"{req.request_id} holds an offload record but "
                        f"phase={req.phase!r}")
                for s in off.slots:
                    need[s] = need.get(s, 0) + 1
            elif req.phase == "offloaded":
                problems.append(f"{req.request_id} phase 'offloaded' "
                                "without an offload record")
        for rid, hrec in getattr(engine, "_handoffs", {}).items():
            if hrec is None:
                continue
            for s in hrec.slots:
                need[s] = need.get(s, 0) + 1
        for req in sched.running:
            if getattr(req, "offload", None) is not None:
                problems.append(f"{req.request_id} RUNNING with an "
                                "offload record")
            if getattr(req, "pending_pagein", None):
                problems.append(f"{req.request_id} pending page-ins "
                                "survived the step fence")
        owner = tier.owner
        for s, cnt in need.items():
            have = store.owner_count(s, owner)
            if have < cnt:
                problems.append(
                    f"store slot {s}: engine {owner!r} references it "
                    f"{cnt}x but holds only {have} store refs")
        # local structural + content audit when the store object is in
        # this process (thread backend / standalone engines); the
        # process backend audits the store router-side
        if getattr(store, "_lock", None) is not None:
            problems.extend(store_audit_problems(
                store, tick=int(engine.metrics.decode_steps.value)))
    elif tier is not None:
        # threaded spill I/O (ISSUE 11): join any in-flight worker
        # copies first — slot contents and content hashes are only
        # defined once the copy lands, and the auditor must never race
        # the worker into a false corruption report
        if hasattr(tier, "sync"):
            tier.sync()
        hfree, hused = list(tier._free), set(tier._hash)
        hfset = set(hfree)
        if len(hfree) != len(hfset):
            problems.append("duplicate slots in the host tier free list")
        if hfset & hused:
            problems.append(
                f"host slots both free and used: {sorted(hfset & hused)}")
        if (hfset | hused) != set(range(tier.max_pages)):
            problems.append(
                "host tier slot accounting broken: "
                f"lost={sorted(set(range(tier.max_pages)) - hfset - hused)}")
        slot_owner: dict = {}
        for req in sched.waiting:
            off = getattr(req, "offload", None)
            if off is not None:
                if req.phase != "offloaded":
                    problems.append(
                        f"{req.request_id} holds an offload record but "
                        f"phase={req.phase!r}")
                for s in off.slots:
                    slot_owner[s] = slot_owner.get(s, 0) + 1
            elif req.phase == "offloaded":
                problems.append(f"{req.request_id} phase 'offloaded' "
                                "without an offload record")
        # handoff buffer (ISSUE 12): a staged request's spilled pages
        # are a third legitimate slot-owner class — owned by the
        # engine's handoff record until extract_handoff ships (and
        # frees) them, or _finish_abnormal releases them on abort
        for rid, rec in getattr(engine, "_handoffs", {}).items():
            if rec is None:
                continue
            for s in rec.slots:
                slot_owner[s] = slot_owner.get(s, 0) + 1
        for req in sched.running:
            if getattr(req, "offload", None) is not None:
                problems.append(f"{req.request_id} RUNNING with an "
                                "offload record")
            if getattr(req, "pending_pagein", None):
                problems.append(f"{req.request_id} pending page-ins "
                                "survived the step fence")
        dupes = sorted(s for s, c in slot_owner.items() if c > 1)
        if dupes:
            problems.append(f"host slots owned by two requests: {dupes}")
        pslots = set(tier._prefix.values())
        if len(pslots) != len(tier._prefix):
            problems.append("host tier prefix index maps two hashes to "
                            "one slot")
        if {s: h for h, s in tier._prefix.items()} != tier._prefix_slot:
            problems.append("host tier prefix index and reverse map "
                            "disagree")
        overlap = set(slot_owner) & pslots
        if overlap:
            problems.append("host slots owned by a request AND the "
                            f"prefix index: {sorted(overlap)}")
        orphans = hused - set(slot_owner) - pslots
        if orphans:
            problems.append(f"host slots used but unowned: "
                            f"{sorted(orphans)}")
        unbacked = (set(slot_owner) | pslots) - hused
        if unbacked:
            problems.append(f"host slots owned but not marked used: "
                            f"{sorted(unbacked)}")
        if cache is not None:
            both = set(cache._index) & set(tier._prefix)
            if both:
                problems.append(f"{len(both)} prefix hashes resident on "
                                "device AND host (XOR violated)")
        sample = sorted(hused)
        if sample:
            # rotating window keyed by the step counter: over a run the
            # spot check sweeps the whole tier, each audit stays O(4)
            start = int(engine.metrics.decode_steps.value) % len(sample)
            for i in range(min(4, len(sample))):
                s = sample[(start + i) % len(sample)]
                if tier.content_hash(s) != tier._hash[s]:
                    problems.append(
                        f"host slot {s} content-hash mismatch — spilled "
                        "bytes corrupted in the host buffer")

    # -- slot accounting -------------------------------------------------
    slots = [r.slot for r in sched.running]
    if any(s is None for s in slots):
        problems.append("RUNNING request without a slot")
    elif len(set(slots)) != len(slots):
        problems.append(f"slot assigned twice: {sorted(slots)}")
    else:
        sset, free_slots = set(slots), list(sched._free_slots)
        if (len(free_slots) != len(set(free_slots))
                or (sset | set(free_slots)) != set(range(sched.max_batch_size))
                or sset & set(free_slots)):
            problems.append(f"slot accounting broken: used={sorted(sset)} "
                            f"free={sorted(free_slots)}")

    # -- waiting requests hold no device resources -----------------------
    for req in sched.waiting:
        if req.kv is not None or req.slot is not None:
            problems.append(f"{req.request_id} WAITING but holds kv/slot")

    if problems:
        raise InvariantViolation("; ".join(problems))


def store_audit_problems(store, live_owners: Optional[set] = None,
                         tick: int = 0, spot_checks: int = 4) -> list:
    """Structural + content invariants of one SharedKVStore (ISSUE 14),
    returned as a problem list (audit_engine folds them in; audit_store
    raises). Checks, all under the store lock where it matters:

      * free/used partition covers exactly range(max_pages), no dupes;
      * prefix index <-> reverse map <-> indexed set are a bijection;
      * every used slot is reachable: owner refs and/or the index ref
        (refcount == live referencing engines + index ref — the
        cross-engine ownership rule); a used slot nobody references is
        a leak, a free slot somebody references is a corruption;
      * with `live_owners`: every owner tag belongs to a live engine
        incarnation or an in-flight transfer ("xfer:*") — a dead
        replica's refs must have been reaped;
      * a rotating `spot_checks`-slot window re-CRCs segment bytes
        against the recorded content hashes, so silent shared-memory
        corruption is caught before any replica serves it.
    """
    problems = []
    with store._lock:
        free = list(store._free)
        fset = set(free)
        owned = {s for s, o in store._owners.items() if o}
        indexed = set(store._indexed)
        used = owned | indexed
        if len(free) != len(fset):
            problems.append("duplicate slots in the store free list")
        if fset & used:
            problems.append(
                f"store slots both free and referenced: "
                f"{sorted(fset & used)}")
        if (fset | used) != set(range(store.max_pages)):
            lost = sorted(set(range(store.max_pages)) - fset - used)
            foreign = sorted((fset | used)
                             - set(range(store.max_pages)))
            problems.append(f"store slot accounting broken: "
                            f"lost={lost} foreign={foreign}")
        stale_hash = sorted(set(store._hash) - used)
        if stale_hash:
            problems.append("store hash bookkeeping survives on "
                            f"unreferenced slots: {stale_hash}")
        if len(set(store._prefix.values())) != len(store._prefix):
            problems.append("store prefix index maps two hashes to one "
                            "slot")
        if {s: h for h, s in store._prefix.items()} != store._prefix_slot:
            problems.append("store prefix index and reverse map disagree")
        if indexed != set(store._prefix.values()):
            problems.append("store indexed-slot set disagrees with the "
                            "prefix index")
        for s, own in store._owners.items():
            if any(c <= 0 for c in own.values()):
                problems.append(f"store slot {s} holds a non-positive "
                                f"owner count: {own}")
        if live_owners is not None:
            legit = set(live_owners)
            for s, own in store._owners.items():
                for o in own:
                    if o not in legit and not str(o).startswith("xfer:"):
                        problems.append(
                            f"store slot {s} referenced by dead/unknown "
                            f"owner {o!r} — reap leaked")
        sample = sorted(s for s in used
                        if store._hash.get(s) is not None)
    if sample:
        start = int(tick) % len(sample)
        for i in range(min(spot_checks, len(sample))):
            s = sample[(start + i) % len(sample)]
            recorded = store.slot_hash(s)
            if recorded is not None and store.content_hash(s) != recorded:
                problems.append(
                    f"store slot {s} content-hash mismatch — segment "
                    "bytes corrupted")
    return problems


def audit_store(store, live_owners: Optional[set] = None,
                tick: int = 0) -> None:
    """Raise InvariantViolation on any broken SharedKVStore invariant
    (see store_audit_problems)."""
    problems = store_audit_problems(store, live_owners, tick)
    if problems:
        raise InvariantViolation("; ".join(problems))


def audit_router(router) -> None:
    """Tier-level invariant auditor (ISSUE 8): every LIVE replica passes
    audit_engine, and the router's at-most-once bookkeeping is
    consistent — each unfinished request is owned by exactly one live
    replica (or by a failed one the supervisor has not yet recovered,
    never by two), no request id is in flight on two live engines at
    once (the double-completion hazard resubmission must never create),
    delivery cursors match the delivered token streams, and the
    prefix-affinity index only names valid replicas. Raises
    InvariantViolation listing every broken invariant."""
    problems = []
    replicas = list(router._replicas)
    for rep in replicas:
        if rep.status != "live":
            continue
        remote = getattr(rep.engine, "remote_audit", None)
        try:
            with rep.lock:
                if remote is not None:
                    # process backend (ISSUE 12): audit_engine runs
                    # INSIDE the replica process — its pool/scheduler
                    # never cross the boundary, only the verdict does
                    p = remote()
                    if p:
                        problems.append(f"replica {rep.index}: {p}")
                else:
                    audit_engine(rep.engine)
        except InvariantViolation as e:
            problems.append(f"replica {rep.index}: {e}")
        except BaseException as e:
            # a replica dying UNDER the audit is a liveness event for
            # the supervisor, not an invariant violation
            logger.warning("replica %d unreachable mid-audit: %s",
                           rep.index, e)

    store = getattr(router, "kv_store", None)
    if store is not None and getattr(store, "_lock", None) is not None:
        # cluster-wide store (ISSUE 14): every live replica's tier
        # joins its pending spill copies, then the store's structural/
        # ownership/content invariants are checked with the LIVE owner
        # set — a dead replica's un-reaped refs are a violation
        live_owners = set()
        for rep in replicas:
            if rep.status != "live":
                continue
            owner = getattr(rep, "store_owner", None)
            if owner:
                live_owners.add(owner)
            t = getattr(getattr(rep.engine, "pool", None), "host_tier",
                        None)
            if t is not None and hasattr(t, "sync"):
                try:
                    with rep.lock:
                        t.sync()
                except BaseException:      # pragma: no cover — dying
                    pass
        problems.extend(store_audit_problems(
            store, live_owners,
            tick=int(router.metrics.requests_completed.value)))

    with router._lock:
        n = len(replicas)
        inflight = {}
        for rep in replicas:
            if rep.status != "live":
                continue
            for rid, req in rep.engine._requests.items():
                if not req.done:
                    if rid in inflight:
                        problems.append(
                            f"request {rid} in flight on replicas "
                            f"{inflight[rid]} AND {rep.index}")
                    inflight[rid] = rep.index
        for rid, rec in router._reqs.items():
            if rec.cursor != len(rec.tokens):
                problems.append(f"request {rid} cursor {rec.cursor} != "
                                f"{len(rec.tokens)} delivered tokens")
            if rec.done:
                continue
            if not 0 <= rec.owner_idx < n:
                problems.append(f"request {rid} owned by replica "
                                f"{rec.owner_idx} out of range")
                continue
            owner = replicas[rec.owner_idx]
            if owner.status == "live":
                if rec.owner_epoch != owner.epoch:
                    problems.append(
                        f"request {rid} owned by stale epoch "
                        f"{rec.owner_epoch} of live replica {rec.owner_idx}"
                        f" (now epoch {owner.epoch})")
                elif rid not in rep_requests(owner):
                    problems.append(
                        f"request {rid} owned by live replica "
                        f"{rec.owner_idx} but unknown to its engine")
        for h, idx in router._affinity.items():
            if not 0 <= idx < n:
                problems.append(f"affinity entry {h} -> replica {idx} "
                                "out of range")

    if problems:
        raise InvariantViolation("; ".join(problems))


def rep_requests(rep) -> frozenset:
    """Request ids a replica's engine knows (finished included)."""
    return frozenset(rep.engine._requests)
