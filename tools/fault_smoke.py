"""Fault-injection smoke driver: run a mixed workload under each fault
class and print the recovery metrics (ISSUE-2 tooling satellite).

Usage (CPU-safe, no TPU needed):

    JAX_PLATFORMS=cpu python tools/fault_smoke.py
    JAX_PLATFORMS=cpu python tools/fault_smoke.py --faults nan,overload \
        --requests 12 --audit

Fault classes:

    none          baseline (also verifies the oracle token equivalence)
    device_error  InjectedDeviceError on 1-in-N decode calls; the engine
                  retries with bounded backoff — tokens must still equal
                  the fault-free oracle
    prefill_error every prefill fails; every request must be quarantined
                  with finish_reason="error" and zero leaks
    nan           NaN logits on selected decode calls under both
                  policies (abort / greedy-fallback)
    stall         a stalled decode step pushes requests past their
                  timeout_s deadline
    overload      2x max_queue_depth arrivals under shed_policy
                  drop_oldest — overload degrades, never thrashes

Exit code 0 iff, for every class: no exception escaped engine.step(),
every request ended with an explicit finish_reason, and the pool/slot
audit came back clean.

ISSUE 3: the workload now runs with the shared-prefix page cache and
chunked prefill enabled by default (--no-prefix-cache / --chunk 0 to
disable) — half the requests share a common header — and the refcounted
invariants are audited after EVERY step via PADDLE_TPU_SERVING_AUDIT.
The leak check releases the cache first: a drained engine plus a cleared
cache must return every page to the free list.

ISSUE 4: the engine additionally runs with fused ragged batching on by
default (--no-ragged-batch to disable): each step's prefill chunks and
decodes ride ONE runner.ragged_step call, which FaultInjector wraps on
the decode op counter — so every fault class also exercises the fused
call site's retry/quarantine. --attn-impl picks the attention path
(default "auto": kernels on TPU, gather oracle on CPU; "ragged" forces
the ragged paged-attention kernel in interpret mode for a CPU-only
kernel-path drill). Records report the attention-bytes counters.

ISSUE 6: `--decode-horizon N` drills all six fault classes with the
device-resident multi-step decode loop on: pure-greedy decode batches
run up to N device steps per host sync (`runner.decode_multi`, wrapped
by FaultInjector on the decode op counter — injected errors hit the
horizon launch, injected NaN drops the packed finiteness flags), and
recovery must stay token-exact with zero leaked pre-committed horizon
pages. Records add host_syncs / host_syncs_per_token /
decode_horizon_steps / horizon_overshoot_tokens. Composes with
--speculate since ISSUE 18: verify spans ride INSIDE the multi-step
scan (`runner.decode_multi_spec`, same decode op counter).

ISSUE 11: `--pipelined` drills every class (plus preempt_storm) with
the ZERO-BUBBLE loop on: host planning runs under the in-flight launch
(one launch outstanding), half the requests sample at temperature 0.8
so seeded horizons ride the decode_multi scan, the on-device stop flag
freezes done rows, and spill I/O is threaded when the host tier is on.
Injected failures now land either at dispatch (retried before the
launch defers) or surface at the deferred drain (pool rollback + sync
rerun) — recovery must stay token-exact against the same oracles, and
the auditor holds with a launch in flight. Records add
planned_ahead_steps.

ISSUE 7: `--tp N` drills all fault classes on a TENSOR-PARALLEL engine:
the runner's weights and the paged K/V pools shard over a (data=1,
model=N) mesh (8-way virtual CPU mesh off-TPU; n_kv_heads must divide
N), the auditor additionally checks per-shard pool shapes against the
replicated block tables after every step, and the none/device_error
classes still require token equality with the naive oracle — injected
sharded-launch errors must retry exactly like single-device ones.
Records add tp / attn bytes, which are counted PER SHARD when tp > 1.

ISSUE 8: `--router N` (N >= 2) switches to the TIER drill: N engine
replicas behind a ServingRouter (prefix-affinity routing, supervisor
attached) run a mixed shared-header workload under the tier fault
classes — none (baseline + oracle equality), replica_kill (one replica
fenced mid-run; the supervisor restores it from its crash-safe snapshot
and redistributes), replica_hang (an injected clock stall trips the
step-progress heartbeat), and tier_shed (per-replica bounded queues
under a 3x burst; a hot replica sheds to siblings, tier overflow drops
oldest). Every class must recover with ZERO lost and ZERO duplicated
requests (token-exact vs the naive oracle where no request was shed),
and the per-replica invariant auditor (audit_router) must come back
green.

ISSUE 9: `--kv-dtype int8 [--weight-dtype int8]` drills every fault
class with QUANTIZED serving on: the paged K/V pools store int8 codes
plus per-page-per-head scale pools (the armed auditor checks the scale
-pool shape invariant — one scale per page per kv-head, sharded like
its pool under --tp), and/or the matmul weights run the weight-only
int8 path. COW forks, prefix-cache adoption, and speculative/horizon
rollback all operate on the quantized pools. The naive oracle cannot
pin token equality here (chunked prefill legitimately changes int8
rounding vs a monolithic prefill), so the none/device_error classes
instead compare against a fault-free TWIN engine with the identical
config — determinism and retry-exactness stay hard-pinned while the
accuracy gate vs fp32 lives in tests/bench. Records add
kv_bytes_reduction_x / sessions_per_pool_x.

ISSUE 10: `--offload [N]` (N defaults to 64 host pages) drills every
fault class with the TIERED KV host offload on: preemption victims
spill their pages to pinned host buffers (phase="offloaded") and
resume by async page-in instead of recompute, prefix-cache evictions
demote to the host index, and the armed auditor additionally checks
host-slot accounting, single ownership, device-XOR-host residency, and
content-hash spot checks of spilled bytes. A seventh class
`preempt_storm` joins the drill: a deliberately tight pool (barely
above one sequence's worth) under a 2x request load churns
preempt/spill/page-in continuously — it must stay token-exact vs the
oracle with zero device OR host leaks. Records add the offload
counters (spill/page-in/hidden-ratio/resumes/fallbacks/drops).

ISSUE 12: `--procs N` (N >= 2) switches to the PROCESS-tier drill: N
replica processes (each a `python -m paddle_tpu.serving.replica`
command loop holding its own Llama runner rebuilt from the shared
seed) behind a process-backend ServingRouter, drilled with REAL
signals — none (baseline + oracle equality), replica_sigkill (SIGKILL
mid-decode; waitpid/socket-EOF detection, respawn + restore +
registry backfill), replica_sigstop (a stopped process trips the
step-progress heartbeat; the fence SIGKILLs the corpse), handoff
(1 prefill + 1 decode replica: KV pages spill, cross the wire
content-hashed, page in on the decode side — token-exact including
the first-token boundary), and handoff_prefill_kill (the prefill
replica dies mid-stream; staged handoffs regenerate from the
registry). Every class must recover with ZERO lost and ZERO
duplicated tokens, token-exact vs the parent process's naive oracle.
`--faults` filters these classes too.

ISSUE 13: `--net [N]` (N replicas, default 2) switches to the TIER
DURABILITY / NETWORK CHAOS drill:

    router_kill    the whole router runs in a CHILD process journaling
                   to a write-ahead JSONL (--net-child is that child's
                   entry); the parent SIGKILLs it mid-stream, then
                   `ServingRouter.recover(journal)` rebuilds the tier —
                   replicas restored from their journaled snapshots,
                   undelivered work resubmitted, re-delivered tokens
                   cursor-deduped — and finishes token-exact vs the
                   oracle with zero lost and zero duplicated tokens.
    frame_corrupt  real replica processes; one client's wire injector
                   first corrupts IDEMPOTENT request frames (the
                   replica CRC-rejects and NAKs, the client retries
                   transparently), then corrupts a STEP frame (fail
                   fast -> ReplicaGoneError -> supervisor respawn).
                   Never a silent mis-parse.
    rpc_delay      gray failure: scheduled delays push idempotent
                   replies past the FAST RPC deadline — the client
                   times out, retries, and seq-discards the late
                   stale replies; the slow-but-alive replica is never
                   fenced and the stream stays token-exact.
    conn_reset     the command connection dies under a step RPC —
                   always fatal, supervisor respawn, token-exact.

All classes must end RECOVERED with zero lost/duplicated tokens.

ISSUE 14: `--shared-kv [N]` (N store pages, default 64) switches to the
CLUSTER-WIDE KV drill: 2 thread replicas share ONE router-owned
content-addressed SharedKVStore (shm-backed for the router_kill class).
Session turns run, the tier rolling-restarts (draining replicas demote
their device prefix caches into the store), and turn 2 resumes through
the store on whichever replica routing picks. Classes:

    none          baseline: token-exact both turns, store hits > 0,
                  tier-wide audit green
    replica_kill  a replica dies with store-resident pages (offload +
                  page-in refs live): supervisor recovery reaps its
                  refs by refcount — INDEX-owned content survives for
                  the siblings, nothing leaks, streams token-exact
    router_kill   the whole router dies mid-stream (workers fenced,
                  journal closed); ServingRouter.recover reattaches
                  the SURVIVING shared-memory segments, revives the
                  journaled store index (each entry CRC-verified
                  against the surviving bytes), and finishes
                  token-exact with the revived pages serving turn 2
    corrupt_slot  a published slot's segment bytes are flipped: the
                  armed rotating CRC spot check must TRIP, scrub()
                  drops the corrupted entry, and the affected session
                  turn recomputes — token-exact, corruption never
                  served

ISSUE 15: `--quant-comm` drills every fault class with BOTH new
quantization rungs armed at once: tensor parallelism at tp=2 (unless
--tp asks for more) with the int8-quantized row-parallel psum
(comm_dtype="int8" — chunked two-level reduce behind the SpecLayout
hook) AND native fp8 KV pages (kv_dtype="fp8", scale-free casts, no
scale pools — the armed auditor asserts their ABSENCE). Both rungs are
batch-shape invariant (per-row chunk scales / per-element casts), so
the none/device_error classes stay TOKEN-EXACT against the engine's
own naive oracle (same quantized runner), and an fp32 twin runner
additionally gates greedy agreement >= 99% — the PR 9 split: exactness
pinned against self, accuracy gated against fp32. `--comm-dtype` /
`--kv-dtype fp8|mixed` are also available individually. Records add
comm_dtype / tp_comm_bytes / tp_comm_bytes_reduction_x /
fp32_greedy_agreement.

ISSUE 5: `--speculate [K]` (K defaults to 4) drills every fault class
with speculative decoding ON: decode rides n-gram verify spans through
the full-logits ragged call — the same decode-op fault schedules now
hit the verify launch — and half the prompts become repetition-heavy
periodic patterns so proposals actually fire. Recovery must stay
token-exact (none/device_error classes still compare against the
naive oracle) and the rejected-tail rollback must leave zero leaked
pages. Records add the proposed/accepted counters and acceptance rate.

ISSUE 18: --speculate now composes with --decode-horizon / --pipelined
— whenever a decode batch has no prefill chunks in flight, verify
spans ride INSIDE the device-resident multi-step scan
(engine._launch_spec_horizon -> runner.decode_multi_spec): accept
/reject happens on device, the corrected token feeds the next scan
step, and ONE packed drain carries up to s*(k+1)-1 tokens per row.
FaultInjector wraps the fused launch on the same decode op counter
(injected NaN zeroes the packed finiteness plane), the armed auditor
bounds page over-provision by the launch's recorded per-row funding,
and drain-failure recovery reruns the horizon synchronously —
token-exactness holds because rejected drafts never change the
emitted stream. `--spec-adaptive-k` arms the per-request EWMA draft
-length controller; `--spec-draft shadow[:int8|int4|fp8|fp32]` swaps
the n-gram proposer for the model-based draft rung (a weight-quantized
shadow of the target proposing via its own paged pool — int4 packs the
shadow to nibbles + group scales, ISSUE 19). The canonical drill:

    JAX_PLATFORMS=cpu python tools/fault_smoke.py --speculate \
        --pipelined --decode-horizon 4 --tp 2

runs all six classes + preempt_storm with fused verify horizons on a
sharded engine. Records add spec_fused_horizons / spec_dead_positions.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAULTS = ("none", "device_error", "prefill_error", "nan", "stall", "overload")


def build_engine(runner, args, **kw):
    from paddle_tpu.serving import ServingEngine

    kw.setdefault("num_blocks", args.num_blocks)
    kw.setdefault("max_batch_size", args.max_batch)
    kw.setdefault("max_model_len", args.max_model_len)
    kw.setdefault("max_step_retries", 2)
    kw.setdefault("retry_backoff_s", 0.001)
    kw.setdefault("audit", True)
    kw.setdefault("enable_prefix_cache", args.prefix_cache)
    kw.setdefault("max_prefill_tokens_per_step", args.chunk or None)
    kw.setdefault("ragged_batch", args.ragged_batch)
    kw.setdefault("num_speculative_tokens", args.speculate)
    kw.setdefault("spec_adaptive_k", getattr(args, "spec_adaptive_k", False))
    kw.setdefault("spec_draft_model", getattr(args, "spec_draft", None))
    kw.setdefault("decode_horizon", args.decode_horizon)
    kw.setdefault("host_tier_pages", args.offload)
    kw.setdefault("host_tier_headroom", args.offload > 0)
    if getattr(args, "pipelined", False):
        # zero-bubble drill (ISSUE 11): plan-under-launch pipelining,
        # temperature>0 horizons, the on-device stop flag, and threaded
        # spill I/O all armed at once — injected failures now land
        # mid-in-flight-launch (dispatch-time) or at the deferred drain
        kw.setdefault("pipelined", True)
        kw.setdefault("horizon_sampling", True)
        kw.setdefault("horizon_early_stop", True)
        kw.setdefault("spill_async", args.offload > 0)
    return ServingEngine(runner, **kw)


def run_class(fault: str, runner, args) -> dict:
    import numpy as np

    from paddle_tpu.serving import FaultInjector, SamplingParams

    timeout_s = None
    engine_kw = {}
    if fault == "device_error":
        target = FaultInjector(runner, error_every=args.error_every,
                               error_target="decode")
    elif fault == "prefill_error":
        target = FaultInjector(runner, error_every=1, error_target="prefill")
    elif fault == "nan":
        target = FaultInjector(runner, nan_every=7, nan_target="decode",
                               nan_fraction=0.5)
        engine_kw["nan_policy"] = "greedy"
    elif fault == "stall":
        # the runner is pre-warmed (the classes share its jit cache), so
        # a healthy decode step is milliseconds; a 1.5s stall blows the
        # 1s deadline for every then-running request
        target = FaultInjector(runner, stall_every=4, stall_target="decode",
                               stall_s=1.5)
        timeout_s = 1.0
    else:
        target = runner
    if fault == "overload":
        engine_kw.update(max_queue_depth=max(2, args.requests // 4),
                         shed_policy="drop_oldest")
    if fault == "preempt_storm":
        # barely more than one sequence's worth of pool (ISSUE 10): the
        # running set churns preempt/spill/page-in on nearly every step
        pages_per_seq = -(-args.max_model_len // args.block_size)
        engine_kw["num_blocks"] = min(args.num_blocks, pages_per_seq + 2)
    eng = build_engine(target, args, **engine_kw)

    rng = np.random.default_rng(0)
    vocab = runner.vocab_size
    n = args.requests * (2 if fault in ("overload", "preempt_storm") else 1)
    # half the workload shares a common header: with the prefix cache on,
    # every fault class also exercises shared-page refcounts + COW paths
    header = list(rng.integers(1, vocab, 9))
    work = []
    for i in range(n):
        plen = int(rng.integers(4, 20))
        if args.speculate and i % 2 == 0:
            # repetition-heavy half (ISSUE 5): a short periodic pattern
            # the n-gram proposer can mine, so the verify path carries
            # real accepted drafts under every fault class
            pattern = list(rng.integers(1, vocab, int(rng.integers(2, 4))))
            prompt = (pattern * (plen // len(pattern) + 1))[:plen]
        else:
            prompt = list(rng.integers(1, vocab, plen))
        if i % 2:
            prompt[:min(len(header), len(prompt) - 1)] = \
                header[:len(prompt) - 1]
        # pipelined drill (ISSUE 11): half the workload samples at
        # temperature > 0 with a fixed seed — those rows now ride
        # device-resident horizons (horizon_sampling) instead of the
        # per-step fallback, and the oracle comparison still holds
        # because the in-scan key schedule IS the naive_generate one
        temp = 0.8 if getattr(args, "pipelined", False) and i % 2 else 0.0
        sp = SamplingParams(max_tokens=int(rng.integers(3, args.max_tokens)),
                            temperature=temp,
                            seed=1000 + i if temp else None,
                            timeout_s=timeout_s)
        work.append((eng.add_request(prompt, sp), prompt, sp))

    crashed = None
    try:
        eng.run()
    except Exception as e:          # must never happen — that's the point
        crashed = f"{type(e).__name__}: {e}"

    outs = eng.outputs()
    reasons = {}
    for o in outs.values():
        reasons[o.finish_reason] = reasons.get(o.finish_reason, 0) + 1
    m = eng.metrics.snapshot()
    eng.release_prefix_cache()      # cached-free pages back to the pool
    leaks_ok = eng.pool.allocator.check_no_leaks()
    slots_ok = sorted(eng.scheduler._free_slots) == list(range(args.max_batch))
    # host tier (ISSUE 10): after the drain, every surviving host slot
    # must belong to the tier's own prefix index (clear() demotions) —
    # an orphan slot is a host-RAM leak
    tier = eng.pool.host_tier
    host_ok = (tier is None
               or set(tier._hash) == set(tier._prefix.values()))

    oracle_ok = True
    accuracy = None
    # int8 KV / int8 weights: chunked prefill legitimately changes the
    # rounding vs a monolithic naive prefill -> twin pin. The ISSUE 15
    # rungs (fp8 KV: per-element casts; int8 psum: per-row chunk
    # scales) are BATCH-SHAPE INVARIANT, so they stay on the naive
    # oracle — token-exact against the engine's own quantized runner
    quantized = (args.kv_dtype == "int8"
                 or args.weight_dtype in ("int8", "int4"))
    if fault in ("none", "device_error", "preempt_storm"):
        if quantized:
            # int8 pools: chunked prefill legitimately changes int8
            # rounding vs the naive monolithic prefill, so the pin is a
            # fault-free TWIN engine with the identical config — exact
            # determinism + retry-exactness, accuracy gate lives in tests
            twin = build_engine(runner, args, **engine_kw)
            twin_ids = {}
            for rid, prompt, sp in work:
                twin_ids[rid] = twin.add_request(prompt, sp)
            twin_outs = twin.run()
            twin.release_prefix_cache()
            for rid, prompt, sp in work:
                if (outs[rid].output_tokens
                        != twin_outs[twin_ids[rid]].output_tokens):
                    oracle_ok = False
                    break
        else:
            # retries are exact: tokens must equal the fault-free oracle
            from paddle_tpu.serving import naive_generate

            for rid, prompt, sp in work:
                ref = naive_generate(runner, prompt, sp,
                                     max_model_len=args.max_model_len)
                o = outs.get(rid)
                if o is None or o.output_tokens != ref:
                    oracle_ok = False
                    break
        twin_fp32 = getattr(args, "fp32_twin_runner", None)
        if twin_fp32 is not None and fault in ("none", "device_error"):
            # ISSUE 15 accuracy gate (the PR 9 split): the quantized
            # rungs are exactness-pinned against the engine's OWN
            # oracle above; greedy agreement vs an fp32 twin runner is
            # gated at >= 99% — quantization noise must not rewrite
            # the streams wholesale
            agree = total = 0
            for rid, prompt, sp in work:
                ref = naive_generate(twin_fp32, prompt, sp,
                                     max_model_len=args.max_model_len)
                got = outs[rid].output_tokens
                total += max(len(ref), len(got))
                agree += sum(int(a == b) for a, b in zip(ref, got))
            accuracy = agree / total if total else 1.0

    ok = (crashed is None and leaks_ok and slots_ok and host_ok
          and oracle_ok and len(outs) == n
          and (accuracy is None or accuracy >= 0.99)
          and all(o.finish_reason for o in outs.values()))
    return {
        "fault": fault, "ok": ok, "requests": n,
        "tp": getattr(runner, "tp_size", 1),
        "host_tier_pages": args.offload,
        "host_slots_leaked": not host_ok,
        "offload_spill_pages": m["offload_spill_pages"],
        "pagein_pages": m["pagein_pages"],
        "pagein_hidden_ratio": m["pagein_hidden_ratio"],
        "offload_resumes": m["offload_resumes"],
        "offload_recompute_fallbacks": m["offload_recompute_fallbacks"],
        "host_tier_drops": m["host_tier_drops"],
        "kv_dtype": args.kv_dtype, "weight_dtype": args.weight_dtype,
        "comm_dtype": getattr(runner, "comm_dtype", "fp32"),
        "kv_bytes_reduction_x": m["kv_bytes_reduction_x"],
        "sessions_per_pool_x": m["sessions_per_pool_x"],
        "tp_comm_bytes": m["tp_comm_bytes"],
        "tp_comm_bytes_reduction_x": m["tp_comm_bytes_reduction_x"],
        "fp32_greedy_agreement": accuracy,
        "finish_reasons": reasons,
        "no_unhandled_exception": crashed is None,
        "crash": crashed,
        "pages_leaked": not leaks_ok, "slots_leaked": not slots_ok,
        "oracle_token_equal": oracle_ok,
        "step_retries": m["step_retries"],
        "requests_timed_out": m["requests_timed_out"],
        "requests_aborted": m["requests_aborted"],
        "nan_logit_events": m["nan_logit_events"],
        "shed_requests": m["shed_requests"],
        "preemptions": m["preemptions"],
        "prefix_hit_tokens": m["prefix_hit_tokens"],
        "prefill_chunks": m["prefill_chunks"],
        "cow_copies": m["cow_copies"],
        "attn_kv_bytes_read": m["attn_kv_bytes_read"],
        "attn_kv_bytes_gather": m["attn_kv_bytes_gather"],
        "spec_proposed_tokens": m["spec_proposed_tokens"],
        "spec_accepted_tokens": m["spec_accepted_tokens"],
        "spec_acceptance_rate": m["spec_acceptance_rate"],
        "spec_fused_horizons": m["spec_fused_horizons"],
        "spec_dead_positions": m["spec_dead_positions"],
        "steps_per_token": m["steps_per_token"],
        "host_syncs": m["host_syncs"],
        "host_syncs_per_token": m["host_syncs_per_token"],
        "decode_horizon_steps": m["decode_horizon_steps"],
        "horizon_overshoot_tokens": m["horizon_overshoot_tokens"],
        "pipelined": getattr(args, "pipelined", False),
        "planned_ahead_steps": m["planned_ahead_steps"],
        "injected": dict(getattr(target, "injected", {})) or None,
    }


ROUTER_FAULTS = ("none", "replica_kill", "replica_hang", "tier_shed")


def run_router_class(fault: str, runner, args) -> dict:
    """One tier-level fault class through a ServingRouter (ISSUE 8)."""
    import time as _time

    import numpy as np

    from paddle_tpu.serving import (
        FaultInjector, SamplingParams, ServingRouter, audit_router,
        naive_generate,
    )

    stalled = []

    def factory(idx):
        # all replicas share ONE warmed runner (the classes reuse its jit
        # cache); replica 0 gets the class's fault wrapper exactly once —
        # the restarted epoch must come back healthy
        if fault == "replica_hang" and idx == 0 and not stalled:
            stalled.append(1)
            return FaultInjector(runner, stall_calls=[4],
                                 stall_target="decode", stall_s=0.8)
        return runner

    router_kw = {}
    if fault == "tier_shed":
        router_kw.update(max_queue_depth=max(2, args.requests // 4),
                         shed_policy="drop_oldest")
    router = ServingRouter(
        factory, replicas=args.router,
        num_blocks=args.num_blocks, max_batch_size=args.max_batch,
        max_model_len=args.max_model_len, max_step_retries=2,
        retry_backoff_s=0.001, audit=True,
        enable_prefix_cache=args.prefix_cache,
        max_prefill_tokens_per_step=args.chunk or None,
        heartbeat_timeout_s=0.25, poll_interval_s=0.05,
        **router_kw)

    rng = np.random.default_rng(0)
    vocab = runner.vocab_size
    n = args.requests * (3 if fault == "tier_shed" else 1)
    header = list(rng.integers(1, vocab, 9))
    work = []
    crashed = None
    try:
        for i in range(n):
            plen = int(rng.integers(4, 20))
            prompt = list(rng.integers(1, vocab, plen))
            if i % 2:
                prompt[:min(len(header), len(prompt) - 1)] = \
                    header[:len(prompt) - 1]
            sp = SamplingParams(
                max_tokens=int(rng.integers(3, args.max_tokens)))
            rid = router.submit(prompt, sp)
            work.append((rid, prompt, sp))
        if fault == "replica_kill":
            # let the tier make some progress first, then fence one
            deadline = _time.monotonic() + 10.0
            while (router.metrics.tokens_delivered.value < n
                    and _time.monotonic() < deadline):
                _time.sleep(0.005)
            router.kill_replica(0)
        outs = router.drain(timeout_s=120.0)
        audit_router(router)
    except Exception as e:      # must never happen — that's the point
        crashed = f"{type(e).__name__}: {e}"
        outs = router.outputs()

    rm = router.metrics.snapshot()
    agg = router.metrics_snapshot()["engines"]
    router.release_prefix_caches()
    leaks_ok = router.check_no_leaks()

    oracle_ok = True
    shed = 0
    for rid, prompt, sp in work:
        o = outs.get(rid)
        if o is None:
            oracle_ok = False
            break
        if o.finish_reason == "shed":
            shed += 1
            continue
        ref = naive_generate(runner, prompt, sp,
                             max_model_len=args.max_model_len)
        if o.output_tokens != ref:
            oracle_ok = False
            break
    router.shutdown()

    ok = (crashed is None and leaks_ok and oracle_ok
          and len(outs) == n
          and all(o.finish_reason for o in outs.values())
          and rm["duplicate_tokens_dropped"] >= 0
          and (fault != "replica_kill" or rm["replica_restarts"] >= 1)
          and (fault != "replica_hang" or rm["replica_hangs"] >= 1)
          and (fault != "tier_shed" or shed > 0))
    return {
        "fault": f"router_{fault}", "ok": ok, "requests": n,
        "replicas": args.router,
        "no_unhandled_exception": crashed is None, "crash": crashed,
        "requests_lost": n - len(outs),
        "requests_shed": shed,
        "pages_leaked": not leaks_ok,
        "oracle_token_equal": oracle_ok,
        "routed_affinity": rm["routed_affinity"],
        "shed_reroutes": rm["shed_reroutes"],
        "tier_overflow": rm["tier_overflow"],
        "replica_crashes": rm["replica_crashes"],
        "replica_hangs": rm["replica_hangs"],
        "replica_restarts": rm["replica_restarts"],
        "resubmitted_requests": rm["resubmitted_requests"],
        "redistributed_requests": rm["redistributed_requests"],
        "duplicate_tokens_dropped": rm["duplicate_tokens_dropped"],
        "prefix_hit_tokens": agg["prefix_hit_tokens"],
        "step_retries": agg["step_retries"],
        "preemptions": agg["preemptions"],
    }


SHARED_KV_FAULTS = ("none", "replica_kill", "router_kill", "corrupt_slot")


def run_shared_kv_class(fault: str, runner, args) -> dict:
    """One cluster-wide-KV fault class (ISSUE 14): 2 thread replicas
    over ONE SharedKVStore, a session workload whose turn-2 resumes
    ride the store across a rolling restart, and a fault injected at
    the store's weakest moment for the class."""
    import tempfile
    import time as _time

    import numpy as np

    from paddle_tpu.serving import (
        SamplingParams, ServingRouter, audit_router, naive_generate,
    )
    from paddle_tpu.serving.resilience import InvariantViolation, audit_store

    rng = np.random.default_rng(0)
    vocab = runner.vocab_size
    jp = (tempfile.mktemp(suffix=".jsonl") if fault == "router_kill"
          else None)
    rkw = dict(replicas=2, num_blocks=args.num_blocks,
               max_batch_size=args.max_batch,
               max_model_len=args.max_model_len, max_step_retries=2,
               retry_backoff_s=0.001, audit=True,
               enable_prefix_cache=True,
               max_prefill_tokens_per_step=args.chunk or None,
               heartbeat_timeout_s=0.25, poll_interval_s=0.05,
               shared_kv_pages=args.shared_kv, snapshot_every_steps=1)
    if jp is not None:
        rkw.update(journal_path=jp, journal_fsync="always",
                   shared_kv_shm=True)
    router = ServingRouter(lambda idx: runner, **rkw)
    header = list(rng.integers(1, vocab, 2 * args.block_size))
    work = []
    t2 = []
    outs1 = {}
    crashed = None
    recovery = {}
    dead_owner = None
    try:
        for i in range(args.requests):
            plen = int(rng.integers(2, 8))
            prompt = header + list(rng.integers(1, vocab, plen))
            sp = SamplingParams(
                max_tokens=int(rng.integers(3, args.max_tokens)),
                session_id=f"s{i}")
            work.append((router.submit(prompt, sp), prompt, sp))
        outs1 = router.drain(timeout_s=120.0)
        audit_router(router)
        # every session's turn-1 KV reaches the store: cycle the tier
        # (draining replicas demote their device caches tier-wide)
        router.rolling_restart()
        store = router.kv_store
        recovery["store_prefix_pages"] = store.prefix_count
        if fault == "corrupt_slot":
            victim = next(iter(store._prefix.values()))
            store.bufs[0][0][victim] += 1.0
            tripped = False
            try:
                audit_store(store)
            except InvariantViolation:
                tripped = True
            recovery["spot_check_tripped"] = tripped
            recovery["scrubbed"] = store.scrub()
        # turn 2: resume through the store on whatever replica routing
        # picks (the corrupted entry, if any, recomputes instead)
        t2 = []
        for i, (rid, p, sp) in enumerate(work):
            p2 = p + outs1[rid].output_tokens
            sp2 = SamplingParams(max_tokens=4, session_id=f"s{i}")
            t2.append((router.submit(p2, sp2), p2, sp2))
        if fault == "replica_kill":
            dead = router._replicas[0]
            dead_owner = dead.store_owner
            router.kill_replica(0)
        elif fault == "router_kill":
            # the router dies mid-turn-2: fence every worker, close
            # the journal, recover from journal + surviving segments
            for rep in router._replicas:
                rep.fenced = True
                rep.stop = True
                rep.wake.set()
            router.supervisor.stop()
            router._journal.close()
            t0 = _time.time()
            rkw2 = {k: v for k, v in rkw.items()
                    if k != "journal_path"}
            router = ServingRouter.recover(lambda idx: runner, jp,
                                           **rkw2)
            recovery["router_recovery_s"] = round(_time.time() - t0, 3)
            recovery["store_index_revived"] = \
                router.kv_store.prefix_count
        outs = router.drain(timeout_s=120.0)
        audit_router(router)
    except Exception as e:      # must never happen — that's the point
        crashed = f"{type(e).__name__}: {e}"
        outs = router.outputs()

    rm = router.metrics.snapshot()
    agg = router.metrics_snapshot()["engines"]
    sstats = (router.kv_store.stats()
              if router.kv_store is not None else {})
    owners = (router.kv_store.owners_snapshot()
              if router.kv_store is not None else {})
    reaped_clean = all(dead_owner not in own for own in owners.values()) \
        if dead_owner else True
    router.release_prefix_caches()
    leaks_ok = router.check_no_leaks()

    oracle_ok = True
    for rid, prompt, sp in work + t2:
        o = outs.get(rid) or outs1.get(rid)
        if o is None or o.output_tokens != naive_generate(
                runner, prompt, sp, max_model_len=args.max_model_len):
            oracle_ok = False
            break
    router.shutdown()
    if jp is not None and os.path.exists(jp):
        os.unlink(jp)

    ok = (crashed is None and leaks_ok and oracle_ok and reaped_clean
          and all(o.finish_reason for o in outs.values())
          and recovery.get("store_prefix_pages", 0) > 0
          and agg["store_hit_pages"] > 0
          and (fault != "replica_kill" or rm["replica_restarts"] >= 1)
          and (fault != "router_kill"
               or recovery.get("store_index_revived", 0) > 0)
          and (fault != "corrupt_slot"
               or (recovery.get("spot_check_tripped")
                   and recovery.get("scrubbed", 0) >= 1)))
    return {
        "fault": f"shared_kv_{fault}", "ok": ok,
        "requests": len(work) + len(t2),
        "no_unhandled_exception": crashed is None, "crash": crashed,
        "oracle_token_equal": oracle_ok,
        "pages_leaked": not leaks_ok,
        "dead_owner_reaped": reaped_clean,
        "store_hit_pages": agg["store_hit_pages"],
        "store_dedup_pages": agg["store_dedup_pages"],
        "handoff_bytes_out": agg["handoff_bytes_out"],
        "rolling_restarts": rm["rolling_restarts"],
        "replica_restarts": rm["replica_restarts"],
        "drain_migrations": rm["drain_migrations"],
        **{k: sstats.get(k, 0.0) for k in
           ("store_published_pages", "store_prefix_hits",
            "store_reaped_slots", "store_evictions")},
        **recovery,
    }


PROC_FAULTS = ("none", "replica_sigkill", "replica_sigstop", "handoff",
               "handoff_prefill_kill")


def run_proc_class(fault: str, runner, args) -> dict:
    """One PROCESS-tier fault class (ISSUE 12): N replica processes
    behind a process-backend ServingRouter, drilled with real signals —
    SIGKILL (waitpid-detected death), SIGSTOP (heartbeat-detected
    hang; the fence SIGKILLs the stopped corpse), and the
    prefill/decode split incl. killing the PREFILL replica mid-stream.
    Every class must drain with zero lost and zero duplicated tokens,
    token-exact vs the parent's naive oracle (the children rebuild
    IDENTICAL weights from the same seed), audit_router green."""
    import os as _os
    import signal
    import time as _time

    import numpy as np

    from paddle_tpu.serving import (
        SamplingParams, ServingRouter, audit_router, naive_generate,
    )

    child_env = dict(_os.environ)
    child_env["JAX_PLATFORMS"] = "cpu"
    for k in ("PJRT_NAMES_AND_LIBRARY_PATHS", "CUSTOM_DEVICE_ROOT"):
        child_env.pop(k, None)
    spec = {"factory": "paddle_tpu.serving.replica:model_runner_factory",
            "factory_kw": {
                "model": "llama", "seed": 0,
                "block_size": args.block_size,
                "max_model_len": args.max_model_len,
                "attn_impl": args.attn_impl,
                "kv_dtype": args.kv_dtype,
                "weight_dtype": args.weight_dtype,
                "vocab_size": 97, "hidden_size": args.hidden,
                "num_layers": args.layers,
                "num_heads": max(2, args.hidden // 16),
                "num_kv_heads": None,
                "max_seq_len": args.max_model_len, "dropout": 0.0}}
    split = fault in ("handoff", "handoff_prefill_kill")
    router = ServingRouter(
        spec, replicas=args.procs, backend="process",
        child_env=child_env, rendezvous_timeout_s=300.0,
        command_timeout_s=300.0,
        prefill_replicas=1 if split else 0,
        host_tier_pages=args.offload or (64 if split else 0),
        num_blocks=args.num_blocks, max_batch_size=args.max_batch,
        max_model_len=args.max_model_len, max_step_retries=2,
        retry_backoff_s=0.001, audit=True,
        enable_prefix_cache=args.prefix_cache,
        max_prefill_tokens_per_step=args.chunk or None,
        snapshot_every_steps=2,
        # the hang drill's heartbeat must outlive a cold child's jit
        # compiles (a first step stuck in XLA is not a hang)
        heartbeat_timeout_s=15.0 if fault == "replica_sigstop" else 600.0,
        poll_interval_s=0.1)

    rng = np.random.default_rng(0)
    vocab = 97
    n = args.requests
    header = list(rng.integers(1, vocab, 9))
    work = []
    crashed = None
    try:
        # warm every replica's jit caches first (fresh processes
        # compile their own) so the signal drills hit STEPS, not
        # compiles — and so the sigstop heartbeat window is honest
        for w in range(2 * args.procs):
            router.submit(list(rng.integers(1, vocab, 8)),
                          SamplingParams(max_tokens=2),
                          request_id=f"warm-{w}")
        router.drain(timeout_s=600.0)
        for i in range(n):
            plen = int(rng.integers(4, 20))
            prompt = list(rng.integers(1, vocab, plen))
            if i % 2:
                prompt[:min(len(header), len(prompt) - 1)] = \
                    header[:len(prompt) - 1]
            sp = SamplingParams(
                max_tokens=int(rng.integers(3, args.max_tokens)),
                temperature=0.7 if i % 4 == 0 else 0.0,
                seed=1000 + i if i % 4 == 0 else None)
            rid = router.submit(prompt, sp)
            work.append((rid, prompt, sp))
        if fault in ("replica_sigkill", "handoff_prefill_kill"):
            deadline = _time.monotonic() + 60.0
            bar = (1 if fault == "handoff_prefill_kill" else n)
            while (router.metrics.tokens_delivered.value < bar
                    and _time.monotonic() < deadline):
                _time.sleep(0.01)
            # replica 0 is the PREFILL replica in the split drill
            _os.kill(router._replicas[0].engine.proc.pid, signal.SIGKILL)
        elif fault == "replica_sigstop":
            deadline = _time.monotonic() + 60.0
            while (router.metrics.tokens_delivered.value < 2
                    and _time.monotonic() < deadline):
                _time.sleep(0.01)
            _os.kill(router._replicas[0].engine.proc.pid, signal.SIGSTOP)
        outs = router.drain(timeout_s=600.0)
        audit_router(router)
    except Exception as e:      # must never happen — that's the point
        crashed = f"{type(e).__name__}: {e}"
        outs = router.outputs()

    rm = router.metrics.snapshot()
    agg = router.metrics_snapshot()["engines"]
    router.release_prefix_caches()
    leaks_ok = router.check_no_leaks()

    oracle_ok = True
    for rid, prompt, sp in work:
        o = outs.get(rid)
        if o is None:
            oracle_ok = False
            break
        ref = naive_generate(runner, prompt, sp,
                             max_model_len=args.max_model_len)
        if o.output_tokens != ref:
            oracle_ok = False
            break
    router.shutdown()

    ok = (crashed is None and leaks_ok and oracle_ok
          and len([r for r in outs if not r.startswith("warm-")]) == n
          and all(o.finish_reason for o in outs.values())
          and (fault not in ("replica_sigkill", "replica_sigstop",
                             "handoff_prefill_kill")
               or rm["replica_restarts"] >= 1)
          and (fault != "replica_sigstop" or rm["replica_hangs"] >= 1)
          and (not split or rm["handoffs"] >= 1))
    return {
        "fault": f"procs_{fault}", "ok": ok, "requests": n,
        "replicas": args.procs, "backend": "process",
        "prefill_replicas": 1 if split else 0,
        "no_unhandled_exception": crashed is None, "crash": crashed,
        "requests_lost": n - len([r for r in outs
                                  if not r.startswith("warm-")]),
        "pages_leaked": not leaks_ok,
        "oracle_token_equal": oracle_ok,
        "replica_crashes": rm["replica_crashes"],
        "replica_hangs": rm["replica_hangs"],
        "replica_restarts": rm["replica_restarts"],
        "resubmitted_requests": rm["resubmitted_requests"],
        "duplicate_tokens_dropped": rm["duplicate_tokens_dropped"],
        "handoffs": rm["handoffs"],
        "handoff_fallbacks": rm["handoff_fallbacks"],
        "handoff_pages_in": agg["handoff_pages_in"],
        "handoff_recompute_fallbacks": agg["handoff_recompute_fallbacks"],
        "pagein_pages": agg["pagein_pages"],
        "step_retries": agg["step_retries"],
    }


NET_FAULTS = ("router_kill", "frame_corrupt", "rpc_delay", "conn_reset")


def _net_workload(args, vocab: int):
    """Deterministic workload shared by the --net parent (oracle side)
    and the --net-child router process (submit side)."""
    import numpy as np

    from paddle_tpu.serving import SamplingParams

    rng = np.random.default_rng(0)
    work = []
    for i in range(args.requests):
        plen = int(rng.integers(4, 20))
        prompt = [int(t) for t in rng.integers(1, vocab, plen)]
        sp = SamplingParams(
            max_tokens=int(rng.integers(4, args.max_tokens)),
            temperature=0.7 if i % 4 == 0 else 0.0,
            seed=1000 + i if i % 4 == 0 else None)
        work.append((f"net-{i}", prompt, sp))
    return work


def _net_router_kw(args) -> dict:
    return dict(num_blocks=args.num_blocks, max_batch_size=args.max_batch,
                max_model_len=args.max_model_len, max_step_retries=2,
                retry_backoff_s=0.001, audit=True,
                enable_prefix_cache=args.prefix_cache,
                max_prefill_tokens_per_step=args.chunk or None,
                snapshot_every_steps=2, poll_interval_s=0.05,
                heartbeat_timeout_s=600.0)


def _run_net_child(args, runner) -> int:
    """--net-child entry: host a journaling thread-backend router in
    THIS process, submit the shared workload, serve until the parent
    SIGKILLs us mid-stream (the whole point — no graceful teardown
    ever runs, the journal is all that survives)."""
    import time as _time

    from paddle_tpu.serving import ServingRouter

    router = ServingRouter(lambda idx: runner, replicas=args.net,
                           journal_path=args.net_child,
                           journal_fsync="interval",
                           **_net_router_kw(args))
    for rid, prompt, sp in _net_workload(args, runner.vocab_size):
        router.submit(prompt, sp, request_id=rid)
    deadline = _time.monotonic() + 600.0
    while router.has_work() and _time.monotonic() < deadline:
        _time.sleep(0.01)
    _time.sleep(60.0)        # hold state; the parent kills us long before
    return 0


def run_net_router_kill(runner, args) -> dict:
    """SIGKILL the ROUTER process mid-stream, then recover the tier
    from its write-ahead journal (ISSUE 13 acceptance)."""
    import os as _os
    import signal
    import subprocess
    import sys as _sys
    import tempfile
    import time as _time

    from paddle_tpu.serving import (
        RouterJournal, ServingRouter, audit_router, naive_generate,
    )

    journal = tempfile.mktemp(prefix="fault_smoke_net_", suffix=".jsonl")
    cmd = [_sys.executable, _os.path.abspath(__file__),
           "--net", str(args.net), "--net-child", journal,
           "--requests", str(args.requests),
           "--num-blocks", str(args.num_blocks),
           "--block-size", str(args.block_size),
           "--max-batch", str(args.max_batch),
           "--max-model-len", str(args.max_model_len),
           "--max-tokens", str(args.max_tokens),
           "--layers", str(args.layers), "--hidden", str(args.hidden),
           "--chunk", str(args.chunk)]
    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(cmd, env=env)
    work = _net_workload(args, runner.vocab_size)
    total = sum(sp.max_tokens for _, _, sp in work)
    bar = max(4, total // 3)
    crashed = None
    delivered_before = 0
    router, outs, recovery_s = None, {}, -1.0
    try:
        # poll the journal until the child has durably delivered a
        # third of the stream, then SIGKILL it mid-flight
        deadline = _time.monotonic() + 300.0
        while _time.monotonic() < deadline and proc.poll() is None:
            try:
                state, _ = RouterJournal.replay(journal)
                delivered_before = sum(len(r["tokens"])
                                       for r in state["reqs"].values())
            except (OSError, ValueError):
                delivered_before = 0
            if delivered_before >= bar:
                break
            _time.sleep(0.02)
        _os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)

        t0 = _time.monotonic()
        router = ServingRouter.recover(
            lambda idx: runner, journal, replicas=args.net,
            **_net_router_kw(args))
        outs = router.drain(timeout_s=300.0)
        recovery_s = _time.monotonic() - t0
        audit_router(router)
    except Exception as e:
        crashed = f"{type(e).__name__}: {e}"

    oracle_ok = True
    if router is not None:
        for rid, prompt, sp in work:
            o = outs.get(rid)
            ref = naive_generate(runner, prompt, sp,
                                 max_model_len=args.max_model_len)
            if o is None or o.output_tokens != ref:
                oracle_ok = False
                break
        rm = router.metrics.snapshot()
        router.release_prefix_caches()
        leaks_ok = router.check_no_leaks()
        router.shutdown()
    else:
        rm, leaks_ok, oracle_ok = {}, False, False
    try:
        _os.unlink(journal)
    except OSError:
        pass
    ok = (crashed is None and oracle_ok and leaks_ok
          and len(outs) == len(work)
          and delivered_before > 0
          and rm.get("recovered_requests", 0) >= 1)
    return {"fault": "net_router_kill", "ok": ok,
            "requests": len(work), "replicas": args.net,
            "no_unhandled_exception": crashed is None, "crash": crashed,
            "requests_lost": len(work) - len(outs),
            "tokens_delivered_before_kill": delivered_before,
            "recovery_s": round(recovery_s, 3),
            "oracle_token_equal": oracle_ok,
            "pages_leaked": not leaks_ok,
            "recovered_requests": rm.get("recovered_requests", 0),
            "duplicate_tokens_dropped":
                rm.get("duplicate_tokens_dropped", 0)}


def run_net_wire_class(fault: str, runner, args) -> dict:
    """One WIRE fault class over real replica processes (ISSUE 13):
    frame_corrupt / rpc_delay / conn_reset through the per-RPC
    deadline + idempotent-retry machinery."""
    import os as _os
    import time as _time

    from paddle_tpu.serving import (
        SamplingParams, ServingRouter, WireFaultInjector, audit_router,
        naive_generate,
    )

    child_env = dict(_os.environ)
    child_env["JAX_PLATFORMS"] = "cpu"
    for k in ("PJRT_NAMES_AND_LIBRARY_PATHS", "CUSTOM_DEVICE_ROOT"):
        child_env.pop(k, None)
    spec = {"factory": "paddle_tpu.serving.replica:model_runner_factory",
            "factory_kw": {
                "model": "llama", "seed": 0,
                "block_size": args.block_size,
                "max_model_len": args.max_model_len,
                "vocab_size": 97, "hidden_size": args.hidden,
                "num_layers": args.layers,
                "num_heads": max(2, args.hidden // 16),
                "num_kv_heads": None,
                "max_seq_len": args.max_model_len, "dropout": 0.0}}
    router = ServingRouter(
        spec, replicas=args.net, backend="process",
        child_env=child_env, rendezvous_timeout_s=300.0,
        command_timeout_s=300.0, rpc_fast_timeout_s=0.5,
        num_blocks=args.num_blocks, max_batch_size=args.max_batch,
        max_model_len=args.max_model_len, max_step_retries=2,
        retry_backoff_s=0.001, audit=True,
        enable_prefix_cache=args.prefix_cache,
        max_prefill_tokens_per_step=args.chunk or None,
        snapshot_every_steps=2, heartbeat_timeout_s=600.0,
        poll_interval_s=0.1)
    client = router._replicas[0].engine

    import numpy as np

    rng = np.random.default_rng(0)
    vocab = 97
    work = []
    crashed = None
    retried_ok = True
    try:
        # warm both children's jit caches so the faults hit steps
        for w in range(2 * args.net):
            router.submit(list(rng.integers(1, vocab, 8)),
                          SamplingParams(max_tokens=2),
                          request_id=f"warm-{w}")
        router.drain(timeout_s=600.0)
        if fault == "frame_corrupt":
            # phase A: corrupt idempotent request frames — the replica
            # NAKs, the client retries TRANSPARENTLY (no restarts)
            client.wire_faults = WireFaultInjector(
                corrupt_every=2, target="idempotent")
            for _ in range(4):
                client.ping()
            retried_ok = (client.rpc_stats["naks"] >= 2
                          and client.rpc_stats["retries"] >= 2
                          and not client.dead)
            # phase B: corrupt a STEP frame — fail fast, supervisor
            client.wire_faults = WireFaultInjector(
                corrupt_calls=[3], target="step")
        elif fault == "rpc_delay":
            client.wire_faults = WireFaultInjector(
                delay_every=3, delay_s=1.0, target="idempotent")
        elif fault == "conn_reset":
            client.wire_faults = WireFaultInjector(
                reset_calls=[4], target="step")
        for i in range(args.requests):
            plen = int(rng.integers(4, 20))
            prompt = list(rng.integers(1, vocab, plen))
            sp = SamplingParams(
                max_tokens=int(rng.integers(3, args.max_tokens)))
            work.append((router.submit(prompt, sp), prompt, sp))
        if fault == "rpc_delay":
            # gray failure needs a caller on the idempotent path: poke
            # the remote metrics while the tier decodes
            for _ in range(9):
                router.metrics_snapshot()
                _time.sleep(0.05)
            retried_ok = (client.rpc_stats["deadline_trips"] >= 1
                          and client.rpc_stats["retries"] >= 1)
        outs = router.drain(timeout_s=600.0)
        audit_router(router)
    except Exception as e:      # must never happen — that's the point
        crashed = f"{type(e).__name__}: {e}"
        outs = router.outputs()

    rm = router.metrics.snapshot()
    stats = dict(client.rpc_stats)
    router.release_prefix_caches()
    leaks_ok = router.check_no_leaks()
    oracle_ok = True
    for rid, prompt, sp in work:
        o = outs.get(rid)
        if o is None or o.output_tokens != naive_generate(
                runner, prompt, sp, max_model_len=args.max_model_len):
            oracle_ok = False
            break
    router.shutdown()

    escalates = fault in ("frame_corrupt", "conn_reset")
    ok = (crashed is None and leaks_ok and oracle_ok and retried_ok
          and all(o.finish_reason for o in outs.values())
          and (not escalates or rm["replica_restarts"] >= 1)
          and (fault != "rpc_delay" or rm["replica_restarts"] == 0))
    return {"fault": f"net_{fault}", "ok": ok, "requests": len(work),
            "replicas": args.net, "backend": "process",
            "no_unhandled_exception": crashed is None, "crash": crashed,
            "requests_lost": len(work) - len([r for r in outs
                                              if not r.startswith("warm")]),
            "oracle_token_equal": oracle_ok,
            "retry_path_exercised": retried_ok,
            "pages_leaked": not leaks_ok,
            "rpc_stats": stats,
            "replica_restarts": rm["replica_restarts"],
            "replica_crashes": rm["replica_crashes"],
            "duplicate_tokens_dropped": rm["duplicate_tokens_dropped"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help=f"comma list from {FAULTS}")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=12)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-model-len", type=int, default=64)
    ap.add_argument("--max-tokens", type=int, default=9)
    ap.add_argument("--error-every", type=int, default=5)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="shared-prefix KV page cache (default: on)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--chunk", type=int, default=16,
                    help="max prefill tokens per step (0 = monolithic)")
    ap.add_argument("--ragged-batch", dest="ragged_batch",
                    action="store_true", default=True,
                    help="fused chunk+decode ragged steps (default: on)")
    ap.add_argument("--no-ragged-batch", dest="ragged_batch",
                    action="store_false")
    ap.add_argument("--speculate", type=int, nargs="?", const=4, default=0,
                    metavar="K",
                    help="speculative decoding with up to K n-gram draft "
                         "tokens per verify span (bare flag: K=4; "
                         "default: off) — half the prompts become "
                         "periodic so proposals fire")
    ap.add_argument("--spec-adaptive-k", action="store_true",
                    help="ISSUE 18: acceptance-rate-adaptive per-request "
                         "draft length (EWMA, clamped to [0, K])")
    ap.add_argument("--spec-draft", default=None,
                    metavar="shadow[:int8|int4|fp8|fp32]",
                    help="ISSUE 18/19: model-based draft rung — replace "
                         "the n-gram proposer with a weight-quantized "
                         "shadow of the target model (default: n-gram)")
    ap.add_argument("--shared-kv", type=int, nargs="?", const=64,
                    default=0, metavar="N",
                    help="ISSUE 14: cluster-wide KV drill — 2 thread "
                         "replicas over ONE shared content-addressed "
                         "store of N pages (default 64); classes none/"
                         "replica_kill/router_kill/corrupt_slot")
    ap.add_argument("--offload", type=int, nargs="?", const=64, default=0,
                    metavar="N",
                    help="tiered KV host offload (ISSUE 10): an N-page "
                         "pinned host tier under the pool (bare flag: "
                         "N=64; default: off) — preemption spills / "
                         "async page-in resume, watermark headroom on, "
                         "and the extra preempt_storm drill class")
    ap.add_argument("--decode-horizon", type=int, default=1, metavar="N",
                    help="multi-step decode: sync with the host every N "
                         "steps on pure-greedy decode batches "
                         "(runner.decode_multi; default 1 = per-step)")
    ap.add_argument("--pipelined", action="store_true",
                    help="zero-bubble drill (ISSUE 11): pipelined "
                         "plan/commit loop + temperature>0 horizons + "
                         "on-device early stop + threaded spill, with "
                         "half the requests sampling at temp=0.8 — "
                         "injected failures land mid-in-flight-launch "
                         "and must recover token-exact; implies "
                         "--decode-horizon 4 when left at 1, and adds "
                         "the preempt_storm class to the default drill")
    ap.add_argument("--procs", type=int, default=0, metavar="N",
                    help="PROCESS tier drill (ISSUE 12): run the "
                         "process fault classes (replica_sigkill / "
                         "replica_sigstop / handoff / "
                         "handoff_prefill_kill) over N engine replica "
                         "PROCESSES behind a process-backend "
                         "ServingRouter — real signals, waitpid "
                         "detection, respawn + restore, and the "
                         "prefill/decode KV handoff")
    ap.add_argument("--net", type=int, nargs="?", const=2, default=0,
                    metavar="N",
                    help="tier durability / network chaos drill "
                         "(ISSUE 13): router_kill (SIGKILL the router "
                         "process mid-stream, recover() from the "
                         "write-ahead journal), frame_corrupt, "
                         "rpc_delay (gray failure) and conn_reset over "
                         "N replicas — all classes must finish "
                         "token-exact with zero lost/dup tokens")
    ap.add_argument("--net-child", default=None, metavar="JOURNAL",
                    help=argparse.SUPPRESS)   # router_kill's child entry
    ap.add_argument("--router", type=int, default=0, metavar="N",
                    help="tier drill (ISSUE 8): run the router fault "
                         "classes (replica_kill / replica_hang / "
                         "tier_shed) over N engine replicas behind a "
                         "ServingRouter + Supervisor instead of the "
                         "single-engine classes")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel degree: shard weights + KV "
                         "pools over a (data=1, model=N) mesh (ISSUE 7; "
                         "default 1 = single-device)")
    ap.add_argument("--attn-impl", default="auto",
                    choices=("auto", "ragged", "reference"),
                    help="attention path (auto: kernels on TPU, gather "
                         "oracle on CPU; ragged: force the ragged "
                         "paged-attention kernel, interpret mode off-TPU)")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=("fp32", "int8", "fp8", "mixed"),
                    help="K/V page pool storage (ISSUE 9/15): int8 codes "
                         "+ per-page-per-head scale pools; fp8 native "
                         "float8_e4m3fn pages (scale-free casts); mixed "
                         "= fp32 storage serving per-request fp8 tenants "
                         "(default fp32)")
    ap.add_argument("--weight-dtype", default="fp32",
                    choices=("fp32", "int8", "int4", "fp8"),
                    help="matmul weight storage (ISSUE 9/19): int8 = "
                         "per-output-channel scales; int4 = packed "
                         "nibble codes + group-wise scales; fp8 = "
                         "native float8 casts — dequant always in the "
                         "matmul epilogue (default fp32)")
    ap.add_argument("--weight-group-size", type=int, default=128,
                    metavar="G",
                    help="int4 reduction rows per group scale "
                         "(ISSUE 19; default 128)")
    ap.add_argument("--comm-dtype", default="fp32",
                    choices=("fp32", "int8"),
                    help="row-parallel allreduce wire precision (ISSUE "
                         "15): int8 = the chunked two-level quantized "
                         "psum behind the SpecLayout hook (needs --tp "
                         ">= 2; default fp32)")
    ap.add_argument("--quant-comm", action="store_true",
                    help="ISSUE 15 drill: arm BOTH new rungs at once — "
                         "tp=2 (unless --tp asks for more) with the "
                         "int8-quantized psum AND fp8 KV pages; "
                         "none/device_error stay token-exact vs the "
                         "engine's own oracle and gate greedy agreement "
                         ">= 99%% vs an fp32 twin runner")
    args = ap.parse_args()

    from paddle_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if args.quant_comm:
        args.tp = max(args.tp, 2)
        args.comm_dtype = "int8"
        if args.kv_dtype == "fp32":
            args.kv_dtype = "fp8"
    if args.comm_dtype != "fp32" and args.tp < 2:
        raise SystemExit("--comm-dtype int8 needs --tp >= 2 (the "
                         "quantized collective replaces the row-parallel "
                         "allreduce, which only exists at tp > 1)")
    if args.pipelined and args.decode_horizon == 1:
        args.decode_horizon = 4     # horizons must actually engage
    # refcounted invariants audited after every step, engine-independent
    os.environ["PADDLE_TPU_SERVING_AUDIT"] = "1"

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=args.hidden,
                      num_layers=args.layers,
                      num_heads=max(2, args.hidden // 16), num_kv_heads=None,
                      max_seq_len=args.max_model_len, dropout=0.0)
    model = Llama(cfg)
    model.eval()
    # one shared runner: the fault classes reuse its jit cache, so only
    # the first class pays compile time (engines/pools stay per-class)
    runner = LlamaRunner(model, block_size=args.block_size,
                         max_model_len=args.max_model_len,
                         attn_impl=args.attn_impl,
                         kv_dtype=args.kv_dtype,
                         weight_dtype=args.weight_dtype,
                         weight_group_size=args.weight_group_size)
    if args.tp > 1:
        from paddle_tpu.parallel.mesh import serving_mesh

        runner.shard(serving_mesh(data=1, model=args.tp),
                     comm_dtype=args.comm_dtype)
    if (args.comm_dtype != "fp32" or args.kv_dtype in ("fp8", "mixed")
            or args.weight_dtype in ("int4", "fp8")):
        # the ISSUE 15/19 accuracy gate's fp32 twin: an UNSHARDED fp32
        # runner of the same weights (the fp32 tp engine is pinned
        # bit-exact to it, so this is the same oracle, compile-cheaper)
        args.fp32_twin_runner = LlamaRunner(
            model, block_size=args.block_size,
            max_model_len=args.max_model_len, attn_impl=args.attn_impl)
    if args.net_child:
        # router_kill's child: host the journaling router until the
        # parent SIGKILLs this process (no warmup detour — the parent
        # polls the journal, not the clock)
        return _run_net_child(args, runner)
    # warm the prefill buckets + decode step so deadline-sensitive classes
    # (stall) measure steps, not compiles
    import numpy as np

    from paddle_tpu.serving import SamplingParams

    warm = build_engine(runner, args)
    wrng = np.random.default_rng(0)
    for _ in range(4):
        warm.add_request(list(wrng.integers(1, 97, int(wrng.integers(4, 20)))),
                         SamplingParams(max_tokens=2))
    warm.run()

    all_ok = True
    if args.net >= 2:
        # ISSUE 13 durability/network-chaos drill (--faults filters:
        # `--net 2 --faults router_kill,rpc_delay`)
        classes = (NET_FAULTS if args.faults == ",".join(FAULTS)
                   else [f for f in args.faults.split(",")
                         if f in NET_FAULTS])
        for fault in classes:
            if fault == "router_kill":
                rec = run_net_router_kill(runner, args)
            else:
                rec = run_net_wire_class(fault, runner, args)
            all_ok &= rec["ok"]
            print(json.dumps(rec))
        print(f"\nfault smoke (net x{args.net}): "
              f"{'ALL RECOVERED' if all_ok else 'FAILURES'}")
        return 0 if all_ok else 1
    if args.procs >= 2:
        # ISSUE 12 process-tier drill: replica processes, real signals
        # (--faults filters here too: `--procs 2 --faults handoff`)
        classes = (PROC_FAULTS if args.faults == ",".join(FAULTS)
                   else [f for f in args.faults.split(",")
                         if f in PROC_FAULTS])
        for fault in classes:
            rec = run_proc_class(fault, runner, args)
            all_ok &= rec["ok"]
            print(json.dumps(rec))
        print(f"\nfault smoke (procs x{args.procs}): "
              f"{'ALL RECOVERED' if all_ok else 'FAILURES'}")
        return 0 if all_ok else 1
    if args.shared_kv:
        # ISSUE 14 cluster-wide KV drill (--faults filters:
        # `--shared-kv --faults router_kill,corrupt_slot`)
        classes = (SHARED_KV_FAULTS if args.faults == ",".join(FAULTS)
                   else [f for f in args.faults.split(",")
                         if f in SHARED_KV_FAULTS])
        for fault in classes:
            rec = run_shared_kv_class(fault, runner, args)
            all_ok &= rec["ok"]
            print(json.dumps(rec))
        print(f"\nfault smoke (shared-kv x{args.shared_kv} pages): "
              f"{'ALL RECOVERED' if all_ok else 'FAILURES'}")
        return 0 if all_ok else 1
    if args.router >= 2:
        # ISSUE 8 tier drill: the router fault classes replace the
        # single-engine ones (the engine classes are the tier's
        # substrate and keep their own default drill)
        for fault in ROUTER_FAULTS:
            rec = run_router_class(fault, runner, args)
            all_ok &= rec["ok"]
            print(json.dumps(rec))
        print(f"\nfault smoke (router x{args.router}): "
              f"{'ALL RECOVERED' if all_ok else 'FAILURES'}")
        return 0 if all_ok else 1
    classes = [f.strip() for f in args.faults.split(",")]
    if (args.offload or args.pipelined) and args.faults == ",".join(FAULTS):
        # the host tier (or the zero-bubble drill) on: the default
        # drill gains the preempt storm class
        classes.append("preempt_storm")
    for fault in classes:
        if fault not in FAULTS + ("preempt_storm",):
            raise SystemExit(f"unknown fault class {fault!r}; "
                             f"choose from {FAULTS + ('preempt_storm',)}")
        rec = run_class(fault, runner, args)
        all_ok &= rec["ok"]
        print(json.dumps(rec))
    print(f"\nfault smoke: {'ALL RECOVERED' if all_ok else 'FAILURES'}")
    return 0 if all_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
