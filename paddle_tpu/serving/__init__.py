"""paddle_tpu.serving — continuous-batching LLM serving engine.

Reference mapping: this subsystem is the TPU-native analogue of the
reference's LLM serving path. What the reference spreads across
`paddle/fluid/inference` (the predictor that executes the network),
`python/paddle/incubate/nn/functional/block_multihead_attention.py` (the
paged block-table KV kernel) and the serving frameworks above them
(PaddleNLP llm predictor / fastdeploy: admission queue, dynamic batch,
cache manager) collapses here into four small modules over the Pallas
paged-decode kernel (`ops/pallas/paged_attention.py`):

  kv_cache.py      page pool + refcounted free-list block allocator +
                   per-sequence block tables (the reference's cache
                   manager), plus the PrefixCache (ISSUE 3): a
                   hash-indexed cache of full immutable KV pages shared
                   across requests with copy-on-write forking and
                   LRU eviction of cached-free pages; plus the
                   HostKVTier (ISSUE 10): pinned host-RAM page buffers
                   under the device pool — preemption spills victims'
                   pages to host (phase="offloaded") and prefix
                   eviction demotes cached pages through evict_hook,
                   so resume and re-match page bytes back in (async
                   device_put ahead of the step, fence at read time)
                   instead of recomputing, with recompute as the
                   always-correct fallback; plus the SharedKVStore
                   (ISSUE 14): ONE router-owned content-addressed host
                   pool per host replacing the private tiers — chain
                   hashes indexed tier-wide with refcounted dual
                   ownership (per-engine owner refs + an index ref),
                   dedup on publish, slot-reference handoffs, dead
                   replicas reaped by refcount, optional shared-memory
                   segments process replicas map directly;
  store_service.py StoreServer (router side) + SharedKVStoreClient
                   (replica-child side): the SharedKVStore's metadata
                   ops over a loopback socket while page BYTES ride the
                   shared-memory segments — the store attach RPC of the
                   process backend (ISSUE 14);
  scheduler.py     FCFS continuous-batching scheduler with prefill/decode
                   phases, chunked prefill under a per-step token budget
                   (max_prefill_tokens_per_step), and youngest-first
                   preemption under pool pressure (recompute-on-resume —
                   mostly prefix-cache hits when the cache is on);
  model_runner.py  the runner chassis: jitted paged prefill/decode step
                   functions adapting a decoder Layer (the
                   fluid/inference role);
  runners/         a module a served configuration, and the table of
                   which runner serves which Layer (`runner_for`);
  engine.py        ServingEngine: per-request sampling params, stop
                   conditions, token streaming, plus `naive_generate`,
                   the sequential oracle continuous batching must match
                   token-for-token; `decode_horizon=s` (ISSUE 6) keeps
                   the greedy sampling loop device-resident for s steps
                   per host sync (runner.decode_multi), draining one
                   packed token buffer per horizon instead of one
                   transfer per token;
  speculate.py     NgramProposer (ISSUE 5): model-free prompt-lookup
                   draft proposals mined from the request's own context
                   (incrementally indexed, ISSUE 18); the engine
                   verifies all k+1 span positions in ONE fused launch
                   and accepts the longest draft prefix the target
                   model reproduces — several tokens per engine step on
                   repetition-heavy workloads, token-exact vs
                   naive_generate by construction. ISSUE 18 moves the
                   verify spans INSIDE the decode_multi scan
                   (runner.decode_multi_spec: accept/reject on device,
                   one drain per horizon, composing with pipelined /
                   horizon_sampling / early stop) and adds the model-
                   based draft rung: DraftModelProposer (a small or
                   int8-shadow runner proposing whole chains) plus
                   AdaptiveK (per-request acceptance-EWMA draft
                   lengths);
  detokenize.py    StreamDetokenizer (ISSUE 5): incremental streaming
                   detokenization over TokenEvents, buffering raw bytes
                   to byte-complete UTF-8 boundaries
                   (engine.stream_text(request_id));
  metrics.py       queue depth, TTFT, tokens/s, pool utilization,
                   preemption counters — plus the failure-side
                   instruments (timeouts, aborts, step retries, NaN
                   events, shed requests);
  resilience.py    the fault story (ISSUE 2): FaultInjector (simulated
                   device errors / NaN logits / clock stalls for tests
                   and drills), the invariant auditor (page + slot +
                   block-table consistency after every step), and the
                   failure vocabulary (InjectedDeviceError,
                   QueueFullError, InvariantViolation). The engine layers
                   per-request deadlines, abort, bounded-queue
                   backpressure, step retries with backoff, and
                   crash-safe snapshot()/restore() on top.

Decode attends through the Pallas kernel on TPU and through the
gather + dense-mask reference path on CPU — the same dual dispatch every
kernel in ops/pallas uses, so the whole engine runs (and is tested)
under JAX_PLATFORMS=cpu.

Tensor-parallel serving (ISSUE 7): `runner.shard(mesh)` over a
`(data, model)` mesh (parallel.mesh.serving_mesh) shards the weights
Megatron-style and the paged K/V pools along the kv-head axis — each
model shard walks its own kv-head slice of the SAME page ids (Pallas
kernels per-shard via shard_map, reference path via GSPMD), while the
allocator, scheduler, block tables, and PrefixCache stay host-side and
replicated. Token streams are identical to the single-device engine;
per-shard pool and attention bytes drop to 1/tp.

Quantized serving (ISSUE 9): `kv_dtype="int8"` on the runner births
int8 K/V page pools plus per-page-per-kv-head scale pools (one layer
tuple `(k, v, k_scale, v_scale)`); every write path quantizes at
append time inside jit and the ragged kernel dequantizes inside its
page walk with the fp32 online softmax kept. `weight_dtype="int8"`
runs the matmuls weight-only int8 (per-output-channel scales, dequant
in the epilogue). The fp32 default stays bit-exact vs naive_generate;
the quantized path is accuracy-gated (top-5 overlap >= 0.99, greedy
agreement >= 99% vs the fp32 oracle — tests/test_serving_quant.py)
and the byte accounting counts code + scale bytes honestly
(`kv_bytes_reduction_x` ~3.9x at block 16 / head_dim 64). ISSUE 19
takes the weight rung to the floor: `weight_dtype="int4"` packs
nibble codes two-per-byte with group-wise fp32 scales along the
reduction dim (`weight_group_size`, `quantization/int4.py`; grouped
dequant fused into the matmul epilogue, ~5.6x resident weight bytes
down with scales counted), `weight_dtype="fp8"` stores scale-free
`float8_e4m3fn` casts, `comm_dtype="int8"` additionally quantizes
the column-parallel logits all-gather (`quantized_allgather`,
`tp_gather_bytes` ~3.7x down), and `spec_draft_model="shadow:int4"`
drafts from a packed-int4 shadow of the target
(tests/test_serving_weight_quant.py).

The serving TIER (ISSUE 8): `router.py` (ServingRouter — N engine
replicas, thread-per-engine, prefix-affinity routing keyed by the
PrefixCache content-hash chain with least-loaded fallback, tier
admission control over the per-engine bounded queues, at-most-once
delivery via per-request cursors + epoch fencing) and `supervisor.py`
(Supervisor — step-progress heartbeats, crash/hang detection,
token-exact restore from the crash-safe snapshot plus registry
backfill, drain/redistribute of the dead replica's queue). Replicas
may each carry their own `(data=1, model=tp)` sub-mesh
(`replica_submeshes`), finally mapping the serving mesh's data axis.

DISAGGREGATED serving (ISSUE 12): `ServingRouter(backend="process")`
makes every replica an OS process — `serving/launch.py`
(ReplicaLauncher + the EngineClient proxy) spawns
`python -m paddle_tpu.serving.replica` children rendezvoused through
the TCPStore barrier and drives each over a length-prefixed socket
protocol (`serving/wire.py`) whose payloads are the engine's existing
snapshot/inject/extract serializations. `prefill_replicas=N` splits
the tier: prefill-role replicas admit + chunk-prefill + sample the
first token, then hand the KV off — pages spill to the HostKVTier,
raw page bytes + scale rows + CRC content hashes cross the wire, the
decode replica verifies-at-receive and resumes through the ordinary
page-in path, token-exact including int8 codes. The Supervisor
recovers dead PROCESSES (waitpid probe, socket-EOF ReplicaGoneError,
SIGSTOP hang fencing) with the same fence/restore/backfill machinery.

TIER DURABILITY (ISSUE 13): `journal.py` gives the router a durable
control plane — an append-only write-ahead JSONL journal (CRC per
line, fsync policy, snapshot compaction) recording the at-most-once
registry, delivery cursors, ownership changes and replica snapshots;
`ServingRouter.recover(factory, journal_path)` rebuilds the whole
tier after a router SIGKILL with zero lost and zero duplicated
tokens. The wire protocol CRC32-checks every frame (corruption is
NAK'd or retried, never mis-parsed), every EngineClient RPC runs
under an explicit per-RPC deadline, and idempotent RPCs retry
transiently (seq-deduped) while mutating ones fail fast to the
supervisor. `router.drain_replica` / `router.rolling_restart` cycle
replicas gracefully — running requests migrate with their KV pages
through the handoff machinery. `resilience.WireFaultInjector` +
`tools/fault_smoke.py --net` drill drop/corrupt/truncate/delay/reset
plus the router-kill recovery end to end.

Entry points: `paddle_tpu.inference.create_serving_engine(model)` /
`create_serving_router(model, replicas=N)` are the bridges from the
Predictor world; `tools/serving_smoke.py` is a runnable demo;
`tools/fault_smoke.py --router N` drills the tier fault classes;
`chip_smoke.py` serves GPT-2 124M on the chip.
"""

from paddle_tpu.serving.detokenize import (  # noqa: F401
    StreamDetokenizer, TokenizerAdapter, complete_utf8_prefix,
)
from paddle_tpu.serving.engine import (  # noqa: F401
    EngineConfig, RequestOutput, ServingEngine, TokenEvent, create_engine,
    greedy_grid, naive_generate, sample_token,
)
from paddle_tpu.serving.kv_cache import (  # noqa: F401
    BlockAllocator, HostKVTier, KVCachePool, OffloadRecord, PrefixCache,
    SCRATCH_PAGE, SequenceKV, SharedKVStore, page_content_hash,
    quantized_page_write,
)
from paddle_tpu.serving.metrics import (  # noqa: F401
    Counter, EngineMetrics, Gauge, Histogram, aggregate_snapshots,
)
from paddle_tpu.serving.model_runner import (  # noqa: F401
    PagedModelRunner, bucket_len, build_runner, runner_for,
)
from paddle_tpu.serving.runners.gpt import GPTRunner  # noqa: F401
from paddle_tpu.serving.runners.llama import LlamaRunner  # noqa: F401
from paddle_tpu.serving.journal import RouterJournal  # noqa: F401
from paddle_tpu.serving.resilience import (  # noqa: F401
    FaultInjector, InjectedDeviceError, InvariantViolation, QueueFullError,
    ReplicaCrashError, ReplicaGoneError, WireFaultInjector, audit_engine,
    audit_router, audit_store,
)
from paddle_tpu.serving.store_service import (  # noqa: F401
    SharedKVStoreClient, StoreServer,
)
from paddle_tpu.serving.wire import (  # noqa: F401
    WireCorruptionError, WireTimeoutError,
)
# process-per-engine replicas (ISSUE 12): the launcher spawns replica
# processes (paddle_tpu/serving/replica.py command loops) rendezvoused
# through the TCPStore barrier; EngineClient is the in-router proxy.
# Imported lazily-by-name here to keep `import paddle_tpu.serving`
# light — launch pulls subprocess/socket plumbing only
from paddle_tpu.serving.launch import (  # noqa: F401
    EngineClient, ReplicaLauncher,
)
from paddle_tpu.serving.router import (  # noqa: F401
    EngineReplica, RouterMetrics, RouterOutput, ServingRouter,
)
from paddle_tpu.serving.scheduler import (  # noqa: F401
    FCFSScheduler, Request, RequestState, SamplingParams,
)
from paddle_tpu.serving.speculate import (  # noqa: F401
    AdaptiveK, DraftModelProposer, NgramProposer, shadow_runner,
)
from paddle_tpu.serving.supervisor import Supervisor  # noqa: F401
# the serving (data, model) mesh builder + spec layout (ISSUE 7) and the
# per-replica sub-mesh splitter (ISSUE 8) live in parallel/ —
# re-exported here because they are the TP/router serving surface
from paddle_tpu.parallel.mesh import (  # noqa: F401
    replica_submeshes, serving_mesh,
)
from paddle_tpu.parallel.compat import SpecLayout  # noqa: F401

__all__ = [
    "AdaptiveK", "DraftModelProposer", "shadow_runner",
    "BlockAllocator", "Counter", "EngineConfig", "EngineMetrics",
    "EngineReplica",
    "FCFSScheduler", "FaultInjector", "GPTRunner", "Gauge", "Histogram",
    "HostKVTier", "InjectedDeviceError", "InvariantViolation",
    "KVCachePool", "LlamaRunner", "NgramProposer", "OffloadRecord",
    "PagedModelRunner", "PrefixCache",
    "EngineClient", "ReplicaLauncher",
    "QueueFullError", "ReplicaCrashError", "ReplicaGoneError",
    "Request", "RequestOutput", "RouterJournal",
    "WireCorruptionError", "WireFaultInjector", "WireTimeoutError",
    "RequestState", "RouterMetrics", "RouterOutput", "SCRATCH_PAGE",
    "SamplingParams", "SequenceKV", "ServingEngine", "ServingRouter",
    "SharedKVStore", "SharedKVStoreClient", "StoreServer",
    "SpecLayout", "StreamDetokenizer", "Supervisor", "TokenEvent",
    "TokenizerAdapter", "audit_engine", "audit_router", "audit_store",
    "aggregate_snapshots", "bucket_len", "build_runner",
    "complete_utf8_prefix",
    "create_engine", "greedy_grid", "naive_generate", "page_content_hash",
    "quantized_page_write", "replica_submeshes", "runner_for",
    "sample_token", "serving_mesh",
]
