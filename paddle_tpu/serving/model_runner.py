"""Model runners: pure paged-KV step functions for the serving engine.

Reference: the reference serving stack splits "model" from "engine" the
same way — fluid/inference executes the network, the serving layer above
owns batching — with block_multihead_attention as the seam. Here a
runner adapts a decoder Layer into jitted step functions over the shared
page pool. This module is the chassis every runner shares
(`PagedModelRunner`) and names no served configuration: those are a
module each under `serving/runners/`, whose table `runner_for` reads.

  prefill(tokens[1, T], table[1, P], real_len, pools) -> (logits[V], pools)
  prefill_chunk(tokens, start_pos, table, pools)      -> (logits[V], pools)
  decode(tokens[B, 1], tables[B, P], pos[B], pools)   -> (logits[B, V], pools)
  decode_multi(tokens[B], tables, pos[B], pools, s)   -> (packed[2, B, s], pools)
  ragged_step(tokens[B, T], tables, start[B], q_lens[B], pools)
                                                      -> (logits[B, V], pools)
  ragged_step(..., full_logits=True)              -> (logits[B, T, V], pools)

`decode_multi` (ISSUE 6 tentpole) is the device-resident sampling loop:
one jitted `lax.scan` over `s` consecutive decode steps that feeds each
step's on-device argmax token straight back as the next input — no host
round-trip between tokens. It returns ONE packed int32 array (row 0 the
[B, s] greedy token buffer, row 1 the per-step all-finite flags), so the
engine drains a horizon with a single device->host transfer instead of
one per token. Block tables are fixed for the whole horizon: the
scheduler pre-commits every page the s steps will write before launch.

Every step writes K/V through the block table and attends through one of
three statically-dispatched paths (`_attn_impl_for`, logged once per
bucket): the ragged paged-attention Pallas kernel (ISSUE 4 — chunked
prefill, GQA, and mixed chunk+decode batches straight off the page pool,
O(live pages) HBM), the specialized single-token paged-decode kernel
(its exact T==1/MHA shape), or the gather + dense-mask reference path
(the CPU oracle; O(table width) HBM per call). `ragged_step` is the
fused call the engine's ragged-batch mode feeds: each batch slot carries
its own query span (decode=1 token, chunk=many, dead slot=0). The
instrumented-pool counters (`attn_kv_bytes_read` / `attn_kv_bytes_gather`)
account the pool bytes each dispatch actually touches vs what the gather
path would have cost — host-side, so the bandwidth win is CPU-countable.
Prefill lengths are padded to shared power-of-2 buckets (`bucket_len`)
so the compile count stays logarithmic; padded positions write to the
scratch page and their logits are never read. Dead decode slots carry
all-scratch tables, so they self-neutralize without a mask.

`prefill_chunk` (ISSUE 3) is the incremental spelling: it computes
context positions [start_pos, start_pos + len(tokens)), attending over
everything already written through the same block table (earlier chunks,
prefix-cache pages) — `prefill` is just the start_pos=0 full-context
case, so both share one jit cache keyed by the chunk-length bucket, and
`start_pos` rides in as a traced scalar (no recompile per offset). The
jit cache logs every compile and can be capped via the
PADDLE_TPU_MAX_JIT_CACHE env var (LRU eviction; 0/unset = unbounded).

Quantized serving (ISSUE 9): `kv_dtype="int8"` stores the paged K/V
pools as int8 codes plus per-page-per-head fp32 scale pools — every
write path (prefill, chunks, decode, the decode_multi scan, ragged/
verify) quantizes at append time inside jit via
`kv_cache.quantized_page_write`, and the attend paths dequantize: the
ragged kernel inside its page walk (scales ride the SMEM scalar
prefetch), the gather reference after its gather. `weight_dtype="int8"`
converts the 2-D matmul weights to int8 codes + per-output-channel
scales at construction; `_mm` dequantizes in the matmul epilogue. Both
default "fp32" — the default runner is bit-identical to pre-ISSUE-9 —
and the quantized paths are accuracy-gated (bounded logit error,
top-k overlap) rather than exactness-pinned. The instrumented byte
counters count the quantized page bytes PLUS scale bytes, so the
fp32-vs-int8 bandwidth claim is measured, not assumed.

Quantized collectives (ISSUE 15): `shard(mesh, comm_dtype="int8")`
swaps the row-parallel allreduce — the fp32 psum GSPMD inserts behind
every o_proj/down_proj — for the chunked two-level quantized reduce
(`quantization/qcomm.py`): per-(row, chunk) fp32 scales agree via
psum-max, int8 codes ride the allreduce, one dequant multiply
recovers the sum. The runner routes exactly the matmuls whose spec is
`SpecLayout.row_parallel` through an explicit shard_map
(`_row_mm`) whose reduce comes from the layout's
`row_parallel_reduce()` hook; `comm_dtype="fp32"` (default) keeps the
GSPMD path untouched and bit-exact. Per-row chunk scales make the
reduce batch-shape invariant, so the engine stays token-exact against
its own oracle; accuracy is gated vs the fp32 TP engine instead (the
PR 9 methodology). `tp_comm_bytes` / `tp_comm_bytes_fp32` count the
wire bytes per shard host-side (scale bytes counted) — the measured
comm reduction, CPU-countable like the attention byte counters.

The fp8 KV rung (ISSUE 15): `kv_dtype="fp8"` stores the paged pools
as native `float8_e4m3fn` — a scale-free per-element cast at append
(no scale pools, no requant-on-grow: simpler than int8), dequantized
by a plain astype inside the ragged kernel's page walk and the gather
reference. `kv_dtype="mixed"` serves MIXED-PRECISION TENANTS from one
pool geometry: fp32 storage plus a per-page tag plane — pages a
request tagged "fp8" (SamplingParams.kv_dtype) are written through
the fp8 round-trip cast, so an fp8 tenant's values are bit-identical
to a native fp8 pool while fp32 tenants stay bit-exact.

`shard(mesh)` (ISSUE 7 tentpole) turns any runner tensor-parallel over
a `(data, model)` jax mesh: weights get the Megatron column/row
PartitionSpecs (`parallel.compat.SpecLayout` — column-wise QKV/up/gate,
row-wise out-proj/down-proj with the allreduce on the row output,
embeddings vocab-sharded), and every jitted step is re-minted with
explicit in/out shardings: params per their specs, the paged K/V pools
split along the KV-HEAD axis (GQA shards naturally — each model shard
walks its own kv-head slice of the SAME page ids through the same
replicated block tables), and host operands replicated. On TPU the
Pallas kernels run per-shard via `shard_map`; on the CPU test mesh the
sharding-annotated gather reference path partitions under GSPMD. The
block tables, allocator, scheduler, and PrefixCache never notice: one
page id means the same page on every shard, so all host-side COW/
refcount/eviction logic is untouched. Sharded runners count the
instrumented-pool bytes PER SHARD (bytes/tp — the acceptance number).
"""

from __future__ import annotations

import logging
import os
from collections import OrderedDict
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

from paddle_tpu import profiler as _prof
from paddle_tpu.models.generation import masked_cache_attention, paged_gather
from paddle_tpu.serving.kv_cache import (
    KV_DTYPES, SCRATCH_PAGE, fp8_page_write, fp8_round, kv_pair_layout,
    quantized_page_write, require_fp8,
)

# params-dict key suffix of a quantized weight's scale tensor (ISSUE 9):
# "layers.0.self_attn.q_proj.weight::scale" — a 1-D [out] per-output-
# channel vector for int8, a 2-D [out, ceil(in/group)] group-scale
# matrix for int4 (ISSUE 19); fp8 weights are scale-free (no entry)
SCALE_SUFFIX = "::scale"

# the weight ladder (ISSUE 9 -> 19): "int8" = per-output-channel scales
# (2x fewer weight bytes), "int4" = packed nibble codes + group-wise
# scales (~8x, group overhead counted), "fp8" = native float8_e4m3fn,
# scale-free like the ISSUE 15 KV rung (4x)
WEIGHT_DTYPES = ("fp32", "int8", "int4", "fp8")


class UnrecoverableStepError(RuntimeError):
    """A step failure that retrying cannot cure. The engine's recovery
    loops treat every other exception as a transient device fault
    (retry, then quarantine a request); these pass through them and
    stop the serve."""


class StepCompileError(UnrecoverableStepError):
    """The first call of a newly built jit-cache entry failed: the step
    could not be traced or compiled for this backend, so every retry and
    every other request would fail the same way."""


class DonatedPoolError(UnrecoverableStepError):
    """A failed launch had already been given the KV pools by donation
    (TPU): their buffers are deleted and no retry can resubmit them."""


def require_retryable(exc: Exception, pools) -> None:
    """The gate every recovery loop passes before it retries a failed
    step with the `pools` it kept: re-raises an UnrecoverableStepError,
    and raises DonatedPoolError if any pool buffer has been deleted."""
    if isinstance(exc, UnrecoverableStepError):
        raise exc
    if any(a.is_deleted() for a in jax.tree_util.tree_leaves(pools)
           if isinstance(a, jax.Array)):
        raise DonatedPoolError(
            "the failed step was given the KV pools by donation; their "
            "buffers are deleted and the step cannot be retried") from exc


def bucket_len(t: int, minimum: int = 8) -> int:
    """Power-of-2 length bucket — the ONE bucket rule every step path
    shares (prefill, chunked prefill, the fused ragged step): compile
    once per bucket, not per length, and never duplicate jit-cache
    entries across paths by rounding differently per call site (the
    PADDLE_TPU_MAX_JIT_CACHE budget counts every entry)."""
    b = minimum
    while b < t:
        b *= 2
    return b


def _shard_mapped_kernel(kernel, shard_ctx, q_spec, rest_specs=()):
    """Wrap a paged-attention Pallas kernel so it runs PER MODEL SHARD
    (ISSUE 7): q and the K/V pools split on their (kv-)head axis, the
    block tables and positions ride replicated — every shard walks the
    SAME page ids over its own kv-head slice, so the kernel body is
    unchanged (GQA's n_rep is shard-invariant because n_heads and
    n_kv_heads divide by tp together). Pallas calls are opaque to GSPMD,
    hence shard_map instead of a sharding annotation — over EVERY mesh
    axis: the chip's compiler refuses a Mosaic kernel while any axis is
    left to GSPMD, so the data axis is manual too and, being unnamed in
    the specs, computes replicated. `rest_specs` give
    explicit specs for leading trailing args (ISSUE 9: the per-page
    scale pools shard on their kv-head axis); unlisted trailing args
    ride replicated."""
    from paddle_tpu.parallel.pipeline import manual_shard_map

    mesh, model_axis = shard_ctx
    pool_spec = P(None, None, model_axis, None)

    def run(q, k_pool, v_pool, tables, pos_q, *rest):
        extra = tuple(rest_specs) + (P(),) * (len(rest) - len(rest_specs))
        return manual_shard_map(
            kernel, mesh=mesh,
            in_specs=(q_spec, pool_spec, pool_spec, P(), P()) + extra,
            out_specs=q_spec,
        )(q, k_pool, v_pool, tables, pos_q, *rest)

    return run
def paged_attend(q, k_new, v_new, layer_pools, tables, write_page,
                 write_off, pos_q, q_len, n_rep: int, impl: str,
                 shard_ctx=None):
    """Write this step's K/V through the block table, then attend.

    q: [B, T, n_h, d]; k_new/v_new: [B, T, n_kv, d]; layer_pools: one
    layer's pool tuple — fp32/fp8 `(k_pool, v_pool)` (fp8 appends are
    a pure cast, ISSUE 15), mixed `(k_pool, v_pool, tag)` (fp32
    storage, fp8-tagged pages written through the fp8 round-trip), or
    int8 `(k_codes, v_codes, k_scale, v_scale)` (ISSUE 9: the write
    path quantizes at append time via `quantized_page_write`, the
    attend paths dequantize with the per-page-per-head scales);
    tables: [B, P];
    write_page/write_off: [B, T] int32; pos_q: [B] context position of q
    row 0; q_len: [B] live rows per span (rows past it are padding).
    impl is the statically-resolved attention path ("reference" |
    "ragged" — PagedModelRunner._attn_impl_for), baked
    per jit entry. shard_ctx = (mesh, model_axis) on a sharded runner
    (ISSUE 7): the kernels then run per-shard via shard_map on each
    shard's kv-head slice; the gather reference path needs no wrapper —
    GSPMD partitions it from the pool sharding alone. Returns
    ([B, T, n_h*d], new_layer_pools). A runner whose pages hold
    something else than a (k, v) pair writes and attends them itself."""
    quantized = len(layer_pools) == 4
    mixed = len(layer_pools) == 3
    if quantized:
        k_pool, v_pool, k_scale, v_scale = layer_pools
        k_pool, k_scale = quantized_page_write(k_pool, k_scale, write_page,
                                               write_off, k_new)
        v_pool, v_scale = quantized_page_write(v_pool, v_scale, write_page,
                                               write_off, v_new)
        out_pools = (k_pool, v_pool, k_scale, v_scale)
    elif mixed:
        # mixed-precision tenants (ISSUE 15): fp32 storage + per-page
        # tag plane — rows landing on fp8-tagged pages are written
        # through the fp8 round-trip cast (exactly the value a native
        # fp8 pool would dequantize); untagged pages take the verbatim
        # fp32 write, so fp32 tenants stay bit-exact
        k_pool, v_pool, tag = layer_pools
        is8 = tag[write_page][..., None, None]              # [B, T, 1, 1]
        k_pool = k_pool.at[write_page, write_off].set(
            jnp.where(is8, fp8_round(k_new), k_new))
        v_pool = v_pool.at[write_page, write_off].set(
            jnp.where(is8, fp8_round(v_new), v_new))
        out_pools = (k_pool, v_pool, tag)
    elif k_new.dtype != layer_pools[0].dtype:
        # native fp8 pools (ISSUE 15): append is a pure per-element
        # cast — no scales, no requant-on-grow
        k_pool, v_pool = layer_pools
        k_pool = fp8_page_write(k_pool, write_page, write_off, k_new)
        v_pool = fp8_page_write(v_pool, write_page, write_off, v_new)
        out_pools = (k_pool, v_pool)
    else:
        k_pool, v_pool = layer_pools
        k_pool = k_pool.at[write_page, write_off].set(k_new)
        v_pool = v_pool.at[write_page, write_off].set(v_new)
        out_pools = (k_pool, v_pool)
    B, T = q.shape[0], q.shape[1]
    if impl == "ragged":
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention

        if quantized:
            def fn(q_, kp, vp, t, p, ql, ks, vs):
                return ragged_paged_attention(q_, kp, vp, t, p, ql,
                                              k_scale=ks, v_scale=vs)

            if shard_ctx is not None:
                sc = P(None, shard_ctx[1])     # scale rows: heads sharded
                fn = _shard_mapped_kernel(
                    fn, shard_ctx, P(None, None, shard_ctx[1], None),
                    rest_specs=(P(), sc, sc))
            out = fn(q, k_pool, v_pool, tables, pos_q, q_len,
                     k_scale, v_scale)
            return out.reshape(B, T, -1), out_pools
        fn = ragged_paged_attention
        if shard_ctx is not None:
            fn = _shard_mapped_kernel(fn, shard_ctx,
                                      P(None, None, shard_ctx[1], None))
        out = fn(q, k_pool, v_pool, tables, pos_q, q_len)
        return out.reshape(B, T, -1), out_pools
    kg = paged_gather(k_pool, tables)
    vg = paged_gather(v_pool, tables)
    if quantized:
        # dequantize the gathered codes with their page/head scales —
        # the CPU oracle path reads the same int8 domain the kernel does
        ps = k_pool.shape[1]
        ks = jnp.repeat(k_scale[tables], ps, axis=1)    # [B, L, n_kv]
        vs = jnp.repeat(v_scale[tables], ps, axis=1)
        kg = kg.astype(jnp.float32) * ks[..., None]
        vg = vg.astype(jnp.float32) * vs[..., None]
    if n_rep > 1:  # GQA: repeat kv groups up to the query heads
        kg = jnp.repeat(kg, n_rep, axis=2)
        vg = jnp.repeat(vg, n_rep, axis=2)
    out = masked_cache_attention(q, kg, vg, pos_q)
    return out, out_pools


class PagedModelRunner:
    """Shared runner chassis: write-index math, jit caching, dispatch.

    Subclasses set the architecture fields in __init__ and implement
    `_forward(params, tokens, positions, write_page, write_off, tables,
    pos_q, pools) -> (logits[B, T, V], pools)`. Their constructors take
    `(model, block_size, max_model_len, attn_impl)` and hand `kv_dtype`,
    `weight_dtype` and `weight_group_size` through to this one, which
    owns their defaults and their checks.
    """

    num_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int

    ATTN_IMPLS = ("auto", "ragged", "reference")

    # what a single-pass step counts on the device, by name: such a
    # runner's `_forward` returns `(logits, pools, counts[len(COUNTS)])`
    COUNTS = ()
    # the host-side counters this runner keeps as attributes under these
    # names (`_account_attn`, `_account_blocks`, `_account_comm`), which an
    # engine mirrors into gauges of the same names at each step's end.
    # KV-pool bytes the chosen attention path touched against what the
    # gather reference would have read for the same calls (PER SHARD on a
    # sharded runner: each shard walks only its own kv-head slice); blocks
    # of pages the ragged kernel's few-rows walks folded (one layer's walk
    # a launch: every layer walks the same) and those of them folded in
    # full, as a walk's edge blocks are; wire bytes PER SHARD the
    # row-parallel allreduces and the column-parallel all-gathers moved at
    # the configured comm dtype (scale bytes counted) against what fp32
    # collectives would have moved for the same calls
    GAUGES = ("attn_kv_bytes_read", "attn_kv_bytes_gather",
              "ragged_blocks", "ragged_edge_blocks",
              "tp_comm_bytes", "tp_comm_bytes_fp32",
              "tp_gather_bytes", "tp_gather_bytes_fp32")
    # True: `_forward` takes `head_rows` [B] and returns logits [B, 1, V]
    # at those rows only, so the steps that want a span's last row never
    # make [B, T, V] (a 16 k prefill bucket times a vocabulary)
    HEAD_ROWS = False
    # True: a (k, v) page is kept as its [block_size * heads, head_dim]
    # rows (`kv_cache.page_arrays`)
    ROW_PAGES = False
    # steps of a subclass's own beside the chassis's, through the same jit
    # cache: kind -> (method, position of the donated pools or None,
    # static positions)
    EXTRA_STEPS: dict = {}

    def __init__(self, params: Dict[str, jnp.ndarray], block_size: int,
                 max_model_len: int, attn_impl: str = "auto",
                 kv_dtype: str = "fp32", weight_dtype: str = "fp32",
                 weight_group_size: int = 128):
        self.params = params
        self.block_size = block_size
        self.max_model_len = max_model_len
        if attn_impl not in self.ATTN_IMPLS:
            raise ValueError(f"attn_impl={attn_impl!r}; expected one of "
                             f"{self.ATTN_IMPLS}")
        self.attn_impl = attn_impl
        # quantized serving knobs (ISSUE 9): kv_dtype="int8" makes the
        # engine build int8 page pools + per-page-per-head scale pools
        # (this runner quantizes at append time and dequantizes in the
        # attend paths); weight_dtype walks the weight ladder (ISSUE 19)
        # — "int8" per-output-channel scales, "int4" packed nibble codes
        # + one scale per group of reduction rows, "fp8" native
        # float8_e4m3fn, scale-free. Subclasses
        # call _quantize_weights at construction. Both knobs default to
        # "fp32", which is bit-identical to the pre-ISSUE-9 runner.
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype={kv_dtype!r}; expected one of "
                             f"{KV_DTYPES}")
        if kv_dtype in ("fp8", "mixed"):
            # loud at construction, never a silent fallback (ISSUE 15)
            require_fp8(f"PagedModelRunner(kv_dtype={kv_dtype!r})")
        if weight_dtype not in WEIGHT_DTYPES:
            raise ValueError(f"weight_dtype={weight_dtype!r}; expected one "
                             f"of {WEIGHT_DTYPES}")
        if weight_dtype == "fp8":
            require_fp8(f"PagedModelRunner(weight_dtype={weight_dtype!r})")
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        self.weight_group_size = group = int(weight_group_size)
        if group < 1:
            raise ValueError(f"weight_group_size must be >= 1, got {group}")
        # the params _quantize_weights converted (codes under the weight
        # name, scales under name+SCALE_SUFFIX) — the weight_bytes()
        # accounting's map back to logical fp32 shapes
        self._quantized_names: frozenset = frozenset()
        self._jit_cache: "OrderedDict" = OrderedDict()
        self._impl_logged: set = set()
        # tensor-parallel state (ISSUE 7): set by shard(); mesh=None is
        # the single-device runner all earlier PRs built
        self.mesh = None
        self.data_axis = "data"
        self.model_axis = "model"
        self.tp_size = 1
        self._layout = None                  # parallel.compat.SpecLayout
        self._param_shardings = None         # name -> NamedSharding
        # quantized collectives (ISSUE 15): set by shard(comm_dtype=);
        # "fp32" keeps the GSPMD-inserted psum (bit-exact default),
        # "int8" routes the row-parallel matmuls through _row_mm's
        # explicit shard_map + quantized reduce. _row_names are the
        # params whose FINAL spec is row-parallel; _row_out_dims their
        # output widths (the comm byte accounting's operand shapes)
        self.comm_dtype = "fp32"
        self._row_names: frozenset = frozenset()
        self._row_out_dims: tuple = ()
        # the gather direction (ISSUE 19): column-parallel weights whose
        # output is consumed REPLICATED (the lm_head's logits) — with a
        # quantized comm_dtype these route through _col_mm's explicit
        # shard_map + layout.column_parallel_gather(). _gather_out_dims
        # are their per-shard output widths (the gather wire operands)
        self._gather_names: frozenset = frozenset()
        self._gather_out_dims: tuple = ()
        self.reset_attn_counters()          # `GAUGES`, all from zero
        self._fold_pages = {}       # span bucket -> _fold_block_pages
        # where a single-pass step's counts go (`COUNTS`): the engine
        # sets this to collect them for its drain; None drops them
        self.on_step_counts = None

    def page_layout(self):
        """What a layer's page holds, for KVCachePool: `[(trailing
        shape, dtype), ...]`, one entry per array. Here the (k, v) pair
        of [n_kv_heads, head_dim]; a runner with another cache names its
        own arrays."""
        return kv_pair_layout(self.n_kv_heads, self.head_dim, self.dtype)

    def state_layout(self):
        """None: every layer keeps pages. A runner whose layers keep a
        fixed recurrent state per sequence answers `(layers, [(trailing
        shape, dtype), ...])`: that many of its `num_layers` keep, in
        place of pages, one row of each array at the sequence's decode
        slot (KVCachePool makes them; `pools` is then the pair (pages,
        states)). A decode step's row IS its slot; a prefill names its
        slot (`prefill_chunk(..., slot=)`) and starts from zeros where it
        starts at position 0, so a slot is reset by the program that
        first writes it."""
        return None

    def _hand_over(self, counts) -> None:
        """A launch's counts, an output of its program and still on the
        device, to whoever asked for them (`on_step_counts`): THE site
        they leave a runner by."""
        if self.on_step_counts is not None:
            self.on_step_counts(counts)

    def _emit(self, out):
        """A single-pass step's outputs as its public entry returns
        them, `(logits, pools)`. A runner that counts (`COUNTS`) has its
        `_forward` return `(logits, pools, counts)`, the steps pass the
        third on as an output of the program, and it is handed over
        from here."""
        logits, pools, *counts = out
        if counts:
            self._hand_over(counts[0])
        return logits, pools

    @property
    def dtype(self):
        """The runner's COMPUTE dtype: the first floating param (int8
        weight codes are storage, not the serving precision)."""
        for v in self.params.values():
            if jnp.issubdtype(v.dtype, jnp.floating):
                return v.dtype
        return next(iter(self.params.values())).dtype

    @property
    def n_rep(self) -> int:
        return self.n_heads // self.n_kv_heads

    def recipe(self) -> dict:
        """What `build_runner` was asked for, as this runner has it: the
        half of an engine snapshot's "config" that the runner owns (the
        engine's half is its `EngineConfig`). `restore_serving_engine`
        builds the next runner from these keys."""
        return {"block_size": self.block_size,
                "max_model_len": self.max_model_len,
                "kv_dtype": self.kv_dtype,
                "weight_dtype": self.weight_dtype,
                "weight_group_size": self.weight_group_size,
                "comm_dtype": self.comm_dtype}

    # --------------------------------- the weight ladder (ISSUE 9 / 19)

    def _quantize_weights(self, names) -> None:
        """Convert the named 2-D [in, out] matmul weights to this
        runner's weight_dtype rung (ISSUE 19): "int8" = int8 codes +
        per-output-channel fp32 scale vectors (the established
        quantization/int8.py abs-max scheme), "int4" = packed nibble
        codes + group-wise scales ([out, ceil(in/group)] — see
        quantization/int4.py's layout contract), "fp8" = a scale-free
        float8_e4m3fn cast. Scales land as `name + "::scale"` params;
        the matmul epilogue dequant lives in `_mm`. Norms, biases, and
        embeddings stay floating — only the HBM-heavy matrices shrink."""
        if self.weight_dtype == "int4":
            from paddle_tpu.quantization.int4 import int4_quantize

            group = self.weight_group_size
            for name in names:
                qw, scale = int4_quantize(self.params[name], group)
                self.params[name] = qw
                self.params[name + SCALE_SUFFIX] = scale
            logger.info("serving weights quantized int4: %d matrices "
                        "(packed nibbles, group scales, group=%d)",
                        len(names), group)
        elif self.weight_dtype == "fp8":
            for name in names:
                self.params[name] = self.params[name].astype(
                    jnp.float8_e4m3fn)
            logger.info("serving weights cast fp8: %d matrices "
                        "(float8_e4m3fn, scale-free)", len(names))
        else:
            from paddle_tpu.quantization.int8 import _weight_quantize

            for name in names:
                w = self.params[name]
                qw, scale = _weight_quantize(w)
                self.params[name] = qw
                self.params[name + SCALE_SUFFIX] = scale.astype(jnp.float32)
            logger.info("serving weights quantized int8: %d matrices "
                        "(per-output-channel scales)", len(names))
        self._quantized_names = frozenset(names)

    def _mm(self, params, name, x):
        """Matmul against a possibly-quantized weight: fp32 weights take
        the exact pre-ISSUE-9 `x @ w` (bit-identical default path);
        quantized weights dequantize in the matmul epilogue — the codes
        are what HBM reads. int8: the per-output-channel scale (1-D)
        multiplies the dot output (exactly `x @ (qw * scale)` by column
        linearity). int4 (ISSUE 19): the 2-D group-scale matrix rides
        quantization/int4.py's grouped epilogue (scale per reduction
        group BEFORE the group-sum — exact by the same linearity).
        fp8: a scale-free cast into the dot. With a quantized
        comm_dtype (ISSUE 15/19), row-parallel weights route through
        _row_mm's explicit shard_map + quantized reduce and the
        replicated-output column weights (lm_head) through _col_mm's
        quantized gather; everything else (and the whole fp32-comm
        default) keeps the GSPMD path verbatim."""
        if self.comm_dtype != "fp32":
            if name in self._row_names:
                return self._row_mm(params, name, x)
            if name in self._gather_names:
                return self._col_mm(params, name, x)
        w = params[name]
        s = params.get(name + SCALE_SUFFIX)
        if s is None:
            if str(w.dtype).startswith("float8"):
                return x @ w.astype(x.dtype)
            return x @ w
        if s.ndim == 2:
            return self._int4_mm(x, w, s)
        return (x @ w.astype(x.dtype)) * s.astype(x.dtype)

    def _int4_mm(self, x, w, s):
        """x @ (packed int4 codes `w` with group scales `s`): the grouped
        epilogue at this runner's group size, whole or one shard's part."""
        from paddle_tpu.quantization.int4 import int4_matmul

        return int4_matmul(x, w, s, self.weight_group_size)

    def _row_mm(self, params, name, x):
        """Row-parallel matmul with an EXPLICIT collective (ISSUE 15):
        each model shard computes its partial product from its input
        slice, then the layout's `row_parallel_reduce()` hook sums the
        partials — `quantized_psum` at comm_dtype="int8" (per-row
        chunked scales via pmax + int8 code psum + dequant). Runs as a
        shard_map over the model axis because the collective must be
        explicit to be quantized (GSPMD would insert its own fp32
        psum). The weight ladder composes: int8's per-output-channel
        scale is replicated on row-parallel weights and multiplies
        AFTER the reduce (exact by linearity for psum; the honest
        dequant point for the quantized reduce); int4's group scales
        shard WITH the reduction dim (each shard owns whole groups —
        shard() enforces the alignment) so the grouped epilogue runs
        in-shard BEFORE the reduce; fp8 weights cast in-shard."""
        from paddle_tpu.parallel.pipeline import manual_shard_map

        axis = self.model_axis
        reduce_fn = self._layout.row_parallel_reduce()
        w = params[name]
        s = params.get(name + SCALE_SUFFIX)
        x_spec = P(*((None,) * (x.ndim - 1) + (axis,)))
        if s is not None and s.ndim == 2:
            def f4(x_local, w_local, s_local):
                return reduce_fn(self._int4_mm(x_local, w_local, s_local),
                                 axis)

            return manual_shard_map(
                f4, mesh=self.mesh,
                in_specs=(x_spec, P(axis, None), P(None, axis)),
                out_specs=P(), axis_names=frozenset({axis}))(x, w, s)

        def f(x_local, w_local):
            part = x_local @ w_local.astype(x_local.dtype)
            return reduce_fn(part, axis)

        out = manual_shard_map(
            f, mesh=self.mesh, in_specs=(x_spec, P(axis, None)),
            out_specs=P(), axis_names=frozenset({axis}))(x, w)
        if s is not None:
            out = out * s.astype(x.dtype)
        return out

    def _col_mm(self, params, name, x):
        """Column-parallel matmul whose output is consumed REPLICATED —
        the lm_head's logits (ISSUE 19) — with an EXPLICIT gather: each
        model shard computes its own output-column slice (weight-ladder
        epilogue included, since scales shard with the columns), then
        the layout's `column_parallel_gather()` hook assembles the full
        width — `quantized_allgather` at comm_dtype="int8" (pmax-shared
        per-row chunk scales, int8 codes gathered wide, one dequant).
        Explicit shard_map for the same reason as _row_mm: GSPMD would
        insert its own fp32 all-gather. x rides in replicated (the
        column-parallel input contract)."""
        from paddle_tpu.parallel.pipeline import manual_shard_map

        axis = self.model_axis
        gather_fn = self._layout.column_parallel_gather()
        w = params[name]
        s = params.get(name + SCALE_SUFFIX)
        w_spec = P(None, axis)
        if s is None:
            def f(x_local, w_local):
                part = x_local @ w_local.astype(x_local.dtype)
                return gather_fn(part, axis)

            return manual_shard_map(
                f, mesh=self.mesh, in_specs=(P(), w_spec),
                out_specs=P(), axis_names=frozenset({axis}))(x, w)
        if s.ndim == 2:
            def f4(x_local, w_local, s_local):
                return gather_fn(self._int4_mm(x_local, w_local, s_local),
                                 axis)

            return manual_shard_map(
                f4, mesh=self.mesh, in_specs=(P(), w_spec, P(axis, None)),
                out_specs=P(), axis_names=frozenset({axis}))(x, w, s)

        def f8(x_local, w_local, s_local):
            part = (x_local @ w_local.astype(x_local.dtype)
                    ) * s_local.astype(x_local.dtype)
            return gather_fn(part, axis)

        return manual_shard_map(
            f8, mesh=self.mesh, in_specs=(P(), w_spec, P(axis)),
            out_specs=P(), axis_names=frozenset({axis}))(x, w, s)

    # --------------------------------------------------- sharding (ISSUE 7)

    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    def _param_specs(self, layout) -> Dict[str, P]:
        """name -> PartitionSpec table for this architecture (subclass
        hook; unlisted params ride replicated)."""
        raise NotImplementedError

    @staticmethod
    def _spec_fits(shape, spec, mesh) -> bool:
        """A spec fits iff every sharded dim divides evenly across its
        mesh axes — the clean-split precondition the fallback leans on."""
        for dim, axes in zip(shape, tuple(spec)):
            if axes is None:
                continue
            names = axes if isinstance(axes, tuple) else (axes,)
            parts = int(np.prod([mesh.shape[a] for a in names]))
            if dim % parts:
                return False
        return True

    def shard(self, mesh, *, data_axis: str = "data",
              model_axis: str = "model",
              comm_dtype: str = "fp32") -> "PagedModelRunner":
        """Shard this runner's weights over `mesh`'s model axis and
        re-mint every jitted step with explicit in/out shardings (the
        ISSUE 7 tentpole). Embeddings go vocab-sharded (replicated over
        `data`), QKV/up/gate column-wise, out-proj/down-proj row-wise
        with the allreduce on the row output — the SpecLayout /
        ColWiseParallel / RowWiseParallel placements — and the paged K/V
        pools the engine builds afterwards split along the kv-head axis.
        GQA must split in whole kv-heads: n_kv_heads (and n_heads) not
        divisible by the model-axis degree is a LOUD error, never a
        silent replication. Params whose other dims don't divide (e.g. a
        prime vocab) fall back to replication for that one param, logged.
        Idempotent per mesh; returns self for chaining.

        `comm_dtype="int8"` (ISSUE 15) swaps the row-parallel allreduce
        for the chunked two-level quantized reduce behind the layout's
        `row_parallel_reduce()` hook: the affected matmuls run in an
        explicit shard_map (`_row_mm`), everything else keeps the GSPMD
        placement. "fp32" (default) changes nothing — bit-exact."""
        from paddle_tpu.quantization.qcomm import COMM_DTYPES

        if comm_dtype not in COMM_DTYPES:
            raise ValueError(f"comm_dtype={comm_dtype!r}; expected one "
                             f"of {COMM_DTYPES}")
        for axis in (data_axis, model_axis):
            if axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} lack {axis!r} — build "
                    "the serving mesh with parallel.mesh.serving_mesh("
                    "data, model)")
        tp = int(mesh.shape[model_axis])
        if self.n_kv_heads % tp:
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} is not divisible by the "
                f"tensor-parallel degree {tp} ({model_axis!r} axis): GQA "
                "shards along kv-heads, so every shard needs a whole "
                "kv-head slice of the paged pools — choose tp dividing "
                "n_kv_heads or reshape the mesh")
        if self.n_heads % tp:
            raise ValueError(
                f"n_heads={self.n_heads} is not divisible by the tensor-"
                f"parallel degree {tp} ({model_axis!r} axis)")
        from paddle_tpu.parallel.compat import SpecLayout

        layout = SpecLayout(data_axis=data_axis, model_axis=model_axis,
                            comm_dtype=comm_dtype)
        specs = self._param_specs(layout)
        # a quantized weight's scale tensor shards WITH its weight
        # (ISSUE 9/19), derived from the weight's own spec so the two
        # can never disagree. int8's 1-D [out] vector takes the
        # out-dim's axes (column-parallel -> P(model), row-parallel ->
        # replicated). int4's 2-D [out, groups] matrix takes the
        # TRANSPOSED weight spec: column-parallel shards codes AND
        # scales on the out dim; row-parallel shards the packed in-dim
        # and the reduction-dim groups with it. fp8 is scale-free.
        for name in list(specs):
            sname = name + SCALE_SUFFIX
            if sname in self.params:
                spec = tuple(specs[name])
                if len(spec) < 2:
                    specs[sname] = P()
                elif self.params[sname].ndim == 2:
                    specs[sname] = P(spec[1], spec[0])
                else:
                    specs[sname] = P(spec[1])
        shardings: Dict[str, NamedSharding] = {}
        for name, v in self.params.items():
            if name.endswith(SCALE_SUFFIX):
                continue                # placed with its weight below
            spec = specs.get(name, P())
            sname = name + SCALE_SUFFIX
            sspec = specs.get(sname, P())
            fits = spec == P() or self._spec_fits(v.shape, spec, mesh)
            if fits and sname in self.params and sspec != P():
                sarr = self.params[sname]
                fits = self._spec_fits(sarr.shape, sspec, mesh)
                if fits and sarr.ndim == 2 and \
                        tuple(spec) == tuple(layout.row_parallel()):
                    # int4 row-parallel: every shard must own WHOLE
                    # reduction groups or the grouped epilogue would
                    # mis-scale across the shard boundary — the logical
                    # in-dim is 2x the packed code rows
                    k = 2 * int(v.shape[0])
                    fits = (k // tp) % min(self.weight_group_size,
                                           k) == 0
            if spec != P() and not fits:
                # a non-dividing weight (or non-aligning scale) falls
                # back replicated TOGETHER with its scale — codes and
                # scales never disagree about placement
                logger.warning(
                    "shard: %s %s does not divide over %s — this param "
                    "(and its scale) stays replicated", name,
                    tuple(v.shape), spec)
                spec, sspec = P(), P()
            shardings[name] = NamedSharding(mesh, spec)
            if sname in self.params:
                shardings[sname] = NamedSharding(mesh, sspec)
        self.params = {name: jax.device_put(v, shardings[name])
                       for name, v in self.params.items()}
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.tp_size = tp
        self._layout = layout
        self._param_shardings = shardings
        # the row-parallel set (ISSUE 15): exactly the params whose
        # FINAL spec is the row placement (fallback-replicated params
        # excluded — they never psum), frozen so _mm's routing and the
        # comm byte accounting can never disagree about which matmuls
        # communicate
        row = tuple(layout.row_parallel())
        rows = sorted(n for n in specs
                      if not n.endswith(SCALE_SUFFIX)
                      and tuple(shardings[n].spec) == row)
        # the gather direction (ISSUE 19): column-parallel weights whose
        # OUTPUT the step consumes replicated. That is exactly the
        # logits head — q/k/v/gate/up outputs stay head-/hidden-sharded
        # into the next op, so only lm_head ever pays a (quantizable)
        # all-gather. Tied-embedding models compute logits off the
        # embedding table and keep the GSPMD path (logged).
        col = tuple(layout.column_parallel())
        gathers = sorted(
            n for n in ("lm_head.weight",)
            if n in self.params and tuple(shardings[n].spec) == col)
        self.comm_dtype = comm_dtype
        self._row_names = frozenset(rows)
        self._row_out_dims = tuple(int(self.params[n].shape[1])
                                   for n in rows)
        self._gather_names = frozenset(gathers)
        self._gather_out_dims = tuple(int(self.params[n].shape[1]) // tp
                                      for n in gathers)
        if comm_dtype != "fp32" and not gathers:
            logger.info(
                "shard: no column-parallel gather to quantize (tied "
                "embeddings or replicated lm_head) — the logits path "
                "keeps GSPMD")
        self._jit_cache.clear()        # shardings are baked per jit entry
        logger.info(
            "serving runner sharded: mesh=%s tp=%d (%d/%d heads, %d/%d "
            "kv-heads per shard) comm_dtype=%s (%d row-parallel "
            "allreduces + %d column-parallel gathers/step)",
            dict(mesh.shape), tp, self.n_heads // tp, self.n_heads,
            self.n_kv_heads // tp, self.n_kv_heads, comm_dtype,
            len(rows), len(gathers))
        return self

    @property
    def _shard_ctx(self):
        """(mesh, model_axis) for the shard_map kernel wrappers, None on
        single-device runners."""
        return (self.mesh, self.model_axis) if self.mesh is not None else None

    def _constrain_heads(self, *xs):
        """Pin [B, T, heads, d] activations to the head sharding at
        trace time — makes GSPMD's Megatron partition deterministic
        instead of solver-chosen. No-op unsharded."""
        if self._layout is None:
            return xs if len(xs) > 1 else xs[0]
        sh = NamedSharding(self.mesh, self._layout.heads())
        out = tuple(jax.lax.with_sharding_constraint(x, sh) for x in xs)
        return out if len(out) > 1 else out[0]

    def stage_host_pages(self, layer_data):
        """Stage one host-tier KV page onto the device AHEAD of the step
        that reads it (ISSUE 10 page-in hook): `layer_data` is the
        HostKVTier slot layout — per layer a tuple of page arrays
        ([block, n_kv, d] K/V, plus [n_kv] scale rows on int8 pools).
        One jax.device_put per page, issued at prefetch/fence time so
        the host->device copy overlaps whatever the device is running;
        the engine's fence later scatters the staged values into the
        pools. On a sharded runner the slices land kv-head-sharded like
        the pools themselves, so the fence scatter never reshards."""
        if self.mesh is None:
            return jax.device_put(layer_data)
        kv = NamedSharding(self.mesh, P(None, self.model_axis, None))
        sc = NamedSharding(self.mesh, P(self.model_axis))
        rep = NamedSharding(self.mesh, P())
        return [tuple(jax.device_put(
                    a, kv if np.ndim(a) == 3
                    else (rep if np.ndim(a) == 0 else sc))
                      for a in layer)
                for layer in layer_data]

    def _stage(self, *host_arrays):
        """Stage host operands for a sharded call (ISSUE 7 satellite):
        ONE jax.device_put of the whole tuple with a replicated
        NamedSharding, so each step ships its block tables / token / pos
        arrays to the mesh in a single staging call instead of one
        implicit per-array transfer per shard path. Unsharded runners
        pass host arrays straight to jit (the ISSUE 6 one-hop rule)."""
        if self.mesh is None:
            return host_arrays
        return jax.device_put(host_arrays, NamedSharding(self.mesh, P()))

    def _step_shardings(self, kind: str, pools_arg: int,
                        trailing_args: int = 0):
        """Explicit (in_shardings, out_shardings) for one jitted step:
        params per their specs, host operands replicated, K/V pools
        split on the kv-head axis in AND out — the pools never leave the
        mesh sharded layout, so no step pays a gather/reshard. Int8
        pools (ISSUE 9) carry their scale pools in the layer tuple,
        sharded along the same kv-head axis."""
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        kv = NamedSharding(mesh, self._layout.kv_pool())
        if self.kv_dtype == "int8":
            sc = NamedSharding(mesh, P(None, self.model_axis))
            layer = (kv, kv, sc, sc)
        elif self.kv_dtype == "mixed":
            # the per-page tag plane is page-indexed like the pools but
            # has no head axis — replicated on every shard (ISSUE 15)
            layer = (kv, kv, rep)
        else:
            layer = (kv, kv)
        pools = [layer for _ in range(self.num_layers)]
        ins = ([self._param_shardings] + [rep] * (pools_arg - 1) + [pools]
               + [rep] * trailing_args)
        return tuple(ins), (rep, pools)

    # --------------------------------------------------------- dispatch

    def _attn_impl_for(self, q_len_bucket: int) -> str:
        """Resolve the attention path for one (padded) query-span length.

        Static per jit entry — called at trace time, where the span
        bucket and head layout are known. "auto" takes the ragged kernel
        on a TPU (decode is its q_len == 1 case) and the gather
        reference elsewhere; "ragged" forces the kernel (interpret mode
        off TPU); "reference" forces the gather oracle. A head layout
        the kernel cannot tile gives way to the reference with a
        warning. The chosen impl is logged once per bucket so a serve's
        dispatch is auditable."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_attention_ok

        want_kernel = (self.attn_impl == "ragged"
                       or (self.attn_impl == "auto"
                           and jax.default_backend() == "tpu"))
        impl = ("ragged" if want_kernel and ragged_attention_ok(
            self.head_dim, self.n_heads, self.n_kv_heads) else "reference")
        key = (q_len_bucket, impl)
        if key not in self._impl_logged:
            self._impl_logged.add(key)
            logger.log(
                logging.WARNING if want_kernel and impl == "reference"
                else logging.INFO,
                "serving attention impl: %s (q_len bucket %d, heads %d/%d, "
                "head_dim %d, attn_impl=%s)", impl, q_len_bucket,
                self.n_heads, self.n_kv_heads, self.head_dim, self.attn_impl)
        return impl

    def _kv_page_bytes(self) -> int:
        """HBM bytes ONE page costs this runner's attention per call,
        PER SHARD: honest accounting (ISSUE 9) — int8 pools count the
        int8 code bytes PLUS the per-page-per-head scale bytes the
        dequant reads, never the logical dtype's itemsize."""
        nkv = self.n_kv_heads // self.tp_size
        data = self.block_size * nkv * self.head_dim
        if self.kv_dtype == "int8":
            return 2 * self.num_layers * (data + nkv * 4)
        if self.kv_dtype == "fp8":
            # native fp8 pages: 1 byte/element, no scale rows (ISSUE 15)
            return 2 * self.num_layers * data
        # "mixed" pools store fp32 (the tag plane steers the write
        # path, the attend path never reads it) — fp32-width reads
        return 2 * self.num_layers * data * np.dtype(self.dtype).itemsize

    def _fold_block_pages(self, span: int) -> int:
        """Pages in one block of the ragged kernel's walk under a launch
        of `span` padded rows a sequence, PER SHARD; 0 where its tiles do
        not take the few-rows fold, whose blocks are what is counted."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            few_rows_block_pages

        itemsize = np.dtype(self.dtype).itemsize
        return few_rows_block_pages(
            span, self.n_heads // self.tp_size, itemsize, self.block_size,
            self.n_kv_heads // self.tp_size, self.head_dim,
            1 if self.kv_dtype in ("int8", "fp8") else itemsize,
            row_pools=self.ROW_PAGES)

    def _account_blocks(self, starts, q_lens, span: int, lower=None):
        """Bump the few-rows fold's block counters for one layer's walk
        of a ragged launch (`ragged_block_counts`)."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_block_counts

        ppb = self._fold_pages.get(span)
        if ppb is None:
            ppb = self._fold_pages[span] = self._fold_block_pages(span)
        if ppb:
            blocks, edges = ragged_block_counts(starts, q_lens,
                                                self.block_size, ppb, lower)
            self.ragged_blocks += int(blocks.sum())
            self.ragged_edge_blocks += int(edges.sum())

    def _account_attn(self, impl: str, starts, q_lens, table_width: int,
                      span: int = 1):
        """Bump the instrumented-pool counters for one step call: the
        kernels read only each span's live pages (the in-kernel walk);
        the gather path reads every table entry of every slot. Counted
        host-side from the same operands the device call gets, so the
        bandwidth claim is verifiable without TPU access. On a sharded
        runner the count is PER SHARD — each shard reads only its
        n_kv/tp kv-head slice of every page, so sharded bytes equal the
        single-device bytes / tp (the ISSUE 7 acceptance number). On an
        int8 pool (ISSUE 9) the per-page bytes are the quantized bytes
        + scale bytes, so fp32-vs-int8 arms of the same workload expose
        the real bandwidth reduction. `span`: the call's padded rows a
        sequence, which says whether its tiles take the kernel's few-rows
        fold, whose blocks are counted too."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            attention_page_reads

        per_page = self._kv_page_bytes()
        gather_pages = len(np.asarray(starts).reshape(-1)) * table_width
        if impl == "ragged":
            pages = int(attention_page_reads(starts, q_lens,
                                             self.block_size).sum())
            self._account_blocks(starts, q_lens, span)
        else:
            pages = gather_pages
        self.attn_kv_bytes_read += pages * per_page
        self.attn_kv_bytes_gather += gather_pages * per_page

    def _account_decode(self, pos, tables) -> None:
        """One decode step's attention, a row a sequence at `pos`."""
        self._account_attn(self._attn_impl_for(1), pos, np.ones_like(pos),
                           tables.shape[1])

    def _account_comm(self, rows: int, steps: int = 1) -> None:
        """Bump the instrumented comm counters for one step call
        (ISSUE 15): every forward runs all `_row_out_dims` row-parallel
        allreduces over [rows, out_dim] activations (rows = the call's
        padded B*T operand rows — what the wire actually carries), so
        the per-shard wire bytes are countable host-side from the same
        operands the device call gets, quantized-vs-fp32 honestly
        (scale bytes included via qcomm.allreduce_bytes). No-op on
        unsharded runners."""
        if self.tp_size <= 1 or not (self._row_out_dims
                                     or self._gather_out_dims):
            return
        from paddle_tpu.quantization.qcomm import (
            allgather_bytes, allreduce_bytes,
        )

        r = int(rows) * int(steps)
        for d in self._row_out_dims:
            self.tp_comm_bytes_fp32 += allreduce_bytes(r, d, "fp32")
            self.tp_comm_bytes += allreduce_bytes(r, d, self.comm_dtype)
        # the gather direction (ISSUE 19): the logits head's
        # column-parallel all-gather moves each shard's [rows, V/tp]
        # slice — counted at the configured comm dtype vs fp32, scale
        # bytes included, same honesty rule as the reduce side (the
        # fp32 engine pays this gather too, via GSPMD)
        for d in self._gather_out_dims:
            self.tp_gather_bytes_fp32 += allgather_bytes(r, d, "fp32")
            self.tp_gather_bytes += allgather_bytes(r, d, self.comm_dtype)

    def reset_attn_counters(self) -> None:
        for name in self.GAUGES:
            setattr(self, name, 0.0)

    # ----------------------------------- weight byte accounting (ISSUE 19)

    def weight_bytes(self) -> int:
        """Resident HBM bytes of the whole params dict — quantized
        codes + scale tensors + the floating params (embeddings, norms,
        biases) counted at their actual storage dtypes. Honest by
        construction: scales and packed nibbles are real residents, so
        the committed reduction is measured, never an assumed 8x."""
        return int(sum(int(v.nbytes) for v in self.params.values()))

    def weight_bytes_fp32(self) -> int:
        """What the SAME logical params would cost at fp32: quantized
        weights count their logical [in, out] element count (packed
        int4 codes hold TWO logical elements per byte) at 4 bytes,
        scale tensors count zero (they don't exist on an fp32 runner),
        floating params count their element count at 4 bytes."""
        total = 0
        for name, v in self.params.items():
            if name.endswith(SCALE_SUFFIX):
                continue
            elems = int(v.size)
            if name in self._quantized_names and self.weight_dtype == \
                    "int4":
                elems *= 2              # two nibbles per packed byte
            total += elems * 4
        return total

    def weight_bytes_reduction_x(self) -> float:
        """Measured whole-model weight-byte reduction vs fp32 — 1.0 on
        the default runner, the bench/acceptance number on quantized
        ones (int4 >= 3.5x on matmul-dominated configs with the group
        scales counted)."""
        wb = self.weight_bytes()
        return self.weight_bytes_fp32() / wb if wb else 1.0

    # ------------------------------------------------------------- steps

    def _write_indices(self, positions, tables, valid):
        """positions/valid: [B, T]; tables: [B, P] -> page/off [B, T].
        Invalid positions are redirected to the scratch page."""
        page = jnp.take_along_axis(
            tables, (positions // self.block_size).astype(jnp.int32), axis=1)
        page = jnp.where(valid, page, SCRATCH_PAGE)
        return page, positions % self.block_size

    def _prefill_step(self, params, tokens, table, real_len, start_pos,
                      pools, **fwd):
        """`fwd`: what a subclass's `_forward` takes besides the
        chassis's operands."""
        T = tokens.shape[1]
        offs = jnp.arange(T, dtype=jnp.int32)[None, :]             # [1, T]
        valid = offs < real_len
        positions = jnp.where(valid, start_pos + offs, 0)
        page, off = self._write_indices(positions, table, valid)
        args = (params, tokens, positions, page, off, table,
                jnp.reshape(start_pos, (1,)), jnp.reshape(real_len, (1,)),
                pools)
        if self.HEAD_ROWS:
            logits, pools, *counts = self._forward(
                *args, head_rows=jnp.reshape(real_len - 1, (1,)), **fwd)
            return (logits[0, 0], pools, *counts)
        logits, pools, *counts = self._forward(*args, **fwd)
        return (logits[0, real_len - 1], pools, *counts)

    def _decode_step(self, params, tokens, tables, pos, pools,
                     write_mask=None):
        positions = pos[:, None].astype(jnp.int32)                 # [B, 1]
        # dead slots carry all-scratch tables; an early-stopped horizon
        # row (ISSUE 11) additionally masks its write so a frozen row's
        # garbage feedback token never lands in a live page
        valid = (jnp.ones_like(positions, bool) if write_mask is None
                 else write_mask[:, None])
        page, off = self._write_indices(positions, tables, valid)
        B = tokens.shape[0]
        logits, pools, *counts = self._forward(
            params, tokens, positions, page, off, tables, pos,
            jnp.ones((B,), jnp.int32), pools)
        return (logits[:, 0], pools, *counts)

    def _decode_multi_step(self, params, tokens, tables, pos, pools,
                           num_steps: int):
        """Device-resident multi-step greedy decode (ISSUE 6 tentpole):
        `lax.scan` over `num_steps` consecutive decode steps, each step's
        argmax token fed back as the next step's input ON DEVICE. K/V is
        written through the fixed block tables at per-step positions
        pos, pos+1, ..., pos+num_steps-1 (the scheduler committed those
        pages up front). Accumulates the [B, s] greedy token buffer and
        a per-step all-finite flag, packed into ONE int32 array so the
        host pays a single transfer per horizon. num_steps is static
        (baked per jit entry); the greedy feedback is jnp.argmax, whose
        first-max tie-break matches the host path (`greedy_grid` /
        np.argmax — the batched-sampling pin), so a horizon is bit-exact
        vs num_steps sequential decode()+argmax round-trips."""

        def body(carry, _):
            toks, p, pools = carry
            # (the scans keep no counts)
            logits, pools, *_ = self._decode_step(params, toks[:, None],
                                                  tables, p, pools)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            fin = jnp.all(jnp.isfinite(logits), axis=-1)
            return (nxt, p + 1, pools), (nxt, fin)

        init = (tokens.astype(jnp.int32), pos.astype(jnp.int32), pools)
        (_, _, pools), (toks, fins) = jax.lax.scan(body, init, None,
                                                   length=num_steps)
        packed = jnp.stack([toks.T, fins.T.astype(jnp.int32)])  # [2, B, s]
        return packed, pools

    @staticmethod
    def _sampled_rows(logits, seeds, steps, temps, top_k, top_p):
        """Per-row seeded sampling INSIDE the decode_multi scan (ISSUE
        11 tentpole): row b is sampled with the key
        fold_in(key(seeds[b]), steps[b]) at temperature temps[b] —
        exactly the step-indexed stream engine.sample_token draws on
        the host, so a temperature>0 horizon is bit-identical to the
        per-step seeded path. The division by temperature happens HERE
        (astype-then-divide, the host order) and `_sample` is then
        invoked at temperature 1.0 — x/1.0 is an IEEE identity, so the
        remaining top-k/top-p/categorical math is the verbatim host
        code path on the same [1, V] shape. top_k/top_p are static
        (one pair per jit entry — the engine only routes homogeneous
        batches here); rows with temps[b] == 0 are ignored by the
        caller (greedy argmax selected via where)."""
        from paddle_tpu.models.generation import _sample

        def one(row, seed, step, temp):
            key = jax.random.fold_in(jax.random.key(seed), step)
            l = row[None].astype(jnp.float32) / jnp.where(temp > 0.0,
                                                          temp, 1.0)
            return _sample(l, key, 1.0, top_k, top_p)[0]

        return jax.vmap(one)(logits, seeds, steps, temps)

    def _decode_multi_x_step(self, params, tokens, tables, pos, pools,
                             seeds, base_steps, temps, stop_ids, remaining,
                             num_steps: int, top_k, top_p,
                             sampling: bool, early_stop: bool):
        """Extended device-resident horizon (ISSUE 11 tentpole): the
        decode_multi scan widened with (a) per-request seeded key
        schedules — rows with temps > 0 draw their step-indexed sample
        stream inside the scan instead of forcing the whole batch back
        to the per-step path — and (b) an on-device stop-condition
        flag: a row whose emitted token hits its stop set (stop_ids,
        -1-padded) or exhausts its remaining-token budget sets a done
        bit that freezes the row's KV writes (masked to scratch) and
        its position, so overshoot past a stop is never computed into
        the pools and never drained as a real token. Returns a packed
        [3, B, s] int32 buffer: row 0 the token buffer, row 1 the
        per-step finiteness flags, row 2 the LIVE flags (1 = this
        token is a real emission; everything after a row's done bit is
        garbage the host must not replay)."""

        def body(carry, _):
            toks, p, done, cnt, pools = carry
            logits, pools, *_ = self._decode_step(
                params, toks[:, None], tables, p, pools,
                write_mask=jnp.logical_not(done))
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            fin = jnp.all(jnp.isfinite(logits), axis=-1)
            if sampling:
                # per-row step index = generated-token count so far
                sampled = self._sampled_rows(logits, seeds,
                                             base_steps + cnt, temps,
                                             top_k, top_p)
                nxt = jnp.where(temps > 0.0, sampled, greedy)
            else:
                nxt = greedy
            live = jnp.logical_not(done)
            if early_stop:
                hit = jnp.any(nxt[:, None] == stop_ids, axis=1)
                cnt2 = cnt + live.astype(jnp.int32)
                done2 = done | (live & (hit | (cnt2 >= remaining)))
            else:
                cnt2 = cnt + 1
                done2 = done
            p2 = jnp.where(live, p + 1, p)    # frozen rows hold position
            return (nxt, p2, done2, cnt2, pools), (nxt, fin, live)

        B = tokens.shape[0]
        init = (tokens.astype(jnp.int32), pos.astype(jnp.int32),
                jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32), pools)
        (_, _, _, _, pools), (toks, fins, lives) = jax.lax.scan(
            body, init, None, length=num_steps)
        packed = jnp.stack([toks.T, fins.T.astype(jnp.int32),
                            lives.T.astype(jnp.int32)])     # [3, B, s]
        return packed, pools

    @staticmethod
    def _sampled_span(logits, seeds, steps, temps, top_k, top_p):
        """Per-position seeded sampling over verify spans (ISSUE 18):
        position i of row b draws fold_in(key(seeds[b]), steps[b, i]) —
        the same step-indexed stream `_sampled_rows` uses, widened to a
        [B, T] step grid so every span position's target token comes
        from exactly the key the host would have used had that position
        been reached per-step. Division by temperature happens here
        (the host order); `_sample` runs at 1.0 on the same [1, V]
        shape, so acceptance is bit-identical to host `_accept_verify`."""
        from paddle_tpu.models.generation import _sample

        def one(row, seed, step, temp):
            key = jax.random.fold_in(jax.random.key(seed), step)
            l = row[None].astype(jnp.float32) / jnp.where(temp > 0.0,
                                                          temp, 1.0)
            return _sample(l, key, 1.0, top_k, top_p)[0]

        per_row = jax.vmap(one, in_axes=(0, None, 0, None))
        return jax.vmap(per_row)(logits, seeds, steps, temps)

    def _decode_multi_spec_step(self, params, tokens, tables, pos, pools,
                                drafts, seeds, base_steps, temps, stop_ids,
                                remaining, num_steps: int, top_k, top_p,
                                sampling: bool):
        """Verify-in-scan (ISSUE 18 tentpole): the extended decode
        horizon where every scan step carries a per-row DRAFT SPAN.

        drafts is [B, num_steps, K] int32, -1-padded: step t feeds row
        b the span [fed_token, draft[b, t, :]] through the ragged-core
        forward (q_len = 1 + #real drafts; every span position's K/V
        lands at p..p+K through `_write_indices`' scratch masking), then
        resolves accept/reject ON DEVICE per position: emission i is
        argmax (or the seeded-stream sample at step base+cnt+i) of span
        position i, and it is KEPT iff the row is live, every earlier
        draft matched its emission, and no earlier kept emission hit a
        stop/budget bound. The last kept emission (corrected or bonus
        token) feeds the next scan step; positions advance by the kept
        count, so a fully-accepted span moves K+1 tokens per step while
        a rejected one degrades to ordinary multi-step decode. Rejected-
        tail K/V self-heals: the next span re-writes from its own start,
        and the host truncates the final overhang at commit
        (`SequenceKV.truncate`). Writes past max_model_len (only ever
        proposed-tail garbage — kept emissions are budget-bounded) are
        masked to scratch rather than letting the page-table gather
        clamp into a live page.

        Returns packed [3, B, num_steps, K+1] int32 — plane 0 emitted
        tokens, plane 1 per-position finiteness, plane 2 the KEEP mask
        (a per-step prefix; everything past it is garbage the host must
        not replay) — ONE host transfer per horizon."""
        B, _, K = drafts.shape
        T = K + 1
        wall = jnp.int32(self.max_model_len)
        offs = jnp.arange(T, dtype=jnp.int32)[None, :]             # [1, T]

        def body(carry, draft_t):
            toks, p, done, cnt, pools = carry
            ndraft = jnp.sum((draft_t >= 0).astype(jnp.int32), axis=1)
            span = jnp.concatenate([toks[:, None],
                                    jnp.maximum(draft_t, 0)], axis=1)
            q_lens = jnp.where(done, 0, ndraft + 1)
            valid = (offs < q_lens[:, None]) & (p[:, None] + offs < wall)
            positions = jnp.where(valid, p[:, None] + offs, 0)
            page, off = self._write_indices(positions, tables, valid)
            logits, pools, *_ = self._forward(params, span, positions,
                                              page, off, tables, p, q_lens,
                                              pools)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]
            fin = jnp.all(jnp.isfinite(logits), axis=-1)            # [B, T]
            if sampling:
                steps = base_steps[:, None] + cnt[:, None] + offs
                sampled = self._sampled_span(logits, seeds, steps, temps,
                                             top_k, top_p)
                nxt = jnp.where(temps[:, None] > 0.0, sampled, greedy)
            else:
                nxt = greedy
            match = (draft_t == nxt[:, :K]) & (draft_t >= 0)        # [B, K]
            hit = jnp.any(nxt[:, :, None] == stop_ids[:, None, :], axis=2)
            pos_done = hit | (cnt[:, None] + 1 + offs
                              >= remaining[:, None])                # [B, T]
            cont = match & jnp.logical_not(pos_done[:, :K])
            live = jnp.logical_not(done)
            keep = jnp.concatenate(
                [live[:, None],
                 live[:, None] & jnp.cumprod(
                     cont.astype(jnp.int32), axis=1).astype(bool)],
                axis=1)                                             # [B, T]
            m = jnp.sum(keep.astype(jnp.int32), axis=1)
            last = jnp.maximum(m - 1, 0)
            fb = jnp.take_along_axis(nxt, last[:, None], axis=1)[:, 0]
            fb = jnp.where(m > 0, fb, toks)
            done2 = done | jnp.any(keep & pos_done, axis=1)
            return (fb, p + m, done2, cnt + m, pools), (nxt, fin, keep)

        init = (tokens.astype(jnp.int32), pos.astype(jnp.int32),
                jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32), pools)
        (_, _, _, _, pools), (toks, fins, keeps) = jax.lax.scan(
            body, init, jnp.swapaxes(drafts, 0, 1), length=num_steps)
        packed = jnp.stack(
            [jnp.swapaxes(toks, 0, 1),
             jnp.swapaxes(fins, 0, 1).astype(jnp.int32),
             jnp.swapaxes(keeps, 0, 1).astype(jnp.int32)])  # [3, B, s, T]
        return packed, pools

    def _ragged_core(self, params, tokens, tables, start_pos, q_lens,
                     pools, **head):
        """One mixed ragged batch: every slot carries its own query span
        — decode steps (q_len=1), prefill chunks (q_len=chunk at an
        offset), verify spans (q_len=k+1, ISSUE 5), dead slots (q_len=0)
        — computed in ONE forward pass. Returns the full per-position
        logits [B, T, V] (rows past a span's q_len are garbage that
        callers never read)."""
        B, T = tokens.shape
        offs = jnp.arange(T, dtype=jnp.int32)[None, :]             # [1, T]
        valid = offs < q_lens[:, None]
        positions = jnp.where(valid, start_pos[:, None] + offs, 0)
        page, off = self._write_indices(positions, tables, valid)
        return self._forward(params, tokens, positions, page, off,
                             tables, start_pos, q_lens, pools, **head)

    def _ragged_step(self, params, tokens, tables, start_pos, q_lens,
                     pools):
        """Ragged batch returning each slot's logits at its span's LAST
        live row only — the fused chunk+decode step's shape."""
        def last():
            return jnp.maximum(q_lens - 1, 0).astype(jnp.int32)

        if self.HEAD_ROWS:
            logits, pools, *counts = self._ragged_core(
                params, tokens, tables, start_pos, q_lens, pools,
                head_rows=last())
            return (logits[:, 0], pools, *counts)
        logits, pools, *counts = self._ragged_core(
            params, tokens, tables, start_pos, q_lens, pools)
        out = jnp.take_along_axis(logits, last()[:, None, None], axis=1)
        return (out[:, 0], pools, *counts)

    def _jitted(self, kind: str, shape_key):
        """Shape-keyed jit cache. Every miss (= a compile) is logged, and
        PADDLE_TPU_MAX_JIT_CACHE bounds the entry count with LRU eviction
        so a pathological shape stream cannot grow the compile cache
        without bound (chunked prefill already buckets its lengths, so a
        healthy serve needs only O(log max_model_len) prefill entries plus
        one decode entry per batch width)."""
        key = (kind, shape_key)
        cached = self._jit_cache.get(key)
        if cached is not None:
            self._jit_cache.move_to_end(key)
            return cached
        if kind in self.EXTRA_STEPS:
            method, pools_arg, static = self.EXTRA_STEPS[kind]
            fn = getattr(self, method)
        else:
            fn = {"prefill": self._prefill_step,
                  "decode": self._decode_step,
                  "decode_multi": self._decode_multi_step,
                  "decode_multi_x": self._decode_multi_x_step,
                  "decode_multi_spec": self._decode_multi_spec_step,
                  "ragged": self._ragged_step,
                  "ragged_full": self._ragged_core}[kind]
            pools_arg = {"prefill": 5, "decode": 4, "decode_multi": 4,
                         "decode_multi_x": 4, "decode_multi_spec": 4,
                         "ragged": 5, "ragged_full": 5}[kind]
            # decode_multi's horizon length is a lax.scan bound — static;
            # the extended horizon additionally bakes the sampling config
            # and the early-stop switch per jit entry; the verify-in-scan
            # horizon bakes the sampling config (its stop plane is always
            # on)
            static = {"decode_multi": (5,),
                      "decode_multi_x": (10, 11, 12, 13, 14),
                      "decode_multi_spec": (11, 12, 13, 14)}.get(kind, ())
        donate = (pools_arg,) if pools_arg is not None \
            and jax.default_backend() == "tpu" else ()
        if self.mesh is not None:
            # sharded runner (ISSUE 7): every step is pjit'd with
            # explicit in/out shardings — params per spec, pools split
            # on the kv-head axis both ways, host operands replicated
            ins, outs = self._step_shardings(
                kind, pools_arg,
                trailing_args={"decode_multi_x": 5,
                               "decode_multi_spec": 6}.get(kind, 0))
            jitted = jax.jit(fn, donate_argnums=donate,
                             static_argnums=static, in_shardings=ins,
                             out_shardings=outs)
        else:
            jitted = jax.jit(fn, donate_argnums=donate,
                             static_argnums=static)
        def first_call(*args):
            # tracing and compilation happen here, not at jax.jit above:
            # a failure is the program's, not a transient device fault
            try:
                # trace + lower + compile (or the compile cache's load),
                # this call's dispatch and whatever that dispatch waits
                # for: the host's time in a first call, not compiler time.
                # Always recorded, a child of the step that caused it
                with _prof.always_span("runner.compile", kind=kind,
                                       key=shape_key):
                    out = jitted(*args)
            except Exception as e:
                self._jit_cache.pop(key, None)
                raise StepCompileError(
                    f"serving step {kind} key={shape_key} failed on its "
                    f"first call (trace/compile): {e}") from e
            if key in self._jit_cache:
                self._jit_cache[key] = jitted
            return out

        self._jit_cache[key] = first_call
        logger.info("serving jit compile %s key=%s (cache entries: %d)",
                    kind, shape_key, len(self._jit_cache))
        cap = int(os.environ.get("PADDLE_TPU_MAX_JIT_CACHE", "0") or "0")
        if cap > 0:
            while len(self._jit_cache) > cap:
                evicted, _ = self._jit_cache.popitem(last=False)
                logger.warning(
                    "serving jit cache over PADDLE_TPU_MAX_JIT_CACHE=%d; "
                    "evicting %s", cap, evicted)
        return first_call

    def _dispatch(self, fn, args, account):
        """The jitted call (`runner.dispatch`), then the call's byte
        accounting (`runner.account`: `_account_attn`, `_account_comm`).
        The counters are host arithmetic on the call's own operands, so
        they are taken once the device has the program and does not wait
        for them; a call that raised is counted like one that ran, as it
        was when the accounting came first."""
        try:
            with _prof.span("runner.dispatch"):
                return fn(*args)
        finally:
            with _prof.span("runner.account"):
                account()

    def prefill(self, tokens: List[int], table_row: List[int], pools):
        """Run one sequence's (re-)prefill; returns (last_logits[V], pools)."""
        return self.prefill_chunk(tokens, 0, table_row, pools)

    def prefill_chunk(self, tokens: List[int], start_pos: int,
                      table_row: List[int], pools, slot=None):
        """Compute context positions [start_pos, start_pos + len(tokens))
        for one sequence, attending over everything the block table
        already holds (earlier chunks, shared prefix pages). Returns the
        logits of the chunk's LAST position plus the updated pools —
        callers only sample from the chunk that completes the context.
        Chunk lengths share the power-of-2 prefill buckets, so chunking
        never compiles per odd length. `slot`: the sequence's state slot,
        for a runner with recurrent state (`state_layout`); it rides
        beside `start_pos` as one operand."""
        with _prof.span("runner.launch") as launch:
            t = len(tokens)
            tb = bucket_len(t)
            with _prof.span("runner.stage"):
                padded = np.zeros((1, tb), np.int32)
                padded[0, :t] = tokens
                fn = self._jitted("prefill", tb)
                launch.set(kind="prefill", key=tb)
                # host operands go to the jitted fn as-is — jit commits them
                # in one hop; a jnp.asarray(np.asarray(...)) round-trip here
                # used to stage an extra host copy per call (ISSUE 6
                # satellite). Sharded runners stage them in ONE replicated
                # device_put (ISSUE 7)
                toks, table = self._stage(
                    padded, np.asarray(table_row, np.int32)[None])
                start = np.int32(start_pos) if slot is None else np.asarray(
                    [start_pos, slot], np.int32)

            def account():
                self._account_attn(self._attn_impl_for(tb),
                                   np.asarray([start_pos]), np.asarray([t]),
                                   len(table_row), span=tb)
                self._account_comm(tb)

            return self._emit(self._dispatch(
                fn, (self.params, toks, table, np.int32(t), start, pools),
                account))

    def decode(self, tokens, tables, pos, pools):
        """Batched decode step; tokens [B], tables [B, P], pos [B]."""
        with _prof.span("runner.launch") as launch:
            pos_np = np.asarray(pos, np.int32)
            with _prof.span("runner.stage"):
                B = np.asarray(tokens).shape[0]
                fn = self._jitted("decode", B)
                launch.set(kind="decode", key=B)
                toks, tabs, pos_a = self._stage(
                    np.asarray(tokens, np.int32)[:, None],
                    np.asarray(tables, np.int32), pos_np)

            def account():
                self._account_decode(pos_np, np.asarray(tables))
                self._account_comm(pos_np.shape[0])

            return self._emit(self._dispatch(
                fn, (self.params, toks, tabs, pos_a, pools), account))

    def decode_multi(self, tokens, tables, pos, pools, num_steps: int, *,
                     seeds=None, base_steps=None, temps=None,
                     top_k=None, top_p=None,
                     stop_ids=None, remaining=None,
                     early_stop: bool = False):
        """Device-resident multi-step decode (ISSUE 6): run `num_steps`
        consecutive decode steps in ONE jitted lax.scan launch, feeding
        each step's on-device token back as the next input. tokens [B]
        (the fed last tokens), tables [B, P] (must already map every
        page the horizon's live rows will write), pos [B].

        With no extension operands the scan is pure greedy and returns
        (packed[2, B, num_steps] int32, pools): row 0 the greedy token
        buffer, row 1 the per-step finiteness flags — one host transfer
        drains the whole horizon.

        Extended horizons (ISSUE 11): `seeds`/`base_steps`/`temps` [B]
        turn on per-row seeded sampling inside the scan (rows with
        temps > 0 draw fold_in(key(seed), base_step + emitted) — the
        host sample stream, bit-identical; top_k/top_p are static and
        must be homogeneous across the sampled rows), and
        `stop_ids` [B, S] (-1-padded) + `remaining` [B] with
        `early_stop=True` set a per-row done bit on device: the row's
        KV writes freeze and subsequent steps emit dead tokens flagged
        by a third packed plane. Any extension makes the return shape
        [3, B, num_steps] (tokens, finite, LIVE)."""
        with _prof.span("runner.launch") as launch:
            if num_steps < 1:
                raise ValueError("decode_multi needs num_steps >= 1")
            pos_np = np.asarray(pos, np.int32)

            def account():
                tabs_np = np.asarray(tables)
                for t in range(num_steps):  # inner step t attends at pos + t
                    # host-side byte analytics; early-stopped rows may
                    # freeze earlier, so this upper-bounds the extended
                    # horizon's reads
                    self._account_decode(pos_np + t, tabs_np)
                self._account_comm(pos_np.shape[0], steps=num_steps)

            B = pos_np.shape[0]
            sampling = temps is not None
            extended = sampling or early_stop
            if not extended:
                with _prof.span("runner.stage"):
                    fn = self._jitted("decode_multi", (B, num_steps))
                    launch.set(kind="decode_multi", key=(B, num_steps))
                    toks, tabs, pos_a = self._stage(
                        np.asarray(tokens, np.int32),
                        np.asarray(tables, np.int32), pos_np)
                return self._dispatch(
                    fn, (self.params, toks, tabs, pos_a, pools, num_steps),
                    account)
            with _prof.span("runner.stage"):
                seeds = np.zeros((B,), np.int32) if seeds is None \
                    else np.asarray(seeds, np.int32)
                base_steps = np.zeros((B,), np.int32) if base_steps is None \
                    else np.asarray(base_steps, np.int32)
                temps = np.zeros((B,), np.float32) if temps is None \
                    else np.asarray(temps, np.float32)
                stop_ids = np.full((B, 1), -1, np.int32) \
                    if stop_ids is None else np.asarray(stop_ids, np.int32)
                remaining = np.full((B,), num_steps, np.int32) \
                    if remaining is None else np.asarray(remaining, np.int32)
                key = (B, num_steps, top_k, top_p, sampling,
                       bool(early_stop), stop_ids.shape[1])
                fn = self._jitted("decode_multi_x", key)
                launch.set(kind="decode_multi_x", key=key)
                toks, tabs, pos_a, sd, bs, tp, si, rem = self._stage(
                    np.asarray(tokens, np.int32),
                    np.asarray(tables, np.int32), pos_np, seeds, base_steps,
                    temps, stop_ids, remaining)
            return self._dispatch(
                fn, (self.params, toks, tabs, pos_a, pools, sd, bs, tp, si,
                     rem, num_steps, top_k, top_p, sampling,
                     bool(early_stop)), account)

    def decode_multi_spec(self, tokens, tables, pos, pools, drafts, *,
                          seeds=None, base_steps=None, temps=None,
                          top_k=None, top_p=None, stop_ids=None,
                          remaining=None):
        """Fused speculative horizon (ISSUE 18): `drafts.shape[1]` scan
        steps, each carrying a [B, K] -1-padded draft span verified and
        accepted ON DEVICE (see `_decode_multi_spec_step`). tokens [B]
        (fed last tokens), tables [B, P] (must map every page the
        horizon's funded writes can touch), pos [B], drafts [B, s, K]
        int32 — K pre-padded by the engine to `bucket_len(1 + k) - 1`
        so fused spans share the per-step verify path's bucket rule
        (same attention impl, bit-identical logits). The stop plane
        (stop_ids [B, S] -1-padded + remaining [B]) is ALWAYS on: the
        budget bound is what keeps every kept emission inside the funded
        page range. Seeded sampling mirrors decode_multi's extension
        operands. Returns (packed [3, B, s, K+1] int32, pools): planes
        tokens / finiteness / keep-mask, one host transfer per horizon."""
        with _prof.span("runner.launch") as launch:
            drafts = np.asarray(drafts, np.int32)
            if drafts.ndim != 3 or drafts.shape[1] < 1:
                raise ValueError(
                    f"drafts must be [B, num_steps>=1, K], got {drafts.shape}")
            B, num_steps, K = drafts.shape
            pos_np = np.asarray(pos, np.int32)
            with _prof.span("runner.stage"):
                sampling = temps is not None
                seeds = np.zeros((B,), np.int32) if seeds is None \
                    else np.asarray(seeds, np.int32)
                base_steps = np.zeros((B,), np.int32) if base_steps is None \
                    else np.asarray(base_steps, np.int32)
                temps = np.zeros((B,), np.float32) if temps is None \
                    else np.asarray(temps, np.float32)
                stop_ids = np.full((B, 1), -1, np.int32) \
                    if stop_ids is None else np.asarray(stop_ids, np.int32)
                remaining = np.full((B,), num_steps * (K + 1), np.int32) \
                    if remaining is None else np.asarray(remaining, np.int32)
                key = (B, num_steps, K, top_k, top_p, sampling,
                       stop_ids.shape[1])
                fn = self._jitted("decode_multi_spec", key)
                launch.set(kind="decode_multi_spec", key=key)
                toks, tabs, pos_a, dr, sd, bs, tp, si, rem = self._stage(
                    np.asarray(tokens, np.int32),
                    np.asarray(tables, np.int32), pos_np, drafts, seeds,
                    base_steps, temps, stop_ids, remaining)

            def account():
                width = np.asarray(tables).shape[1]
                impl = self._attn_impl_for(K + 1)
                spans = np.full((B,), K + 1, np.int32)
                for t in range(num_steps):  # upper-bounds the per-step reads
                    self._account_attn(impl, pos_np + t * (K + 1), spans,
                                       width, span=K + 1)
                self._account_comm(B * (K + 1), steps=num_steps)

            return self._dispatch(
                fn, (self.params, toks, tabs, pos_a, pools, dr, sd, bs, tp,
                     si, rem, num_steps, top_k, top_p, sampling), account)

    def ragged_step(self, tokens, tables, start_pos, q_lens, pools,
                    full_logits: bool = False):
        """One mixed ragged batch (the fused chunk+decode step): tokens
        [B, T] int (T pre-padded to a shared power-of-2 bucket by the
        engine via `bucket_len` — verify spans and prefill chunks share
        the SAME bucket rule, so a k+1-token verify span reuses the
        small-chunk jit entry instead of minting its own), tables
        [B, P], start_pos/q_lens [B]. Returns (logits, pools): logits is
        [B, V] at each span's last live row, or the full per-position
        [B, T, V] when `full_logits=True` — the speculative verify step
        (ISSUE 5) scores all k+1 span positions from one launch."""
        with _prof.span("runner.launch") as launch:
            tokens = np.asarray(tokens, np.int32)
            B, T = tokens.shape
            start_pos = np.asarray(start_pos, np.int32)
            q_lens = np.asarray(q_lens, np.int32)
            with _prof.span("runner.stage"):
                kind = "ragged_full" if full_logits else "ragged"
                fn = self._jitted(kind, (B, T))
                launch.set(kind=kind, key=(B, T))
                toks, tabs, starts, lens = self._stage(
                    tokens, np.asarray(tables, np.int32), start_pos, q_lens)

            def account():
                self._account_attn(self._attn_impl_for(T), start_pos, q_lens,
                                   np.asarray(tables).shape[1], span=T)
                self._account_comm(B * T)

            return self._emit(self._dispatch(
                fn, (self.params, toks, tabs, starts, lens, pools), account))

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools):
        raise NotImplementedError


def runner_for(model, block_size: int = 16, max_model_len: int | None = None,
               attn_impl: str = "auto", kv_dtype: str = "fp32",
               weight_dtype: str = "fp32",
               weight_group_size: int = 128) -> PagedModelRunner:
    """The runner for a supported decoder Layer, by its class:
    `serving/runners/__init__.py` has the table, a module a runner."""
    from paddle_tpu.serving import runners

    cls = runners.runner_class(model)
    if cls is None:
        raise TypeError(
            f"no serving runner for {type(model).__name__}; supported: "
            f"{runners.supported()} (write a PagedModelRunner subclass for "
            "custom decoders)")
    return cls(model, block_size, max_model_len, attn_impl,
               kv_dtype=kv_dtype, weight_dtype=weight_dtype,
               weight_group_size=weight_group_size)


# what `runner_for` takes besides the model: an entry point that is handed
# one `**kw` for runner and engine together splits it by these names
RUNNER_OPTIONS = ("block_size", "max_model_len", "attn_impl", "kv_dtype",
                  "weight_dtype", "weight_group_size")


def build_runner(model, *, dtype=None, mesh=None, data_axis: str = "data",
                 model_axis: str = "model", comm_dtype: str = "fp32",
                 **runner_kw) -> PagedModelRunner:
    """THE recipe "decoder Layer -> runner ready to serve", for every
    entry point (`create_engine`, `inference.create_serving_engine`, the
    router's replica factory, `restore_serving_engine`, the replica
    process): `runner_for` with `runner_kw`, then the floating
    parameters cast to `dtype`, then `shard(mesh, ...)`. Cast first,
    shard second: the device_put then ships the final serving dtype, not
    fp32 weights that get re-cast on the device."""
    if comm_dtype != "fp32" and mesh is None:
        raise ValueError(
            f"comm_dtype={comm_dtype!r} needs a tensor-parallel mesh — "
            "the quantized collective replaces the row-parallel "
            "allreduce, which only exists at tp > 1")
    runner = runner_for(model, **runner_kw)
    if dtype is not None:
        runner.params = {
            k: (v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                else v) for k, v in runner.params.items()}
    if mesh is not None:
        runner.shard(mesh, data_axis=data_axis, model_axis=model_axis,
                     comm_dtype=comm_dtype)
    return runner
