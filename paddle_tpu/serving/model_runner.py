"""Model runners: pure paged-KV step functions for the serving engine.

Reference: the reference serving stack splits "model" from "engine" the
same way — fluid/inference executes the network, the serving layer above
owns batching — with block_multihead_attention as the seam. Here a
runner adapts a decoder Layer (models.Llama, models.GPT) into two jitted
step functions over the shared page pool:

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
from paddle_tpu.models.generation import (
    _block_params, _layer_norm, _mlp, masked_cache_attention, paged_gather,
)
from paddle_tpu.models import deepseek_v3 as _dsv3
from paddle_tpu.models import olmo_hybrid as _olmo
from paddle_tpu.models import phi4flash as _phi
from paddle_tpu.models.llama import _rope_tables
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


_bucket_len = bucket_len          # pre-rename spelling (internal callers)


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


def _latent_attend(q, latent_new, layer_pools, tables, write_page,
                   write_off, pos_q, q_len, impl: str, scale: float,
                   v_lanes: int, runs=None):
    """paged_attend for a LATENT layer: one array a page, `[num_blocks,
    page, lanes]`, each token's row its compressed key whose first
    `v_lanes` lanes are also its value, shared by every query head (the
    absorbed form of latent attention; models/deepseek_v3.py). q: [B, T,
    n_h, lanes]; latent_new: [B, T, lanes]. Returns ([B, T, n_h,
    v_lanes], (pool,)): the per-head sums of p . value, which the caller
    takes through its value projection. "ragged" is the kernel over
    latent pages (a decode step: T == 1; `runs` its flags of which
    groups of the table are consecutive pages, where the caller's layers
    share one table), "reference" the gather path for any span."""
    (pool,) = layer_pools
    pool = pool.at[write_page, write_off].set(latent_new.astype(pool.dtype))
    B, T = q.shape[0], q.shape[1]
    if impl == "ragged":
        from paddle_tpu.ops.pallas.latent_paged_attention import \
            latent_paged_attention

        if T != 1:
            raise ValueError(f"the latent kernel is a decode kernel; span "
                             f"of {T} rows")
        out = latent_paged_attention(q[:, 0], pool, tables, pos_q,
                                     v_lanes=v_lanes, scale=scale, runs=runs)
        return out[:, None], (pool,)
    lat = pool[tables].reshape(B, -1, pool.shape[-1])           # [B, L, lanes]
    s = jnp.einsum("bthc,blc->bhtl", q, lat,
                   preferred_element_type=jnp.float32) * scale
    t_idx = jnp.arange(T, dtype=jnp.int32)
    visible = ((jnp.arange(lat.shape[1], dtype=jnp.int32)[None, None, :]
                <= pos_q[:, None, None] + t_idx[None, :, None])
               & (t_idx[None, :, None] < q_len[:, None, None]))  # [B, T, L]
    p = jax.nn.softmax(jnp.where(visible[:, None], s, -1e30), axis=-1)
    out = jnp.einsum("bhtl,blc->bthc", p.astype(lat.dtype),
                     lat[..., :v_lanes])
    return out.astype(q.dtype), (pool,)


def _sparse_latent_attend(q, latent_new, index, layer_pools, tables,
                          write_page, write_off, pos_q, q_len, impl: str,
                          scale: float, v_lanes: int, topk: int, runs=None):
    """paged_attend for a latent layer under a learned selection (DeepSeek
    Sparse Attention): TWO arrays a page behind one table, the latent rows
    and the indexer's keys `[num_blocks, page, index lanes]`. `index` is
    the indexer's view of the new tokens (models/deepseek_v3.index_project,
    padded to the page's lanes): queries [B, T, heads, lanes], the tokens'
    keys [B, T, lanes], the heads' weights [B, T, heads] float32. Each
    query row scores the index keys of its context, keeps the `topk` best
    (ties to the lower position) and attends over those rows alone.
    "ragged" is a decode step on the chip: the scan kernel over index
    pages, then the latent kernel's walk over every live page with each
    block folded under the selection (`topk_threshold`: exact, no list of
    rows is made; ops/pallas/sparse_latent_attention.py says why the walk
    and not a fetch by row). `runs`: (the scan's, the walk's) flags of
    consecutive pages. "reference" is the gather path for any span. Returns ([B, T,
    n_h, v_lanes], (pool, index pool))."""
    from paddle_tpu.ops.pallas import sparse_latent_attention as sla

    pool, ipool = layer_pools
    q_i, k_i, w_i = index
    pool = pool.at[write_page, write_off].set(latent_new.astype(pool.dtype))
    ipool = ipool.at[write_page, write_off].set(k_i.astype(ipool.dtype))
    B, T = q.shape[0], q.shape[1]
    if impl == "ragged":
        if T != 1:
            raise ValueError(f"the sparse latent kernels are decode "
                             f"kernels; span of {T} rows")
        scan_runs, walk_runs = runs if runs is not None else (None, None)
        with jax.named_scope("block/dsa/index"):
            scores = sla.paged_index_scores(q_i[:, 0], w_i[:, 0], ipool,
                                            tables, pos_q, runs=scan_runs)
        keys = scores.shape[1]
        if keys <= topk:                 # every visible key is chosen
            with jax.named_scope("block/dsa/attend"):
                out = sla.latent_paged_attention(
                    q[:, 0], pool, tables, pos_q, v_lanes=v_lanes,
                    scale=scale, runs=walk_runs)
        else:
            with jax.named_scope("block/dsa/select"):
                value, last = _dsv3.topk_threshold(scores, topk)
            with jax.named_scope("block/dsa/attend"):
                out = sla.latent_paged_attention(
                    q[:, 0], pool, tables, pos_q, v_lanes=v_lanes,
                    scale=scale, runs=walk_runs,
                    select=(scores, value, last))
        return out[:, None], (pool, ipool)
    L = tables.shape[1] * pool.shape[1]
    t_idx = jnp.arange(T, dtype=jnp.int32)
    visible = ((jnp.arange(L, dtype=jnp.int32)[None, None, :]
                <= pos_q[:, None, None] + t_idx[None, :, None])
               & (t_idx[None, :, None] < q_len[:, None, None]))  # [B, T, L]
    with jax.named_scope("block/dsa/index"):
        scores = jax.vmap(_dsv3.index_scores)(
            q_i, w_i, ipool[tables].reshape(B, L, ipool.shape[-1]))
    with jax.named_scope("block/dsa/select"):
        chosen = visible & _dsv3.topk_mask(
            jnp.where(visible, scores, -jnp.inf).reshape(B * T, L), topk
        ).reshape(B, T, L)
    with jax.named_scope("block/dsa/attend"):
        lat = pool[tables].reshape(B, L, pool.shape[-1])
        s = jnp.einsum("bthc,blc->bhtl", q, lat,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(chosen[:, None], s, -1e30), axis=-1)
        out = jnp.einsum("bhtl,blc->bthc", p.astype(lat.dtype),
                         lat[..., :v_lanes])
    return out.astype(q.dtype), (pool, ipool)


def paged_attend(q, k_new, v_new, layer_pools, tables, write_page,
                 write_off, pos_q, q_len, n_rep: int, impl: str,
                 shard_ctx=None, scale=None, v_lanes=None, runs=None,
                 kind: str = "kv", index=None, topk=None):
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
    ([B, T, n_h*d], new_layer_pools).

    `kind` says what the layer's pages hold, "kv" above. "latent": ONE
    array a page; k_new is the tokens' latent rows, v_new None, `scale`
    the softmax scale and `v_lanes` the value's lanes, `runs` the
    kernel's run flags (see _latent_attend, whose return it is).
    "latent+index": the latent rows and the indexer's keys, attended
    under the `topk` selection of `index` (see _sparse_latent_attend)."""
    if kind == "latent":
        return _latent_attend(q, k_new, layer_pools, tables, write_page,
                              write_off, pos_q, q_len, impl, scale, v_lanes,
                              runs)
    if kind == "latent+index":
        return _sparse_latent_attend(q, k_new, index, layer_pools, tables,
                                     write_page, write_off, pos_q, q_len,
                                     impl, scale, v_lanes, topk, runs)
    if kind != "kv":
        raise ValueError(f"paged_attend(kind={kind!r}); expected 'kv', "
                         "'latent' or 'latent+index'")
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
        # instrumented-comm counters (ISSUE 15): wire bytes PER SHARD
        # the row-parallel allreduces moved at the configured comm
        # dtype vs what fp32 psums would have moved for the same calls
        # (scale bytes counted on the int8 side) — host-side analytics
        # like the attention byte counters below. ISSUE 19 adds the
        # gather direction's pair (the column-parallel all-gather)
        self.tp_comm_bytes = 0.0
        self.tp_comm_bytes_fp32 = 0.0
        self.tp_gather_bytes = 0.0
        self.tp_gather_bytes_fp32 = 0.0
        # instrumented-pool counters: HBM bytes of KV pool the chosen
        # attention path touches (host-side analytics, CPU-countable) vs
        # what the gather path would have read for the same calls.
        # Sharded runners count PER-SHARD bytes (each shard walks only
        # its own kv-head slice, so sharded = single-device / tp)
        self.attn_kv_bytes_read = 0.0
        self.attn_kv_bytes_gather = 0.0
        # blocks of pages the ragged kernel's few-rows walks folded (one
        # layer's walk a launch: every layer walks the same), and those
        # of them folded in full, as a walk's edge blocks are; counted
        # on the host like the bytes (`ragged_block_counts`)
        self.ragged_blocks = 0
        self.ragged_edge_blocks = 0
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

    def _emit(self, out):
        """A single-pass step's outputs as its public entry returns
        them, `(logits, pools)`. A runner that counts (`COUNTS`) has its
        `_forward` return `(logits, pools, counts)`, the steps pass the
        third on as an output of the program, and it goes from here to
        whoever asked for it (`on_step_counts`), still on the device."""
        logits, pools, *counts = out
        if counts and self.on_step_counts is not None:
            self.on_step_counts(counts[0])
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
        self.attn_kv_bytes_read = 0.0
        self.attn_kv_bytes_gather = 0.0
        self.ragged_blocks = self.ragged_edge_blocks = 0
        self.tp_comm_bytes = 0.0
        self.tp_comm_bytes_fp32 = 0.0
        self.tp_gather_bytes = 0.0
        self.tp_gather_bytes_fp32 = 0.0

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


class LlamaRunner(PagedModelRunner):
    """Paged-step adapter for models.Llama (RMSNorm + RoPE + GQA + SwiGLU).

    Params come from jit.functionalize, so the runner serves exactly the
    weights of the Layer it was built from."""

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        params = functionalize(model).param_values()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.n_heads = cfg.num_heads
        self.n_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.vocab_size = cfg.vocab_size
        cos, sin = _rope_tables(self.max_model_len, self.head_dim,
                                cfg.rope_theta)
        self._rope_cos, self._rope_sin = cos, sin      # [L, d] fp32
        if self.weight_dtype != "fp32":
            names = []
            for i in range(self.num_layers):
                pre = f"layers.{i}."
                names += [pre + n for n in (
                    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
                    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                    "mlp.gate_proj.weight", "mlp.up_proj.weight",
                    "mlp.down_proj.weight")]
            if "lm_head.weight" in self.params:
                names.append("lm_head.weight")
            # embeddings stay floating (lookup table; tied heads reuse it)
            self._quantize_weights(names)

    def _param_specs(self, layout):
        """Megatron placements for the Llama block (ISSUE 7): column-
        wise Q/K/V and gate/up (each shard computes its own head /
        hidden slice), row-wise o_proj/down_proj (allreduce on the row
        output), vocab-sharded embeddings; norms replicated (default)."""
        col, row = layout.column_parallel(), layout.row_parallel()
        specs = {"embed_tokens.weight": layout.embeddings()}
        for i in range(self.num_layers):
            pre = f"layers.{i}."
            specs[pre + "self_attn.q_proj.weight"] = col
            specs[pre + "self_attn.k_proj.weight"] = col
            specs[pre + "self_attn.v_proj.weight"] = col
            specs[pre + "self_attn.o_proj.weight"] = row
            specs[pre + "mlp.gate_proj.weight"] = col
            specs[pre + "mlp.up_proj.weight"] = col
            specs[pre + "mlp.down_proj.weight"] = row
        if "lm_head.weight" in self.params:        # [H, V]: column-wise
            specs["lm_head.weight"] = col
        return specs

    def _rope(self, x, cos, sin):
        # same rotate-half convention as ops.rotary_embedding
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return (x * cos[:, :, None, :] + rot * sin[:, :, None, :]
                ).astype(x.dtype)

    def _rms(self, x, w, eps):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools):
        cfg = self.cfg
        B, T = tokens.shape
        d = self.head_dim
        impl = self._attn_impl_for(T)
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        cos = jnp.take(self._rope_cos, positions, axis=0)   # [B, T, d]
        sin = jnp.take(self._rope_sin, positions, axis=0)
        new_pools = []
        for i in range(cfg.num_layers):
            pre = f"layers.{i}."
            h = self._rms(x, params[pre + "input_layernorm.weight"],
                          cfg.rms_eps)
            q = self._mm(params, pre + "self_attn.q_proj.weight", h
                         ).reshape(B, T, self.n_heads, d)
            k = self._mm(params, pre + "self_attn.k_proj.weight", h
                         ).reshape(B, T, self.n_kv_heads, d)
            v = self._mm(params, pre + "self_attn.v_proj.weight", h
                         ).reshape(B, T, self.n_kv_heads, d)
            q = self._rope(q, cos, sin)
            k = self._rope(k, cos, sin)
            q, k, v = self._constrain_heads(q, k, v)
            out, layer = paged_attend(
                q, k, v, pools[i], tables, write_page,
                write_off, pos_q, q_lens, self.n_rep, impl,
                shard_ctx=self._shard_ctx)
            x = x + self._mm(params, pre + "self_attn.o_proj.weight", out)
            h = self._rms(x, params[pre + "post_attention_layernorm.weight"],
                          cfg.rms_eps)
            gate = self._mm(params, pre + "mlp.gate_proj.weight", h)
            up = self._mm(params, pre + "mlp.up_proj.weight", h)
            x = x + self._mm(params, pre + "mlp.down_proj.weight",
                             jax.nn.silu(gate) * up)
            new_pools.append(layer)
        x = self._rms(x, params["norm.weight"], cfg.rms_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed_tokens.weight"].T
        else:
            logits = self._mm(params, "lm_head.weight", x)
        return logits, new_pools


class GPTRunner(PagedModelRunner):
    """Paged-step adapter for models.GPT — reuses the functional block
    helpers the dense-cache generator already runs."""

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        params = functionalize(model).param_values()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.n_heads = cfg.num_heads
        self.n_kv_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.vocab_size = cfg.vocab_size
        if self.weight_dtype != "fp32":
            # GPT stores the fused QKV weight FLAT as [hidden, 3*nh*d]
            # (column order (3, nh, d)), so per-output-channel/group
            # abs-max quantization is exact per fused column; the
            # quantizers reject a raw (3, nh, d) tensor loudly (ISSUE 9
            # satellite, generalized to int4 in ISSUE 19) rather than
            # silently scaling over the qkv axis.
            # MoE blocks (mlp.gate present) keep their expert weights
            # floating — only dense matmul matrices quantize.
            names = []
            for i in range(self.num_layers):
                pre = f"blocks.{i}."
                names += [pre + "attn.qkv.weight", pre + "attn.out.weight"]
                if pre + "mlp.fc1.weight" in self.params:
                    names += [pre + "mlp.fc1.weight", pre + "mlp.fc2.weight"]
            if "lm_head.weight" in self.params:
                names.append("lm_head.weight")
            self._quantize_weights(names)

    def _param_specs(self, layout):
        """GPT placements (ISSUE 7). The fused attn.qkv weight keeps its
        (3, n_heads, d) column layout — a flat column shard would split
        across the q/k/v boundary — so it stays replicated and the
        sharded K/V POOLS carry the attention split instead (the head-
        sharded pool makes the whole attention block compute per-shard;
        out-proj then reduces row-wise). MLP and the vocab matrices
        shard the standard Megatron way."""
        col, row = layout.column_parallel(), layout.row_parallel()
        specs = {"wte.weight": layout.embeddings()}
        for i in range(self.num_layers):
            pre = f"blocks.{i}."
            specs[pre + "attn.out.weight"] = row
            specs[pre + "mlp.fc1.weight"] = col
            specs[pre + "mlp.fc1.bias"] = layout.bias_column()
            specs[pre + "mlp.fc2.weight"] = row
        if "lm_head.weight" in self.params:        # [H, V]: column-wise
            specs["lm_head.weight"] = col
        return specs

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools):
        cfg = self.cfg
        B, T = tokens.shape
        d = self.head_dim
        impl = self._attn_impl_for(T)
        # scope names reach the device trace: embed, block/attn, block/mlp,
        # final_norm, lm_head (the same as models/gpt.py gives training)
        with jax.named_scope("embed"):
            x = (jnp.take(params["wte.weight"], tokens, axis=0)
                 + jnp.take(params["wpe.weight"], positions, axis=0))
        new_pools = []
        for i in range(cfg.num_layers):
            p = _block_params(params, i)
            with jax.named_scope("block/attn"):
                h = _layer_norm(x, p["ln1.weight"], p["ln1.bias"])
                qkv = (self._mm(p, "attn.qkv.weight", h)
                       + p["attn.qkv.bias"]
                       ).reshape(B, T, 3, self.n_heads, d)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                q, k, v = self._constrain_heads(q, k, v)
                out, layer = paged_attend(
                    q, k, v, pools[i], tables, write_page,
                    write_off, pos_q, q_lens, 1, impl,
                    shard_ctx=self._shard_ctx)
                x = x + (self._mm(p, "attn.out.weight", out)
                         + p["attn.out.bias"])
            with jax.named_scope("block/mlp"):
                h = _layer_norm(x, p["ln2.weight"], p["ln2.bias"])
                fc1 = p.get("mlp.fc1.weight")
                if fc1 is not None and (
                        "mlp.fc1.weight" + SCALE_SUFFIX in p
                        or str(fc1.dtype).startswith("float8")):
                    # dense MLP with quantized weights (scale-carrying
                    # int8/int4 or scale-free fp8 — keyed on both, since
                    # fp8 has no scale entry): same gelu(fc1)+fc2 math,
                    # matmuls through the dequant epilogue (_mlp stays
                    # the untouched fp32 path so the default is
                    # bit-identical)
                    hm = jax.nn.gelu(self._mm(p, "mlp.fc1.weight", h)
                                     + p["mlp.fc1.bias"], approximate=True)
                    x = (x + self._mm(p, "mlp.fc2.weight", hm)
                         + p["mlp.fc2.bias"])
                else:
                    x = x + _mlp(p, h)
            new_pools.append(layer)
        with jax.named_scope("final_norm"):
            x = _layer_norm(x, params["ln_f.weight"], params["ln_f.bias"])
        with jax.named_scope("lm_head"):
            if "lm_head.weight" in params and (
                    "lm_head.weight" + SCALE_SUFFIX in params
                    or str(params["lm_head.weight"].dtype
                           ).startswith("float8")
                    or (self.comm_dtype != "fp32"
                        and "lm_head.weight" in self._gather_names)):
                # quantized head, or a head whose gather is routed through
                # the explicit quantized collective (ISSUE 19)
                logits = self._mm(params, "lm_head.weight", x)
            elif "lm_head.weight" in params:
                logits = jnp.einsum("bth,hv->btv", x,
                                    params["lm_head.weight"])
            else:
                logits = jnp.einsum("bth,vh->btv", x, params["wte.weight"])
        return logits, new_pools


class DeepseekV3Runner(PagedModelRunner):
    """Paged-step adapter for models.DeepseekV3ForCausalLM: latent
    attention over LATENT pages and a routed + shared expert layer, one
    rank's share of it (models/deepseek_v3.py has the equations and the
    functions; this class is their paging).

    A layer's cache is ONE array a page, `[num_blocks, page, lanes]`:
    per token c_kv | k_r (`cfg.latent_dim` values), allocated with its
    lanes rounded up to whole 128-lane tiles because the chip copies a
    page only as whole tiles (576 -> 640; PERF.md). A configuration with
    an indexer (`cfg.index_topk`: DeepSeek-V3.2) names a SECOND array a
    page behind the same table, the indexer's key of each token, and
    every query row attends over the `index_topk` keys it scored best
    (`_sparse_latent_attend`; a prompt's span in the expanded form under
    `selection_mask`, its heads a group at a time); it counts the keys
    scored and kept beside the rest. Two attention paths
    from one set of weights, chosen from shapes: ONE sequence's span of
    several rows (a prefill bucket, a chunk) runs the EXPANDED form
    (per-head keys and values rebuilt from the table's latent rows,
    blocked over query and key rows), anything else the ABSORBED form
    through `paged_attend`: the latent decode kernel where `attn_impl`
    resolves to "ragged" (a decode step on a TPU), the gather path
    elsewhere. `weight_dtype="int8"` / "fp8" convert the dense matrices
    (the experts and the router stay floating); latent pages come in the
    stated dtype only. The expert layers count (tokens routed, pairs
    computed here, held experts touched) and so does the latent kernel's
    walk (groups of pages copied, those copied as one run): an output of
    every single-pass step, handed to `on_step_counts`."""

    COUNTS = ("moe_tokens_routed", "moe_local_pairs", "moe_experts_touched",
              "latent_copy_groups", "latent_run_groups")
    # what a runner with an indexer counts besides
    SPARSE_COUNTS = ("dsa_keys_scored", "dsa_keys_selected")
    HEAD_ROWS = True

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        if quant.get("kv_dtype", "fp32") != "fp32":
            raise ValueError(
                f"kv_dtype={quant['kv_dtype']!r}: latent pages come in the "
                "model's stated dtype only (no quantized rung for them yet)")
        if quant.get("weight_dtype") == "int4":
            raise ValueError("weight_dtype='int4' is not wired for the "
                             "latent-attention runner (int8 and fp8 are)")
        params = functionalize(model).param_values()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.n_heads = cfg.num_attention_heads
        self.vocab_size = cfg.vocab_size
        # a page's lanes: the latent row in whole 128-lane tiles
        self.page_lanes = -(-cfg.latent_dim // 128) * 128
        self.sparse = cfg.index_topk is not None
        if self.sparse:
            self.index_lanes = -(-cfg.index_head_dim // 128) * 128
            self.COUNTS = self.COUNTS + self.SPARSE_COUNTS
        self._rope_cos, self._rope_sin = _dsv3.rope_tables(
            cfg, self.max_model_len)                   # [L, rope] fp32
        self._scale = _dsv3.softmax_scale(cfg)
        if self.weight_dtype != "fp32":
            names = ["lm_head.weight"]
            for i in range(self.num_layers):
                pre = f"layers.{i}."
                names += [pre + "self_attn." + n + ".weight" for n in (
                    "q_a_proj", "q_b_proj", "kv_a_proj_with_mqa",
                    "kv_b_proj", "o_proj") + (
                        ("indexer.wq_b", "indexer.wk",
                         "indexer.weights_proj") if self.sparse else ())]
                mlp = pre + ("mlp." if cfg.is_dense(i)
                             else "mlp.shared_experts.")
                names += [mlp + n + ".weight" for n in (
                    "gate_proj", "up_proj", "down_proj")]
            self._quantize_weights(names)

    def page_layout(self):
        layout = [((self.page_lanes,), self.dtype)]
        if self.sparse:
            layout.append(((self.index_lanes,), self.dtype))
        return layout

    def _param_specs(self, layout):
        raise NotImplementedError(
            "DeepseekV3Runner serves one chip's share; exchanging experts "
            "and splitting latent pages over a mesh is not built")

    def _attn_impl_for(self, q_len_bucket: int) -> str:
        """The ABSORBED paths: the latent kernel for a decode step where
        a kernel is wanted ("auto" on a TPU, or "ragged": interpret mode
        off it), else the gather reference. (One sequence's longer span
        takes the expanded form whatever this says: `_forward`.)"""
        want_kernel = (self.attn_impl == "ragged"
                       or (self.attn_impl == "auto"
                           and jax.default_backend() == "tpu"))
        impl = "ragged" if want_kernel and q_len_bucket == 1 else "reference"
        key = (q_len_bucket, impl)
        if key not in self._impl_logged:
            self._impl_logged.add(key)
            logger.info("serving attention impl: latent %s (q_len bucket "
                        "%d, %d heads over %d lanes, attn_impl=%s)", impl,
                        q_len_bucket, self.n_heads, self.page_lanes,
                        self.attn_impl)
        return impl

    def _kv_page_bytes(self) -> int:
        """A page's bytes in every layer; under a selection both its arrays
        (the scan reads every live page's index keys, the walk its latent
        rows: each block is folded under the selection, none is skipped)."""
        lanes = self.page_lanes + (self.index_lanes if self.sparse else 0)
        return (self.num_layers * self.block_size * lanes
                * np.dtype(self.dtype).itemsize)

    def _fold_block_pages(self, span: int) -> int:
        return 0        # the latent kernel's walk, not the ragged one's

    def _w(self, params, name):
        """A named matrix as its floating self (dequantized where
        `_quantize_weights` converted it): the absorbed form multiplies
        by slices of kv_b_proj, not by the whole of it."""
        w, s = params[name], params.get(name + SCALE_SUFFIX)
        dt = params["embed_tokens.weight"].dtype
        return w.astype(dt) if s is None else w.astype(dt) * s.astype(dt)

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools, head_rows=None):
        cfg, m = self.cfg, _dsv3
        B, T = tokens.shape
        lanes, nh = self.page_lanes, self.n_heads
        impl = self._attn_impl_for(T)
        expanded = B == 1 and T > 1
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        cos = jnp.take(self._rope_cos, positions, axis=0)      # [B, T, rope]
        sin = jnp.take(self._rope_sin, positions, axis=0)
        valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < q_lens[:, None]).reshape(B * T)
        experts = jnp.zeros((3,), jnp.int32)
        walked = jnp.zeros((2,), jnp.int32)
        runs = None
        if impl == "ragged" and not expanded:
            # which groups of the table are runs of consecutive pages: the
            # layers share one table, so once for the step's program
            from paddle_tpu.ops.pallas import latent_paged_attention as lpa

            pool = pools[0][0]
            _, group = lpa.walk_shape(nh, pool, cfg.kv_lora_rank)
            runs = lpa.page_runs(tables, group)
            walked = cfg.num_hidden_layers * lpa.walked_groups(
                runs, pos_q, self.block_size, group, tables.shape[1])
            if self.sparse:
                # the scan over index pages walks in groups of its own
                from paddle_tpu.ops.pallas.sparse_latent_attention import \
                    scan_shape

                runs = (lpa.page_runs(tables, scan_shape(pools[0][1])[1]),
                        runs)
        new_pools = []
        for i in range(cfg.num_hidden_layers):
            pre = f"layers.{i}."
            if self.sparse:
                x, layer = self._sparse_attention(
                    params, pre, x, cos, sin, pools[i], tables, write_page,
                    write_off, pos_q, q_lens, impl, expanded, runs)
            else:
                with jax.named_scope("block/mla"):
                    h = m.rms_norm(x, params[pre + "input_layernorm.weight"],
                                   cfg.rms_norm_eps)
                    qn, qr, lat, _ = m.mla_project(cfg, params, pre, h, cos,
                                                   sin, mm=self._mm)
                    lat = jnp.pad(lat, ((0, 0), (0, 0),
                                        (0, lanes - cfg.latent_dim)))
                    w_kvb = self._w(params,
                                    pre + "self_attn.kv_b_proj.weight")
                    if expanded:
                        (pool,) = pools[i]
                        pool = pool.at[write_page, write_off].set(
                            lat.astype(pool.dtype))
                        o = m.expanded_attention(
                            cfg, qn[0], qr[0],
                            pool[tables[0]].reshape(-1, lanes), w_kvb,
                            pos_q[0], q_lens[0])[None]
                        layer = (pool,)
                    else:
                        o, layer = paged_attend(
                            m.absorb_queries(cfg, qn, qr, w_kvb, lanes), lat,
                            None, pools[i], tables, write_page, write_off,
                            pos_q, q_lens, nh, impl, scale=self._scale,
                            v_lanes=cfg.kv_lora_rank, runs=runs,
                            kind="latent")
                        o = m.absorb_outputs(cfg, o, w_kvb)
                    x = x + self._mm(params, pre + "self_attn.o_proj.weight",
                                     o)
            h = m.rms_norm(x, params[pre + "post_attention_layernorm.weight"],
                           cfg.rms_norm_eps).reshape(B * T, -1)
            if cfg.is_dense(i):
                with jax.named_scope("block/mlp"):
                    f = m.dense_ffn(params, pre + "mlp.", h, self._mm)
            else:
                f, c = m.moe_ffn(cfg, params, pre + "mlp.", h, valid,
                                 self._mm)
                experts = experts + c
            x = x + f.reshape(B, T, -1)
            new_pools.append(layer)
        with jax.named_scope("final_norm"):
            x = m.rms_norm(x, params["norm.weight"], cfg.rms_norm_eps)
            if head_rows is not None:
                x = jnp.take_along_axis(x, head_rows[:, None, None], axis=1)
        with jax.named_scope("lm_head"):
            logits = self._mm(params, "lm_head.weight", x)
        counts = [experts, walked]
        if self.sparse:
            # every live query row scored its context and kept the best
            t_idx = jnp.arange(T, dtype=jnp.int32)[None, :]
            context = jnp.where(t_idx < q_lens[:, None],
                                pos_q[:, None] + t_idx + 1, 0)
            counts.append(cfg.num_hidden_layers * jnp.stack(
                [jnp.sum(context),
                 jnp.sum(jnp.minimum(context, cfg.index_topk))]))
        return logits, new_pools, jnp.concatenate(counts)

    def _sparse_attention(self, params, pre, x, cos, sin, layer_pools,
                          tables, write_page, write_off, pos_q, q_lens, impl,
                          expanded, runs):
        """One layer's attention under the indexer's selection, residual
        added: (x, the layer's (latent pool, index pool))."""
        cfg, m = self.cfg, _dsv3
        lanes = self.page_lanes
        pad = lambda a, n: jnp.pad(
            a, ((0, 0),) * (a.ndim - 1) + ((0, n - a.shape[-1]),))
        with jax.named_scope("block/mla"):
            h = m.rms_norm(x, params[pre + "input_layernorm.weight"],
                           cfg.rms_norm_eps)
            # (a prompt's span makes its queries a group of heads at a
            # time and leaves these to the compiler's dead-code pass)
            qn, qr, lat, c_q = m.mla_project(cfg, params, pre, h, cos, sin,
                                             mm=self._mm)
            lat = pad(lat, lanes)
            w_kvb = self._w(params, pre + "self_attn.kv_b_proj.weight")
        with jax.named_scope("block/dsa/index"):
            q_i, k_i, w_i = m.index_project(cfg, params, pre, h, c_q, cos,
                                            sin, mm=self._mm)
            q_i, k_i = pad(q_i, self.index_lanes), pad(k_i, self.index_lanes)
        if expanded:
            pool, ipool = layer_pools
            pool = pool.at[write_page, write_off].set(lat.astype(pool.dtype))
            ipool = ipool.at[write_page, write_off].set(
                k_i.astype(ipool.dtype))
            with jax.named_scope("block/dsa/select"):
                chosen = m.selection_mask(
                    cfg, q_i[0], w_i[0],
                    ipool[tables[0]].reshape(-1, self.index_lanes),
                    pos_q[0], q_lens[0])
            with jax.named_scope("block/dsa/attend"):
                o = m.sparse_expanded_attention(
                    cfg, c_q[0], cos[0], sin[0],
                    pool[tables[0]].reshape(-1, lanes), chosen,
                    self._w(params, pre + "self_attn.q_b_proj.weight"),
                    w_kvb, self._w(params, pre + "self_attn.o_proj.weight"),
                    pos_q[0], q_lens[0])[None]
            return x + o, (pool, ipool)
        o, layer = paged_attend(
            m.absorb_queries(cfg, qn, qr, w_kvb, lanes), lat, None,
            layer_pools, tables, write_page, write_off, pos_q, q_lens,
            self.n_heads, impl, scale=self._scale, v_lanes=cfg.kv_lora_rank,
            runs=runs, kind="latent+index", index=(q_i, k_i, w_i),
            topk=cfg.index_topk)
        with jax.named_scope("block/mla"):
            o = m.absorb_outputs(cfg, o, w_kvb)
            return x + self._mm(params, pre + "self_attn.o_proj.weight",
                                o), layer


class OlmoHybridRunner(PagedModelRunner):
    """Paged-step adapter for models.OlmoHybridForCausalLM: pages for the
    full-attention layers, a STATE SLOT per sequence for the Gated
    DeltaNet layers (models/olmo_hybrid.py has the equations and the
    functions; this class is their caching).

    `pools` is the pair (pages, states). pages: the (k, v) arrays of the
    full layers only, through `paged_attend` like any dense runner's,
    their heads rounded up to what the chip copies as whole tiles (30 ->
    32 below 32 bits: allocated so, never padded per call); a span longer
    than ATTN_SPAN rows attends in pieces, its keys written first. states:
    per linear layer `(state [slots, d_k, H * d_v] float32, conv [slots,
    (taps - 1) * conv_dim])`, a sequence's row its decode slot. A decode
    step (one token a row) advances rows 0..B-1 in place where the row is
    LIVE, which is read off the write indices: a dead slot's all-scratch
    table and a horizon's frozen row (`write_mask`) both write to the
    scratch page. The update is the Pallas kernel where `attn_impl`
    resolves to "ragged" (a TPU, or forced), plain jnp elsewhere. A
    prefill or a chunk of one (one sequence, `slot`) runs the chunked form
    from the slot's state, or from zeros where it starts at position 0:
    the program that first writes a slot resets it; padding rows change
    nothing (beta = 0, no decay). The steps count on the device
    (`COUNTS`): live rows x linear layers a decode step advanced, a
    prefill's real tokens and computed positions, slots reset.

    What needs a copy or a rollback of a state is not built: spans of
    several rows for several sequences (`ragged_step`, speculation) raise
    here, and ServingEngine refuses the options that need them by name."""

    COUNTS = ("delta_decode_seq_steps", "delta_prefill_tokens",
              "delta_prefill_positions", "state_slot_resets")
    HEAD_ROWS = True
    ATTN_SPAN = 128      # query rows of one call of the attention kernel

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            _page_copy_heads

        cfg = model.cfg
        if quant.get("weight_dtype") == "int4":
            raise ValueError("weight_dtype='int4' is not wired for the "
                             "hybrid runner (int8 and fp8 are)")
        if quant.get("kv_dtype", "fp32") not in ("fp32", "fp8"):
            raise ValueError(
                f"kv_dtype={quant['kv_dtype']!r}: the hybrid runner's paged "
                "layers come in the model's dtype or in fp8 (a long span "
                "writes its keys once and attends in pieces, which the "
                "int8 and mixed write paths are not built for)")
        params = functionalize(model).param_values()
        if cfg.init == "deferred":
            # the Layer was the weights' way in: they live here now
            model.release_weights()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.linear_layers = [i for i in range(self.num_layers)
                              if cfg.is_linear(i)]
        self.n_heads = self.n_kv_heads = cfg.num_attention_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        # heads of a page: what the chip copies as whole tiles
        self.page_heads = _page_copy_heads(self.n_heads,
                                           self._kv_itemsize())
        self._rope = _olmo.rope_tables(cfg, self.max_model_len)
        if self.weight_dtype != "fp32":
            names = ["lm_head.weight"]
            for i in range(self.num_layers):
                pre = f"layers.{i}."
                mixer = ("linear_attn.", "qkvgo") if cfg.is_linear(i) \
                    else ("self_attn.", "qkvo")
                names += [pre + mixer[0] + n + "_proj.weight"
                          for n in mixer[1]]
                names += [pre + "mlp." + n + "_proj.weight"
                          for n in ("gate", "up", "down")]
            self._quantize_weights(names)

    def page_layout(self):
        return kv_pair_layout(self.page_heads, self.head_dim, self.dtype)

    def state_layout(self):
        cfg = self.cfg
        return (len(self.linear_layers), [
            ((cfg.linear_key_head_dim, cfg.linear_num_value_heads
              * cfg.linear_value_head_dim), jnp.float32),
            (((cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim,),
             self.dtype)])

    def _param_specs(self, layout):
        raise NotImplementedError(
            "OlmoHybridRunner serves one chip; splitting state slots over "
            "a mesh is not built")

    def _kv_page_bytes(self) -> int:
        """Bytes a page costs the attention of the layers that page."""
        full = self.num_layers - len(self.linear_layers)
        return (2 * full * self.block_size * self.page_heads * self.head_dim
                * self._kv_itemsize())

    def _kv_itemsize(self) -> int:
        """Bytes of a cached value: fp8 pages, or the model's dtype."""
        return 1 if self.kv_dtype == "fp8" else np.dtype(self.dtype).itemsize

    @staticmethod
    def _starts_fresh(pos_q):
        """A span that starts at position 0 starts from a zero state, not
        from what the slot's last holder left."""
        return pos_q[0] == 0

    def _delta_kernel(self) -> bool:
        return self.attn_impl == "ragged" or (
            self.attn_impl == "auto" and jax.default_backend() == "tpu")

    # ------------------------------------------------------------- steps

    def _prefill_step(self, params, tokens, table, real_len, start_slot,
                      pools):
        """The chassis's prefill with the sequence's state slot beside its
        start position (`prefill_chunk(..., slot=)`; slot 0 where the
        caller named none: the oracle's private pool)."""
        start_slot = jnp.reshape(start_slot, (-1,))
        slot = start_slot[1:] if start_slot.shape[0] > 1 \
            else jnp.zeros((1,), jnp.int32)
        return super()._prefill_step(params, tokens, table, real_len,
                                     start_slot[0], pools, slots=slot)

    def _attend(self, q, k, v, layer_pools, tables, write_page, write_off,
                pos_q, q_lens, impl):
        """One full layer's attention through the pages: the span's keys
        written once, then its query rows ATTN_SPAN at a time."""
        B, T = q.shape[:2]
        pad = ((0, 0), (0, 0), (0, self.page_heads - self.n_heads), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        span = min(T, self.ATTN_SPAN) if impl == "ragged" else T
        out = []
        for lo in range(0, T, span):
            wrote = lo > 0            # later pieces write nothing
            cut = lambda a: a[:, :0] if wrote else a
            o, layer_pools = paged_attend(
                q[:, lo:lo + span], cut(k), cut(v), layer_pools, tables,
                cut(write_page), cut(write_off), pos_q + lo,
                jnp.clip(q_lens - lo, 0, span), 1, impl)
            out.append(o)
        o = out[0] if len(out) == 1 else jnp.concatenate(out, 1)
        o = o.reshape(B, T, self.page_heads, self.head_dim)
        return o[:, :, :self.n_heads].reshape(B, T, -1), layer_pools

    def _linear(self, params, pre, x, valid, fresh, slots, layer_states):
        """One Gated DeltaNet mixer on x [B, T, hidden] against its state
        arrays. T == 1: a decode step, row b at slot b. T > 1: one
        sequence (B == 1) at `slots[0]`."""
        from paddle_tpu.ops import gated_delta as gd
        from paddle_tpu.ops.pallas import gated_delta_decode as gk

        cfg = self.cfg
        B, T = x.shape[:2]
        H, taps = cfg.linear_num_value_heads, cfg.linear_conv_kernel_dim
        state, conv = layer_states
        rows = _olmo.conv_inputs(params, pre, x, self._mm)     # [B, T, C]
        w = _olmo.conv_weights(params, pre)
        if T == 1:
            live = valid[:, 0]
            before = conv[:B].reshape(B, taps - 1, -1)
            rows = jnp.concatenate([before, rows.astype(conv.dtype)], 1)
            q, k, v, g, beta = _olmo.delta_inputs(
                cfg, params, pre, x[:, 0], _olmo.conv_silu(rows, w)[:, 0],
                self._mm)
            conv = jax.lax.dynamic_update_slice(conv, jnp.where(
                live[:, None], rows[:, 1:].reshape(B, -1), conv[:B]), (0, 0))
            if self._delta_kernel():
                o, state = gk.gated_delta_decode(state, q, k, v, g, beta,
                                                 live)
            else:
                on = live[:, None]
                o, new = gd.gated_delta_step(
                    gk.head_form(state[:B], H), q, k, v,
                    jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0))
                state = jax.lax.dynamic_update_slice(
                    state, gk.pool_form(new), (0, 0, 0))
            return _olmo.gated_output(cfg, params, pre, x[:, 0], o,
                                      self._mm)[:, None], (state, conv)
        if B != 1:
            raise NotImplementedError(
                "spans of several rows for several sequences at once (the "
                "fused ragged step, speculative verify spans) are not "
                "built for recurrent state")
        slot = slots[0]
        before = jnp.where(fresh, 0, conv[slot]).reshape(taps - 1, -1)
        rows = jnp.concatenate([before, rows[0].astype(conv.dtype)], 0)
        q, k, v, g, beta = _olmo.delta_inputs(
            cfg, params, pre, x[0], _olmo.conv_silu(rows, w), self._mm)
        on = valid[0][:, None]
        o, new = gd.gated_delta_chunked(
            q, k, v, jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0),
            jnp.where(fresh, 0.0, gk.head_form(state[slot], H)))
        n_real = jnp.sum(valid[0].astype(jnp.int32))
        # what the next token's convolution reads: the last real rows
        kept = jax.lax.dynamic_slice_in_dim(rows, n_real, taps - 1, 0)
        state = jax.lax.dynamic_update_index_in_dim(
            state, gk.pool_form(new), slot, 0)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, kept.reshape(-1), slot, 0)
        return _olmo.gated_output(cfg, params, pre, x[0], o,
                                  self._mm)[None], (state, conv)

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools, head_rows=None, slots=None):
        cfg, m = self.cfg, _olmo
        B, T = tokens.shape
        impl = self._attn_impl_for(T)
        pages, states = pools
        # a position is real where its write lands on a page of its own
        valid = write_page != SCRATCH_PAGE                          # [B, T]
        fresh = self._starts_fresh(pos_q)
        if slots is None:
            slots = jnp.arange(B, dtype=jnp.int32)
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        cos_sin = None if self._rope is None else tuple(
            jnp.take(t, positions, axis=0) for t in self._rope)
        new_pages, new_states = [], []
        for i in range(cfg.num_hidden_layers):
            pre = f"layers.{i}."
            if cfg.is_linear(i):
                with jax.named_scope("block/delta"):
                    mix, layer = self._linear(
                        params, pre + "linear_attn.", x, valid, fresh, slots,
                        states[len(new_states)])
                new_states.append(layer)
            else:
                with jax.named_scope("block/attention"):
                    a = pre + "self_attn."
                    q, k, v = m.attention_qkv(cfg, params, a, x, cos_sin,
                                              self._mm)
                    o, layer = self._attend(
                        q, k, v, pages[len(new_pages)], tables, write_page,
                        write_off, pos_q, q_lens, impl)
                    mix = self._mm(params, a + "o_proj.weight", o)
                new_pages.append(layer)
            x = x + m.rms_norm(
                mix, params[pre + "post_attention_layernorm.weight"],
                cfg.rms_norm_eps)
            with jax.named_scope("block/mlp"):
                f = m.swiglu(params, pre + "mlp.", x, self._mm)
            x = x + m.rms_norm(
                f, params[pre + "post_feedforward_layernorm.weight"],
                cfg.rms_norm_eps)
        with jax.named_scope("final_norm"):
            x = m.rms_norm(x, params["norm.weight"], cfg.rms_norm_eps)
            if head_rows is not None:
                x = jnp.take_along_axis(x, head_rows[:, None, None], axis=1)
        with jax.named_scope("lm_head"):
            logits = self._mm(params, "lm_head.weight", x)
        real = jnp.sum(valid.astype(jnp.int32))
        zero = jnp.int32(0)
        counts = jnp.stack(
            [real * len(self.linear_layers), zero, zero, zero] if T == 1
            else [zero, real, jnp.int32(B * T), fresh.astype(jnp.int32)])
        return logits, (new_pages, new_states), counts


class Phi4FlashRunner(PagedModelRunner):
    """Paged-step adapter for models.Phi4FlashForCausalLM, whose layers keep
    a cache of four kinds (models/phi4flash.py has the equations and the
    functions; this class is their caching). It names page GROUPS to the
    pool (`page_groups`), and `pools` is the triple (pages, states, ring):

    pages   the ONE full-attention layer's keys and values, whole context,
            through the block table as any dense runner's (the "full"
            group: `num_blocks` counts its pages). The cross-attention
            layers of the cross-decoder own no cache: they read these pages
            with the same kernel and write nothing.
    ring    the window layers' pages (the "window" group, `WindowGroup`):
            only a sequence's last `sliding_window` positions. Its table
            columns ride behind the full group's in the one block table a
            step takes, `[pages | ring pages | ring base]`; positions
            there are the ring's own (less `base * block_size`), and the
            kernel is given the first position still inside the window.
    states  per Mamba layer `(state [slots, d_state, d_inner] float32, conv
            [slots, (taps - 1) * d_inner])`, a sequence's row its decode
            slot, as OlmoHybridRunner keeps its delta rule's.

    A page is kept as ROWS, `[block_size * pairs, 2 head_dim]` (10 pairs of
    128 lanes at the published widths: whole tiles, where `[16, 10, 128]`
    would be allocated as 16 pairs). The differential pairing costs no
    second walk: a query head padded to its pair's width scores its own key
    head against the pair (`models.phi4flash.pair_queries`), so ONE pass of
    the ragged kernel over pair heads gives both softmaxes' products.

    A decode step (one token a row) runs every layer; the gated memory
    units read the memory layer's scan output of the same step. A prefill,
    or a chunk of one (one sequence, `slot`), runs in pieces of
    PREFILL_SPAN rows: layers up to the full layer's key/value write for
    EVERY row, the full layer's attention and the whole cross-decoder for
    the chunk's LAST row only (nothing after that write keeps anything of
    an earlier row, so this is exact: tests hold it equal to the unskipped
    forward). Its window attention is dense over the chunk's own keys and
    the `window - 1` before them, which the pieces hand on as an array:
    loaded from the ring before the first piece (`ring=(before, after)`,
    the group's rows as the engine found and left them), stored into it
    after the last. The steps count on the device (`COUNTS`).

    Precision: weights, pages and convolution rows in the model's dtype;
    the scan state, dt, exp(dt A), the softmax, lambda and both norms'
    statistics float32. What needs a copy or a rollback of a state or of
    the ring is not built: spans of several rows for several sequences
    raise here, and ServingEngine refuses the options by name."""

    COUNTS = ("ssm_decode_seq_steps", "ssm_prefill_tokens",
              "cross_rows_skipped", "state_slot_resets")
    HEAD_ROWS = True
    ROW_PAGES = True
    PREFILL_SPAN = 2048    # rows of one piece of a prefill
    SHORT_CHUNK = 256      # a chunk up to this long is one piece of its bucket
    WINDOW_ROWS = 512      # query rows of one block of its window attention
    EXTRA_STEPS = {"phi_body": ("_piece_body", 6, ()),
                   "phi_head": ("_piece_head", None, ()),
                   "phi_ring_load": ("_ring_load", None, ()),
                   "phi_ring_store": ("_ring_store", 0, ())}

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        if quant.get("weight_dtype") == "int4":
            raise ValueError("weight_dtype='int4' is not wired for the "
                             "Phi-4-flash runner (int8 and fp8 are)")
        if quant.get("kv_dtype", "fp32") not in ("fp32", "fp8"):
            raise ValueError(
                f"kv_dtype={quant['kv_dtype']!r}: row pages come in the "
                "model's dtype or in fp8")
        params = functionalize(model).param_values()
        if cfg.init == "deferred":
            # the Layer was the weights' way in: they live here now
            model.release_weights()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        # the geometry the attention kernel sees: PAIR heads
        self.n_heads = cfg.num_attention_heads
        self.n_kv_heads = cfg.kv_pairs
        self.head_dim = 2 * cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.kinds = [cfg.kind(i) for i in range(self.num_layers)]
        self.table_pages = -(-self.max_model_len // block_size)
        if self.weight_dtype != "fp32":
            per_kind = {
                "mamba": ["mamba." + n for n in ("in_proj", "x_proj",
                                                 "dt_proj", "out_proj")],
                "gmu": ["gmu.in_proj", "gmu.out_proj"],
                "cross": ["attn.q_proj", "attn.o_proj"]}
            names = []
            for i, kind in enumerate(self.kinds):
                names += [f"layers.{i}.{n}.weight" for n in per_kind.get(
                    kind, ["attn.qkv_proj", "attn.o_proj"])
                    + ["mlp.gate_up_proj", "mlp.down_proj"]]
            self._quantize_weights(names)

    def page_layout(self):
        return kv_pair_layout(self.n_kv_heads, self.head_dim, self.dtype)

    def page_groups(self):
        """The pool's page groups by name: layers that keep their whole
        context, and (layers, window) that keep a window of it."""
        return {"full": self.kinds.count("full"),
                "window": (self.kinds.count("window"),
                           self.cfg.sliding_window)}

    def state_layout(self):
        cfg = self.cfg
        return (self.kinds.count("mamba"), [
            ((cfg.mamba_d_state, cfg.d_inner), jnp.float32),
            (((cfg.mamba_d_conv - 1) * cfg.d_inner,), self.dtype)])

    def _param_specs(self, layout):
        raise NotImplementedError(
            "Phi4FlashRunner serves one chip; splitting state slots and "
            "page groups over a mesh is not built")

    def _kv_itemsize(self) -> int:
        return 1 if self.kv_dtype == "fp8" else np.dtype(self.dtype).itemsize

    def _kv_page_bytes(self) -> int:
        """Bytes a page of the full group costs a step's attention: the
        full layer and every cross layer read it."""
        readers = self.kinds.count("full") + self.kinds.count("cross")
        return (2 * readers * self.block_size * self.n_kv_heads
                * self.head_dim * self._kv_itemsize())

    def _account_decode(self, pos, tables) -> None:
        """The full group's walk as any runner's, then the window
        group's: the ring's own positions from its base (the table's last
        column), bounded where the window begins, as `_forward` has it."""
        super()._account_decode(pos, tables)
        if self._attn_impl_for(1) == "ragged":
            rel = pos - tables[:, -1] * self.block_size
            self._account_blocks(
                rel, np.ones_like(pos), 1,
                np.maximum(rel - (self.cfg.sliding_window - 1), 0))

    def _scan_kernel(self) -> bool:
        return self.attn_impl == "ragged" or (
            self.attn_impl == "auto" and jax.default_backend() == "tpu")

    # ----------------------------------------------------- cache plumbing

    def _split_tables(self, tables):
        """[.., pages | ring pages | ring base] -> the three."""
        P = self.table_pages
        if tables.shape[-1] < P + 2:
            raise ValueError(
                f"a block table of {tables.shape[-1]} columns holds no "
                f"window group behind {P} pages (max_model_len "
                f"{self.max_model_len}): build it with "
                "WindowGroup.extend_tables")
        return tables[..., :P], tables[..., P:-1], tables[..., -1]

    def _write_rows(self, pool, page, off, new):
        """page, off [...]; new [..., pairs, lanes] -> the row pool with
        those tokens' rows written: a page's rows are key-major, so a
        token's pairs are ONE window of consecutive rows."""
        n = self.n_kv_heads
        at = jnp.stack([page, off * n], -1).reshape(-1, 2)
        return jax.lax.scatter(
            pool, at, new.astype(pool.dtype).reshape(-1, n, new.shape[-1]),
            jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1, 2), inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0, 1)))

    def _take_rows(self, pool, page, off):
        """page, off [n] -> those tokens' rows [n, pairs, lanes]."""
        n = self.n_kv_heads
        return jax.lax.gather(
            pool, jnp.stack([page, off * n], -1),
            jax.lax.GatherDimensionNumbers(
                offset_dims=(1, 2), collapsed_slice_dims=(0,),
                start_index_map=(0, 1)),
            slice_sizes=(1, n, pool.shape[-1]))

    def _attend(self, q, layer_pools, table, pos, q_len, lower=None):
        """q [B, heads, head_dim]: one row a sequence, at `pos` of the
        table's own positions -> [B, heads, 2 head_dim]: each head's
        softmax applied to its pair's values."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention, ragged_reference,
        )

        fn = (ragged_paged_attention if self._attn_impl_for(1) == "ragged"
              else ragged_reference)
        return fn(_phi.pair_queries(q)[:, None], *layer_pools, table, pos,
                  q_len, scale=self.cfg.head_dim ** -0.5, lower=lower,
                  kv_heads=self.n_kv_heads)[:, 0]

    def _ring_at(self, row, end):
        """(page, offset) of the positions [end - (W - 1), end) through a
        window group's `row`; positions before 0 go to the scratch page."""
        W, bs = self.cfg.sliding_window, self.block_size
        pos = end - (W - 1) + jnp.arange(W - 1, dtype=jnp.int32)
        at = jnp.clip(pos // bs - row[-1], 0, row.shape[0] - 2)
        return jnp.where(pos >= 0, row[at], SCRATCH_PAGE), pos % bs

    def _ring_load(self, ring, row, start):
        """The window layers' keys and values of positions [start - (W -
        1), start) as arrays ([layers, W - 1, pairs, lanes] each; rows of
        positions before 0 are whatever the scratch page holds, and
        masked)."""
        page, off = self._ring_at(row, start)
        take = lambda pool: self._take_rows(pool, page, off).astype(
            self.dtype)
        return (jnp.stack([take(k) for k, _ in ring]),
                jnp.stack([take(v) for _, v in ring]))

    def _ring_store(self, ring, tail, row, end):
        """The ring with the positions [end - (W - 1), end) of `tail`
        written through `row` (the group's row after the chunk)."""
        page, off = self._ring_at(row, end)
        return [(self._write_rows(k, page, off, tail[0][i]),
                 self._write_rows(v, page, off, tail[1][i]))
                for i, (k, v) in enumerate(ring)]

    # ------------------------------------------------------------ layers

    def _mamba(self, params, pre, u, valid, fresh, slots, layer_states):
        """One Mamba mixer on u [B, T, hidden] against its state arrays.
        T == 1: a decode step, row b at slot b. T > 1: one sequence (B ==
        1) at `slots[0]`. Returns (out, the memory y float32, states)."""
        from paddle_tpu.ops import selective_scan as ss
        from paddle_tpu.ops.pallas.selective_scan_decode import \
            selective_scan_decode

        cfg, m = self.cfg, _phi
        B, T = u.shape[:2]
        taps, c = cfg.mamba_d_conv, cfg.d_inner
        state, conv = layer_states
        xin, z = m.mamba_inputs(params, pre, u, self._mm)
        if T == 1:
            live = valid[:, 0]
            before = conv[:B].reshape(B, taps - 1, c)
            rows = jnp.concatenate([before, xin.astype(conv.dtype)], 1)
            xc = m.conv_silu(params, pre, rows)[:, 0]            # [B, c]
            dt, Bm, Cm, A = m.ssm_inputs(cfg, params, pre, xc, u.dtype,
                                         self._mm)
            conv = jax.lax.dynamic_update_slice(conv, jnp.where(
                live[:, None], rows[:, 1:].reshape(B, -1), conv[:B]), (0, 0))
            with jax.named_scope("block/ssm/scan"):
                if self._scan_kernel():
                    s, state = selective_scan_decode(state, xc, dt, A, Bm,
                                                     Cm, live)
                else:
                    s, new = ss.selective_scan_step(
                        state[:B], xc, jnp.where(live[:, None], dt, 0.0), A,
                        Bm, Cm)
                    state = jax.lax.dynamic_update_slice(state, new,
                                                         (0, 0, 0))
            y = m.mamba_memory(params, pre, s, xc)
            return (m.mamba_output(params, pre, y, z[:, 0], self._mm)[:, None],
                    y[:, None], (state, conv))
        if B != 1:
            raise NotImplementedError(
                "spans of several rows for several sequences at once (the "
                "fused ragged step, speculative verify spans) are not "
                "built for recurrent state")
        slot = slots[0]
        before = jnp.where(fresh, 0, conv[slot]).reshape(taps - 1, c)
        rows = jnp.concatenate([before, xin[0].astype(conv.dtype)], 0)
        xc = m.conv_silu(params, pre, rows)                      # [T, c]
        dt, Bm, Cm, A = m.ssm_inputs(cfg, params, pre, xc, u.dtype, self._mm)
        with jax.named_scope("block/ssm/scan"):
            s, new = ss.selective_scan_chunked(
                xc, jnp.where(valid[0][:, None], dt, 0.0), A, Bm, Cm,
                jnp.where(fresh, 0.0, state[slot]))
        n_real = jnp.sum(valid[0].astype(jnp.int32))
        # what the next token's convolution reads: the last real rows
        kept = jax.lax.dynamic_slice_in_dim(rows, n_real, taps - 1, 0)
        state = jax.lax.dynamic_update_index_in_dim(state, new, slot, 0)
        conv = jax.lax.dynamic_update_index_in_dim(conv, kept.reshape(-1),
                                                   slot, 0)
        y = m.mamba_memory(params, pre, s, xc)
        return (m.mamba_output(params, pre, y, z[0], self._mm)[None],
                y[None], (state, conv))

    def _window_prefill(self, q, k, v, tail, start, real_len):
        """Dense window attention of one sequence's rows: q [T, heads,
        d]; k, v [T, pairs, 2d] its own; `tail` (k, v) [W - 1, pairs, 2d]
        of the positions before `start`. Returns (o [T, heads, 2d], the
        tail after the rows)."""
        cfg = self.cfg
        T, W, d = q.shape[0], cfg.sliding_window, cfg.head_dim
        g, rep = cfg.kv_pairs, cfg.num_attention_heads // cfg.kv_pairs // 2
        ks = jnp.concatenate([tail[0], k], 0)          # index = W - 1 + t
        vs = jnp.concatenate([tail[1], v], 0)
        # [pairs', rep, 2, T, d]: query pair p = p' * rep + r
        qh = q.reshape(T, g, rep, 2, d).transpose(1, 2, 3, 0, 4)
        rows = min(T, self.WINDOW_ROWS)
        out = []
        for t0 in range(0, T, rows):
            S = rows + W - 1
            kb = ks[t0:t0 + S].reshape(S, g, 2, d).transpose(1, 2, 0, 3)
            s = jnp.einsum("grjtd,gjsd->grjts", qh[:, :, :, t0:t0 + rows],
                           kb, preferred_element_type=jnp.float32
                           ) * d ** -0.5
            t = t0 + jnp.arange(rows)[:, None]
            idx = t0 + jnp.arange(S)[None, :]
            # row t sees indices [t, t + W - 1] at positions >= 0
            seen = (idx >= t) & (idx <= t + W - 1) & (
                start - (W - 1) + idx >= 0)
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            o = jnp.einsum("grjts,sgd->tgrjd", p.astype(vs.dtype),
                           vs[t0:t0 + S],
                           preferred_element_type=jnp.float32)
            out.append(o.reshape(rows, cfg.num_attention_heads, 2 * d))
        keep = lambda a: jax.lax.dynamic_slice_in_dim(a, real_len, W - 1, 0)
        return (out[0] if len(out) == 1 else jnp.concatenate(out, 0),
                (keep(ks), keep(vs)))

    def _block(self, params, i, x, mixer):
        """h = x + Mixer(LN(x)); y = h + MLP(LN(h)); mixer(u) -> (m,
        extra)."""
        cfg, m, pre = self.cfg, _phi, f"layers.{i}."
        mix, extra = mixer(
            m.block_norm(cfg, params, pre + "input_layernorm", x))
        x = x + mix
        with jax.named_scope("block/mlp"):
            x = x + m.mlp(params, pre + "mlp.", m.block_norm(
                cfg, params, pre + "post_attention_layernorm", x), self._mm)
        return x, extra

    def _cross_decoder(self, params, x, memory, pages, table, pos, q_len):
        """The layers after the full layer on x [B, 1, hidden]: gated
        memory units on `memory` [B, 1, d_inner], cross-attention to the
        full layer's pages (no write)."""
        cfg, m = self.cfg, _phi
        for i in range(cfg.split, cfg.num_hidden_layers):
            pre = f"layers.{i}."
            if self.kinds[i] == "gmu":
                def mixer(u, pre=pre):
                    with jax.named_scope("block/gmu"):
                        return m.gmu(params, pre + "gmu.", u, memory,
                                     self._mm), None
            else:
                def mixer(u, pre=pre, i=i):
                    with jax.named_scope("block/attn/shared"):
                        q = m.cross_q(cfg, params, pre + "attn.", u, self._mm)
                        o = self._attend(q[:, 0], pages, table, pos, q_len)
                        return m.differential_output(
                            cfg, params, pre + "attn.", i, o[:, None],
                            u.dtype, self._mm), None
            x, _ = self._block(params, i, x, mixer)
        return x

    def _head(self, params, x):
        with jax.named_scope("final_norm"):
            x = _phi.block_norm(self.cfg, params, "final_layernorm", x)
        with jax.named_scope("lm_head"):
            return x @ params["embed_tokens.weight"].T

    # ------------------------------------------------------------- steps

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools, head_rows=None):
        """A decode step: one token a row, every layer."""
        cfg, m = self.cfg, _phi
        B, T = tokens.shape
        if T != 1:
            raise NotImplementedError(
                "spans of several rows for several sequences at once (the "
                "fused ragged step, speculative verify spans) are not "
                "built for this runner; a prefill goes through "
                "prefill_chunk")
        pages, states, ring = pools
        full_tab, ring_tab, ring_base = self._split_tables(tables)
        valid = write_page != SCRATCH_PAGE                          # [B, 1]
        live = valid[:, 0]
        n_live = live.astype(jnp.int32)
        bs, W = self.block_size, cfg.sliding_window
        # the ring's own positions: its table's column 0 holds `base`
        rel = pos_q - ring_base * bs
        ring_page = jnp.where(live, jnp.take_along_axis(
            ring_tab, jnp.clip(rel // bs, 0, ring_tab.shape[1] - 1)[:, None],
            axis=1)[:, 0], SCRATCH_PAGE)
        lower = jnp.maximum(rel - (W - 1), 0)
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        new_states, new_ring, new_pages, memory = [], [], list(pages), None
        for i in range(cfg.split):
            pre, kind = f"layers.{i}.", self.kinds[i]
            if kind == "mamba":
                def mixer(u, pre=pre):
                    out, y, layer = self._mamba(
                        params, pre + "mamba.", u, valid, None, None,
                        states[len(new_states)])
                    new_states.append(layer)
                    return out, y
                x, y = self._block(params, i, x, mixer)
                if i == cfg.memory_layer:
                    memory = y
                continue

            def mixer(u, pre=pre, i=i, kind=kind):
                a = pre + "attn."
                q, k, v = m.attention_qkv(cfg, params, a, u, self._mm)
                if kind == "window":
                    with jax.named_scope("block/attn/window"):
                        kp, vp = ring[len(new_ring)]
                        layer = (self._write_rows(kp, ring_page, rel % bs,
                                                  k[:, 0]),
                                 self._write_rows(vp, ring_page, rel % bs,
                                                  v[:, 0]))
                        new_ring.append(layer)
                        o = self._attend(q[:, 0], layer, ring_tab, rel,
                                         n_live, lower)
                else:
                    with jax.named_scope("block/attn/shared"):
                        kp, vp = pages[0]
                        layer = (self._write_rows(kp, write_page[:, 0],
                                                  write_off[:, 0], k[:, 0]),
                                 self._write_rows(vp, write_page[:, 0],
                                                  write_off[:, 0], v[:, 0]))
                        new_pages[0] = layer
                        o = self._attend(q[:, 0], layer, full_tab, pos_q,
                                         n_live)
                return m.differential_output(cfg, params, a, i, o[:, None],
                                             u.dtype, self._mm), None
            x, _ = self._block(params, i, x, mixer)
        x = self._cross_decoder(params, x, memory, new_pages[0], full_tab,
                                pos_q, n_live)
        logits = self._head(params, x)
        zero = jnp.int32(0)
        counts = jnp.stack([jnp.sum(n_live) * len(states), zero, zero, zero])
        return logits, (new_pages, new_states, new_ring), counts

    def _self_decoder(self, params, tokens, table, real_len, start_slot,
                      tail, cache):
        """A prefill piece's rows through the layers before the full one,
        and the full layer's key/value write: tokens [1, T] of ONE
        sequence at positions start.. . Returns (x [1, T, hidden] before
        the full layer, the memory, the full layer's (q, written pages),
        the states, the tail after the piece, valid)."""
        cfg, m = self.cfg, _phi
        pages, states = cache
        T = tokens.shape[1]
        start, slots = start_slot[0], start_slot[1:]
        offs = jnp.arange(T, dtype=jnp.int32)[None, :]
        valid = offs < real_len
        positions = jnp.where(valid, start + offs, 0)
        page, off = self._write_indices(positions, table[None, :self.table_pages],
                                        valid)
        fresh = start == 0
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        new_states, tail_k, tail_v, memory = [], [], [], None
        for i in range(cfg.split - 1):
            pre = f"layers.{i}."
            if self.kinds[i] == "mamba":
                def mixer(u, pre=pre):
                    out, y, layer = self._mamba(
                        params, pre + "mamba.", u, valid, fresh, slots,
                        states[len(new_states)])
                    new_states.append(layer)
                    return out, y
                x, y = self._block(params, i, x, mixer)
                if i == cfg.memory_layer:
                    memory = y
                continue

            def mixer(u, pre=pre, i=i):
                a, n = pre + "attn.", len(tail_k)
                with jax.named_scope("block/attn/window"):
                    q, k, v = m.attention_qkv(cfg, params, a, u, self._mm)
                    o, (tk, tv) = self._window_prefill(
                        q[0], k[0], v[0], (tail[0][n], tail[1][n]), start,
                        real_len)
                    tail_k.append(tk)
                    tail_v.append(tv)
                return m.differential_output(
                    cfg, params, a, i, o[None].astype(u.dtype), u.dtype,
                    self._mm), None
            x, _ = self._block(params, i, x, mixer)
        # the full layer: keys and values of every row go to its pages
        i = cfg.split - 1
        a = f"layers.{i}.attn."
        with jax.named_scope("block/attn/shared"):
            u = m.block_norm(cfg, params, f"layers.{i}.input_layernorm", x)
            q, k, v = m.attention_qkv(cfg, params, a, u, self._mm)
            kp, vp = pages[0]
            written = (self._write_rows(kp, page[0], off[0], k[0]),
                       self._write_rows(vp, page[0], off[0], v[0]))
        return (x, memory, q, written, new_states,
                (jnp.stack(tail_k), jnp.stack(tail_v)), valid)

    def _piece_body(self, params, tokens, table, real_len, start_slot, tail,
                    cache):
        """A piece's rows through the layers before the full one and the
        full layer's key/value write; `start_slot` is (start, slot, whether
        the piece is its chunk's last). No row of it reaches the full
        layer's attention or the cross-decoder here: of its LAST real row
        it hands on what `_piece_head` takes there (the stream before the
        full layer, the memory, the full layer's query)."""
        x, memory, q, written, states, tail, valid = self._self_decoder(
            params, tokens, table, real_len, start_slot[:2], tail, cache)
        last = jnp.reshape(real_len - 1, (1,))
        row = lambda a: jnp.take_along_axis(a, last[:, None, None], axis=1)
        q_row = jnp.take_along_axis(q, last[:, None, None, None],
                                    axis=1)[:, 0]
        real = jnp.sum(valid.astype(jnp.int32))
        counts = jnp.stack([jnp.int32(0), real, real - start_slot[2],
                            (start_slot[0] == 0).astype(jnp.int32)])
        return ([written], states), tail, counts, (row(x), row(memory), q_row)

    def _piece_head(self, params, last_row, table, pos, pages):
        """A chunk's LAST real row (what `_piece_body` handed on, at
        position `pos` [1]) through the full layer's attention and the
        cross-decoder to the logits [vocab]. Reads the full group's pages,
        writes nothing."""
        cfg, m = self.cfg, _phi
        x, memory, q = last_row
        one = jnp.ones((1,), jnp.int32)
        full_tab = table[None, :self.table_pages]
        i = cfg.split - 1

        def mixer(u):
            # u is the last row's norm again: the same numbers
            with jax.named_scope("block/attn/shared"):
                o = self._attend(q, pages[0], full_tab, pos, one)
                return m.differential_output(
                    cfg, params, f"layers.{i}.attn.", i, o[:, None], u.dtype,
                    self._mm), None
        x, _ = self._block(params, i, x, mixer)
        x = self._cross_decoder(params, x, memory, pages[0], full_tab, pos,
                                one)
        return self._head(params, x)[0, 0]

    def _piece_rows(self, t: int) -> int:
        """Rows of the pieces a chunk of t tokens runs in (the last one is
        padded to it): a short chunk is one piece of its power-of-two
        bucket, as every runner's prefill is; a longer one runs in pieces
        of PREFILL_SPAN rows whatever is left for the last, so that long
        prompts of any length share ONE program of the 17 layers (a quarter
        of a minute to compile; a piece reads every weight once, 9.4 ms of
        a v5e's memory at the published sizes, whatever its rows)."""
        return bucket_len(t) if t <= min(self.SHORT_CHUNK,
                                         self.PREFILL_SPAN) \
            else self.PREFILL_SPAN

    def prefill_chunk(self, tokens: List[int], start_pos: int,
                      table_row: List[int], pools, slot=None, ring=None):
        """The chassis's entry, in pieces of PREFILL_SPAN rows (the head of
        this class). `ring`: the window group's row for this sequence
        before and after the chunk (`WindowGroup.row`); `table_row` may
        carry the group's columns behind the pages (they are not read)."""
        if ring is None:
            raise ValueError(
                "Phi4FlashRunner.prefill_chunk needs ring=(before, after), "
                "the window group's rows for this sequence around the "
                "chunk (ServingEngine and naive_generate pass them)")
        with _prof.span("runner.launch") as launch:
            pages, states, win = pools
            t, span = len(tokens), self._piece_rows(len(tokens))
            launch.set(kind="prefill", key=span)
            table = np.asarray(table_row, np.int32)[:self.table_pages]
            slot = 0 if slot is None else slot
            tail = self._jitted("phi_ring_load", 0)(
                win, np.asarray(ring[0], np.int32), np.int32(start_pos))
            body = self._jitted("phi_body", span)
            for lo in range(0, t, span):
                piece = tokens[lo:lo + span]
                padded = np.zeros((1, span), np.int32)
                padded[0, :len(piece)] = piece
                with _prof.span("runner.dispatch"):
                    (pages, states), tail, counts, last_row = body(
                        self.params, padded, table, np.int32(len(piece)),
                        np.asarray([start_pos + lo, slot, lo + span >= t],
                                   np.int32), tail, (pages, states))
                if self.on_step_counts is not None:
                    self.on_step_counts(counts)
            with _prof.span("runner.dispatch"):
                logits = self._jitted("phi_head", 0)(
                    self.params, last_row, table,
                    np.asarray([start_pos + t - 1], np.int32), pages)
            win = self._jitted("phi_ring_store", 0)(
                win, tail, np.asarray(ring[1], np.int32),
                np.int32(start_pos + t))
            return logits, (pages, states, win)



def runner_for(model, block_size: int = 16, max_model_len: int | None = None,
               attn_impl: str = "auto", kv_dtype: str = "fp32",
               weight_dtype: str = "fp32",
               weight_group_size: int = 128) -> PagedModelRunner:
    """Pick the runner for a supported decoder Layer, by its class (a
    DeepseekV3ForCausalLM is DeepSeek-V3, Kimi K2 or, with an indexer in
    its configuration, DeepSeek-V3.2: one runner)."""
    from paddle_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM
    from paddle_tpu.models.gpt import GPT
    from paddle_tpu.models.llama import Llama
    from paddle_tpu.models.olmo_hybrid import OlmoHybridForCausalLM
    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM

    for layer, cls in ((Llama, LlamaRunner), (GPT, GPTRunner),
                       (DeepseekV3ForCausalLM, DeepseekV3Runner),
                       (OlmoHybridForCausalLM, OlmoHybridRunner),
                       (Phi4FlashForCausalLM, Phi4FlashRunner)):
        if isinstance(model, layer):
            return cls(model, block_size, max_model_len, attn_impl,
                       kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                       weight_group_size=weight_group_size)
    raise TypeError(
        f"no serving runner for {type(model).__name__}; supported: Llama, "
        "GPT, DeepseekV3ForCausalLM (DeepSeek-V3, Kimi K2, and DeepSeek-V3.2 "
        "where its configuration sets index_topk), OlmoHybridForCausalLM, "
        "Phi4FlashForCausalLM (write a PagedModelRunner subclass for "
        "custom decoders)")


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
