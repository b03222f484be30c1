"""Learned sparse attention over latent pages (DeepSeek Sparse Attention), the
decode step's two kernels beside ops/pallas/latent_paged_attention.py.

A sparse latent layer's page is TWO arrays behind one block table: the
latent rows `[pages, page, lanes]` (c_kv | k_r, as a dense latent layer
keeps them) and the indexer's keys `[pages, page, d]`, one vector a token.
A decode step of such a layer is three things:

  1. `paged_index_scores`: every LIVE index key of a sequence against the
     query token's `heads` index queries, I(s) = sum_j w_j ReLU(q_j . k(s)),
     float32, no softmax and no value. The walk is the latent kernel's
     (`page_copies`: blocks of pages, double-buffered, a group of
     consecutive pages one copy, the block tables in scalar memory); a fold
     is one [heads, d] x [d, keys] product, a ReLU, a weighted sum over the
     heads. Positions past the query's are -inf.
  2. the selection (exact; ties to the lower position; the caller's):
     `models/deepseek_v3.topk_threshold`, the k-th largest score and the
     position up to which its equals count. No list of rows is made.
  3. attention over the selected rows alone:
     `latent_paged_attention(..., select=)`, the dense kernel's walk over
     every live page, each block folded under the selection (a key counts
     if its score lies above the k-th largest, or equals it early enough).
     It reads what a dense layer reads and computes what a sparse one
     computes: 1.31 ms a layer for 36 contexts of 11-17 k at 128 heads on
     a v5e. Reading the chosen rows alone was tried twice and is not here
     (PERF.md section 6, PR 44, has the table; ROADMAP R5 (a)). A page
     holds 16 tokens and a selection of 2048 from 14 k touches nearly every
     page, so reading touched pages whole would read everything: the fetch
     has to be by row. A kernel that copies the rows itself (positions in
     scalar memory -> page and offset -> one row copy each) passed
     interpret mode and is REFUSED by the chip's compiler: a page's rows
     lie in tiles of 8 x 128 32-bit words, two bfloat16 rows a sublane, and
     a copy's slice of the second-minor dimension must be whole tiles
     ("Slice shape along dimension 0 must be aligned to tiling (8), but is
     1"). XLA's gather in front of the dense kernel has no such limit and
     takes 26 ns a row whatever the row holds: 1.92 ms for 36 x 2048 rows
     of 1280 B, slower than the walk at every context a cell reaches.

`sparse_latent_reference` is the oracle of step 3: it takes the selection
as a list `sel` [B, K] int32 of a sequence's chosen positions, the
min(K, pos + 1) real ones FIRST (as `lax.top_k` leaves them, the scores
past `pos` being -inf); what follows them is never read as a key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pragma: no cover - absent on pure-CPU builds
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from paddle_tpu.ops.pallas.latent_paged_attention import (
    NEG_INF, RUN_COPY_BYTES, latent_paged_attention, page_copies, page_runs,
)

# keys one block of the scan scores: [heads, keys] float32 twice over and
# the two page buffers stay far inside a grid step's VMEM
SCAN_KEYS = 2048


# --------------------------------------------------------- 1. the scan


def scan_shape(ipool, ppb: int | None = None, group: int | None = None):
    """(pages a block, pages a run copy) of the scan over `ipool` (an array
    or its shape-and-dtype)."""
    n_pages, page_size, d = ipool.shape
    itemsize = jnp.dtype(ipool.dtype).itemsize
    if ppb is None:
        ppb = max(1, SCAN_KEYS // page_size)
    if group is None:
        group = ppb
        while group > 1 and (group * page_size * d * itemsize > RUN_COPY_BYTES
                             or group > n_pages):
            group //= 2
    if ppb % group:
        raise ValueError(f"a block of {ppb} pages is not whole groups of "
                         f"{group}")
    return int(ppb), int(group)


def _scan_kernel(table_ref, pos_ref, runs_ref, q_ref, w_ref, pool_hbm, o_ref,
                 buf, sem, slot_ref, *, page_size: int, group: int):
    b, n_seq = pl.program_id(0), pl.num_programs(0)
    ppb = buf.shape[1]
    keys = ppb * page_size
    table_width = table_ref.shape[1]

    def seq_pages(b_):
        return jnp.minimum(pos_ref[b_] // page_size + 1, table_width)

    def copies(b_, block, slot, n_pages, wait: bool = False):
        page_copies(table_ref, runs_ref, pool_hbm, buf, sem, b_, block, slot,
                    n_pages, group=group, wait=wait)

    last_pos = pos_ref[b]
    n_pages = seq_pages(b)
    n_blocks = pl.cdiv(n_pages, ppb)
    nb = jnp.minimum(b + 1, n_seq - 1)
    next_pages = jnp.where(b == n_seq - 1, 0, seq_pages(nb))

    @pl.when(b == 0)
    def _first_step():
        slot_ref[0] = 0
        copies(b, 0, 0, n_pages)

    slot0 = slot_ref[0]
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)
    q = q_ref[0]                                           # [heads, d]
    w = w_ref[0]                                           # [heads, 1]

    def walk(block, carry):
        slot = (slot0 + block) % 2
        more = block + 1 < n_blocks
        copies(jnp.where(more, b, nb), jnp.where(more, block + 1, 0),
               1 - slot, jnp.where(more, n_pages, next_pages))
        copies(b, block, slot, n_pages, wait=True)
        kv = buf[slot].reshape(keys, buf.shape[3])
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        score = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        key0 = pl.multiple_of(block * keys, keys)
        live = key0 + jax.lax.broadcasted_iota(
            jnp.int32, score.shape, 1) <= last_pos
        # slots of a partial block that no copy filled hold what was there
        # before: selected away, never multiplied
        o_ref[0, :, pl.ds(key0, keys)] = jnp.where(live, score, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n_blocks, walk, 0)
    slot_ref[0] = (slot0 + n_blocks) % 2


def paged_index_scores(q_i, w_i, ipool, block_table, pos, *,
                       interpret: bool | None = None, runs=None,
                       pages_per_block: int | None = None,
                       group: int | None = None):
    """Index scores of B decode rows over their sequences' index pages:
    q_i [B, heads, d], w_i [B, heads] float32, ipool [pages, page, d],
    block_table [B, pages_per_seq], pos [B] -> [B, pages_per_seq * page]
    float32, -inf at every position past `pos`. `runs`: `page_runs` of the
    table at `scan_shape`'s group (None: computed here)."""
    if ipool.shape[2] % 128:
        raise ValueError(f"index pages of {ipool.shape[2]} lanes: the chip "
                         "copies whole tiles")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ppb, group = scan_shape(ipool, pages_per_block, group)
    if runs is None:
        runs = page_runs(block_table, group)
    return _scan_call(q_i, w_i.astype(jnp.float32)[..., None], ipool,
                      block_table, pos, runs, interpret=bool(interpret),
                      ppb=ppb, group=group)


@functools.partial(jax.jit, static_argnames=("interpret", "ppb", "group"))
def _scan_call(q_i, w_i, ipool, block_table, pos, runs, *, interpret: bool,
               ppb: int, group: int):
    B, nh, d = q_i.shape
    page_size = ipool.shape[1]
    # whole blocks: a block's scores are stored whole
    width = -(-block_table.shape[1] // ppb) * ppb * page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, nh, d), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec((1, nh, 1), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, d), ipool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_scan_kernel, page_size=page_size, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_index_scores",
    )(block_table.astype(jnp.int32), jnp.asarray(pos, jnp.int32).reshape(-1),
      jnp.asarray(runs, jnp.int32), q_i, w_i, ipool)
    return out[:, 0, :block_table.shape[1] * page_size]


def index_scores_reference(q_i, w_i, ipool, block_table, pos):
    """Gather oracle of the scan: O(B * table width) HBM."""
    B = q_i.shape[0]
    keys = ipool[block_table].reshape(B, -1, ipool.shape[-1])
    s = jnp.einsum("bhd,bld->bhl", q_i, keys,
                   preferred_element_type=jnp.float32)
    score = jnp.sum(jax.nn.relu(s) * w_i.astype(jnp.float32)[:, :, None],
                    axis=1)
    k_pos = jnp.arange(keys.shape[1], dtype=jnp.int32)
    return jnp.where(k_pos[None, :] <= jnp.asarray(pos)[:, None], score,
                     -jnp.inf)


# ------------------------------- 3. attention over chosen rows: the oracle


def sparse_latent_reference(q, pool, block_table, pos, sel, *, v_lanes: int,
                            scale: float):
    """Gather + dense-mask oracle of the walk under a selection: the
    first min(K, pos + 1) positions of each row of `sel` through the
    table, a softmax over those rows alone."""
    sel = jnp.asarray(sel, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    n = jnp.minimum(sel.shape[1], pos + 1)
    real = jnp.arange(sel.shape[1], dtype=jnp.int32)[None, :] < n[:, None]
    sel = jnp.where(real, sel, 0)        # a row that exists in every table
    page_size, lanes = pool.shape[1], pool.shape[2]
    page = jnp.take_along_axis(block_table.astype(jnp.int32),
                               sel // page_size, axis=1)
    rows = pool.reshape(-1, lanes)[page * page_size + sel % page_size
                                   ].astype(jnp.float32)        # [B, K, lanes]
    s = jnp.einsum("bhc,bkc->bhk", q.astype(jnp.float32), rows) * scale
    p = jax.nn.softmax(jnp.where(real[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p, rows[..., :v_lanes]).astype(q.dtype)
