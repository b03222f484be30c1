"""Multi-query attention over LATENT pages: the decode kernel of the
absorbed form of latent attention.

A latent layer's cache holds ONE array per page, `[num_pages, page_size,
lanes]`: per token a compressed key/value vector whose first `v_lanes`
lanes are also the value (the rest, a rotary part, enter the scores only).
Every query head of a sequence attends the SAME keys, so a decode step is
a multi-query problem: the `n_q` heads of one token are the rows of one
matmul against each block of the sequence's pages,

    s = q [n_q, lanes] . block [keys, lanes]^T     o += p . block[:, :v_lanes]

with the online softmax of ops/pallas/ragged_paged_attention.py around it.
Built as that kernel's walk is (PR 26): grid (batch,), the pool stays in
HBM, a step loops over the LIVE pages of its sequence only, a block of
`pages_per_block` pages at a time, copied into a double-buffered VMEM
block while the block before is folded; after a sequence's last block the
copy in flight is the first block of the NEXT sequence. The block tables
ride SMEM by scalar prefetch.

A block is copied a GROUP of `run_group` pages at a time. Where a group's
page ids are consecutive (`page_runs`: the allocator hands a prompt's pages
out in ascending order, so most are) its pages are consecutive bytes of the
pool and the group is ONE async copy; any other group (pages appended while
decoding, a sequence's partial tail, a dead slot's scratch row) is a copy
per page, unrolled where the whole group is live (a loop's scalar chain, not
the copy engine, is what a 20 KB page cannot amortise). The flags are data
beside the table: same pages, same bytes, same fold, so the result does not
depend on them. `latent_walk_counts` counts the groups and descriptors of a
step in numpy.

The chip's compiler copies a page only where its minor dims are whole
tiles, so `lanes` must be a multiple of 128: a pool whose vector is 576
wide is ALLOCATED 640 wide (serving/kv_cache.py takes the layout from the
runner); nothing is padded per call.

q: [B, n_q, lanes] (lanes past the vector's width zero); pool as above;
block_table [B, pages_per_seq] int32; pos [B] int32, the context position
of the query: it sees keys at positions <= pos. Returns [B, n_q, v_lanes].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:  # pragma: no cover - absent on pure-CPU builds
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30
# what one grid step may hold of the chip's 16 MiB of scoped VMEM
VMEM_BUDGET = 10 * 2 ** 20
# the size of a run copy: past it a copy's fixed cost no longer shows
RUN_COPY_BYTES = 320 * 2 ** 10


def pages_per_block(n_q: int, page_size: int, lanes: int, v_lanes: int,
                    itemsize: int) -> int:
    """Pages one block of the walk copies and folds: the largest power of
    two, up to 64, at which the two buffers, the block's float32 form, its
    value slice and four score tiles fit VMEM_BUDGET."""
    per_page = page_size * (2 * lanes * itemsize + lanes * 4
                            + v_lanes * itemsize + 4 * n_q * 4)
    fixed = n_q * (2 * lanes * itemsize + 3 * v_lanes * 4)
    ppb = 64
    while ppb > 1 and fixed + ppb * per_page > VMEM_BUDGET:
        ppb //= 2
    return ppb


def run_group(ppb: int, page_size: int, lanes: int, itemsize: int) -> int:
    """Pages a run copy moves at once: the largest power of two within
    RUN_COPY_BYTES, at most a block."""
    group = ppb
    while group > 1 and group * page_size * lanes * itemsize > RUN_COPY_BYTES:
        group //= 2
    return group


def page_runs(block_table, group: int):
    """Which groups of `group` table entries are runs of consecutive page
    ids, in order (a permuted range is not one): int32 [B, ceil(width /
    group)]; a group the table's width cuts short is not a run."""
    table = jnp.asarray(block_table, jnp.int32)
    B, width = table.shape
    full = width // group
    g = table[:, :full * group].reshape(B, full, group)
    runs = jnp.all(g[:, :, 1:] == g[:, :, :-1] + 1, axis=-1)
    return jnp.pad(runs.astype(jnp.int32), ((0, 0), (0, -(-width // group)
                                                     - full)))


def walked_groups(runs, pos, page_size: int, group: int, table_width: int):
    """What one call of the kernel walks, on the device: int32 [2], the
    groups that hold a live page and those among them copied as one (a
    flagged group wholly inside its sequence's live pages)."""
    n_pages = jnp.minimum(jnp.asarray(pos, jnp.int32) // page_size + 1,
                          table_width)[:, None]
    first = jnp.arange(runs.shape[1], dtype=jnp.int32)[None] * group
    return jnp.stack([jnp.sum(first < n_pages),
                      jnp.sum((runs != 0) & (first + group <= n_pages))]
                     ).astype(jnp.int32)


def latent_walk_counts(block_table, pos, page_size: int, group: int):
    """numpy twin of the walk, for tests and accounting: (groups walked,
    groups copied as one run, copy descriptors issued) by one call over
    these tables and positions."""
    table, pos = np.asarray(block_table), np.asarray(pos).reshape(-1)
    groups = as_run = descriptors = 0
    for row, p in zip(table, pos):
        n_pages = min(int(p) // page_size + 1, table.shape[1])
        for first in range(0, n_pages, group):
            ids = row[first:first + group]
            run = (first + group <= n_pages
                   and bool(np.all(ids[1:] == ids[:-1] + 1)))
            groups += 1
            as_run += run
            descriptors += 1 if run else min(group, n_pages - first)
    return groups, as_run, descriptors


def page_copies(table_ref, runs_ref, pool_hbm, buf, sem, b_, block, slot,
                n_pages, *, group: int, wait: bool = False):
    """Start (or wait for) the copies of the live pages of `block` of
    sequence b_'s table row into buffer `slot` of `buf` [2, pages a block,
    page, lanes]: one per group that is a run and wholly live, one per page
    elsewhere (unrolled for a whole group, a loop over a sequence's tail).
    A wait has the shape of the copy it waits for. The walk of every
    kernel over pages of `pool_hbm` [pages, page, lanes]."""
    ppb = buf.shape[1]
    last_group = runs_ref.shape[1] - 1
    first = block * ppb
    live = jnp.clip(n_pages - first, 0, ppb)

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem.at[slot])
        cp.wait() if wait else cp.start()

    def one_group(g, carry):
        page0 = first + g * group
        whole = page0 + group <= n_pages
        is_run = jnp.logical_and(
            runs_ref[b_, jnp.minimum(page0 // group, last_group)] != 0,
            whole)

        def page(r, c):
            pid = 0 if wait else table_ref[b_, page0 + r]
            copy(pool_hbm.at[pid], buf.at[slot, g * group + r])
            return c

        @pl.when(is_run)
        def _as_one():
            pid = 0 if wait else table_ref[b_, page0]
            copy(pool_hbm.at[pl.ds(pid, group)],
                 buf.at[slot, pl.ds(g * group, group)])

        @pl.when(jnp.logical_and(whole, jnp.logical_not(is_run)))
        def _scattered():
            jax.lax.fori_loop(0, group, page, 0, unroll=True)

        @pl.when(jnp.logical_not(whole))
        def _tail():
            jax.lax.fori_loop(0, live - g * group, page, 0)

        return carry

    jax.lax.fori_loop(0, pl.cdiv(live, group), one_group, 0)


def _select_kernel(table_ref, pos_ref, runs_ref, value_ref, last_ref, q_ref,
                   score_ref, *refs, **kw):
    """The kernel under a selection: two more scalar rows and the
    sequence's row of scores in front of the pool."""
    _kernel(table_ref, pos_ref, runs_ref, q_ref, *refs,
            select=(value_ref, last_ref, score_ref), **kw)


def _kernel(table_ref, pos_ref, runs_ref, q_ref, pool_hbm, o_ref, buf, sem,
            m_ref, l_ref, acc_ref, slot_ref, *, page_size: int, v_lanes: int,
            scale: float, group: int, select=None):
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

    slot0 = slot_ref[0]                # buffer of this sequence's block 0
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    q = q_ref[0]                                           # [n_q, lanes]

    def fold(block, slot, masked: bool):
        """Fold one block into (m, l, acc). Only a sequence's LAST block
        can hold keys past its context (and slots no copy filled): the
        others skip the masks, which cost as much as the matmuls."""
        key0 = block * keys
        kv = buf[slot].reshape(keys, buf.shape[3])
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        v = kv[:, :v_lanes]
        if select is not None:
            # only the keys the sequence's query chose (a score above the
            # k-th largest, or equal to it and early enough) and can see
            value_ref, last_ref, score_ref = select
            score = score_ref[0, :, pl.ds(pl.multiple_of(key0, keys), keys)]
            k_pos = key0 + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
            value = value_ref[b]
            live = (((score > value) | ((score == value)
                                        & (k_pos <= last_ref[b])))
                    & (k_pos <= last_pos))
            s = jnp.where(live, s, NEG_INF)
        elif masked:
            live = key0 + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) <= last_pos
            s = jnp.where(live, s, NEG_INF)
        if masked:
            # the slots of a partial block that no copy filled hold what
            # was there before, and 0 * NaN is NaN: the values are masked
            v = jnp.where(key0 + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) <= last_pos, v, jnp.zeros_like(v))
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        if masked or select is not None:
            p = jnp.where(live, p, 0.0)
        corr = jnp.exp(m - new_m)
        m_ref[...] = new_m
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def walk(block, carry):
        slot = (slot0 + block) % 2
        # in flight while this block is folded: the sequence's next block,
        # or after its last the first block of the next sequence
        more = block + 1 < n_blocks
        copies(jnp.where(more, b, nb), jnp.where(more, block + 1, 0),
               1 - slot, jnp.where(more, n_pages, next_pages))
        copies(b, block, slot, n_pages, wait=True)

        @pl.when(more)
        def _full_block():
            fold(block, slot, masked=False)

        @pl.when(jnp.logical_not(more))
        def _last_block():
            fold(block, slot, masked=True)

        return carry

    jax.lax.fori_loop(0, n_blocks, walk, 0)
    slot_ref[0] = (slot0 + n_blocks) % 2
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def latent_paged_attention(q, pool, block_table, pos, *, v_lanes: int,
                           scale: float, interpret: bool | None = None,
                           runs=None, pages_per_block: int | None = None,
                           group: int | None = None, select=None):
    """Decode attention of B sequences over latent pages (see the head).
    Every sequence reads at least its first page (a dead slot's table is
    all scratch and its position 0). `runs` are `page_runs` of the table
    at `walk_shape`'s group, for a caller whose layers share one table
    (None: computed here). `pages_per_block` and `group` override the
    rule: for tests of the walk at small sizes. `select` = (scores [B,
    keys] float32, value [B] float32, last [B] int32) restricts each
    sequence to the keys it chose: those whose score lies above `value`, or
    equals it at a position <= `last` (models/deepseek_v3.topk_threshold);
    the walk still reads every live page, and every block is folded under
    the mask."""
    if pool.shape[2] % 128:
        raise ValueError(
            f"latent pages of {pool.shape[2]} lanes: the chip copies whole "
            "tiles, allocate the pool in multiples of 128")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ppb, group = walk_shape(q.shape[1], pool, v_lanes, pages_per_block, group)
    if runs is None:
        runs = page_runs(block_table, group)
    if select is not None:
        scores, value, last = select
        # whole blocks of scores, + 0.0 so that -0.0 counts as 0.0
        keys = ppb * pool.shape[1]
        width = -(-block_table.shape[1] * pool.shape[1] // keys) * keys
        select = (jnp.pad(scores.astype(jnp.float32) + 0.0,
                          ((0, 0), (0, width - scores.shape[1])),
                          constant_values=-jnp.inf)[:, None, :],
                  value.astype(jnp.float32), last.astype(jnp.int32))
    return _call(q, pool, block_table, pos, runs, select,
                 v_lanes=int(v_lanes), scale=float(scale),
                 interpret=bool(interpret), ppb=ppb, group=group)


def walk_shape(n_q: int, pool, v_lanes: int, ppb: int | None = None,
               group: int | None = None):
    """(pages a block, pages a run copy) of the walk over `pool` (an array
    or its shape-and-dtype) for `n_q` query rows."""
    _, page_size, lanes = pool.shape
    itemsize = jnp.dtype(pool.dtype).itemsize
    if ppb is None:
        ppb = pages_per_block(n_q, page_size, lanes, v_lanes, itemsize)
    if group is None:
        group = run_group(ppb, page_size, lanes, itemsize)
        while group > pool.shape[0]:       # a run lies inside the pool
            group //= 2
    if ppb % group:
        raise ValueError(f"a block of {ppb} pages is not whole groups of "
                         f"{group}")
    return int(ppb), int(group)


# jitted here as the ragged kernel's wrapper is: a model's layers call it
# with the same shapes, and a jitted callee is lowered once per program
@functools.partial(jax.jit, static_argnames=("v_lanes", "scale", "interpret",
                                             "ppb", "group"))
def _call(q, pool, block_table, pos, runs, select=None, *, v_lanes: int,
          scale: float, interpret: bool, ppb: int, group: int):
    B, n_q, lanes = q.shape
    page_size = pool.shape[1]
    scalars = (block_table.astype(jnp.int32),
               jnp.asarray(pos, jnp.int32).reshape(-1),
               jnp.asarray(runs, jnp.int32))
    in_specs = [pl.BlockSpec((1, n_q, lanes), lambda b, *_: (b, 0, 0))]
    inputs = (q,)
    if select is not None:
        scores, value, last = select
        scalars += (value, last)
        in_specs.append(pl.BlockSpec((1, 1, scores.shape[2]),
                                     lambda b, *_: (b, 0, 0)))
        inputs += (scores,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_q, v_lanes), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((n_q, 1), jnp.float32),
            pltpu.VMEM((n_q, 1), jnp.float32),
            pltpu.VMEM((n_q, v_lanes), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),           # slot of the next block 0
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel if select is None else _select_kernel,
                          page_size=page_size, v_lanes=v_lanes, scale=scale,
                          group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_q, v_lanes), q.dtype),
        # a step starts the next step's first copies: the grid is a
        # sequence, in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_attn",
    )(*scalars, *inputs, pool)


def latent_reference(q, pool, block_table, pos, *, v_lanes: int,
                     scale: float):
    """Gather + dense-mask oracle of the kernel: O(B * table width) HBM."""
    B, n_q, lanes = q.shape
    lat = pool[block_table].reshape(B, -1, lanes).astype(jnp.float32)
    s = jnp.einsum("bhc,blc->bhl", q.astype(jnp.float32), lat) * scale
    k_pos = jnp.arange(lat.shape[1], dtype=jnp.int32)
    s = jnp.where(k_pos[None, None, :] <= jnp.asarray(pos)[:, None, None],
                  s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blc->bhc", p, lat[..., :v_lanes]).astype(q.dtype)
