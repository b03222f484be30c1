"""Ragged paged attention: one Pallas TPU kernel for the serving hot path.

Reference: "Ragged Paged Attention" (arXiv:2604.15464) — TPU serving
computes causal attention for a *ragged* batch of query spans (decode
steps with q_len=1, chunked-prefill spans with q_len=chunk at an offset,
and mixes of both) in a single kernel launch straight against the paged
KV pools. The reference's serving analogue is the CUDA kernel behind
incubate/nn/functional/block_multihead_attention.py; before this kernel
the serving engine's prefill chunks and GQA decodes took the
paged_gather + dense-mask path, materializing every sequence's ENTIRE
padded KV history ([B, max_pages*page_size, H, D]) in HBM per step.

Design (the flash-attention online-softmax structure of
ops/pallas/flash_attention.py, with the page walk inside the kernel):

  * grid (batch, q_tile): one step per sequence and tile of Q_TILE span
    rows (a whole span when it is shorter), so VMEM use does not grow
    with the prefill bucket; per-sequence block tables, span start
    positions, and span lengths ride in SMEM via
    pltpu.PrefetchScalarGridSpec. The K and V pools stay in HBM
    (memory_space=ANY, no BlockSpec);
  * the walk: a step computes the tile's last visible page from
    start_pos and q_len and loops over its LIVE pages only, a block of
    pages_per_block pages at a time: one async copy per live page
    (``k_pool.at[table[b, p]]`` -> a slot of a double-buffered VMEM
    block) while the block before is folded into the accumulators; the
    first block of the NEXT grid step is started before this one ends.
    A short sequence in a wide table pays for its own pages and nothing
    else: no grid step, no copy and no FLOP for a page past its context
    (attention_page_reads counts exactly the copies);
  * ragged spans: sequence b computes query rows t in [0, q_len[b])
    standing at context positions start_pos[b] + t; rows past q_len are
    hard-masked and produce exact zeros (padded buckets never NaN), so
    one launch serves decode (q_len=1), prefill chunks (q_len=chunk,
    start_pos=chunk offset), and dead batch slots (q_len=0: no copy,
    zeros);
  * one algorithm, two block layouts chosen from static shapes. Few
    query rows (decode, speculative verify: T * n_q <= 128): the rows
    stay (t, q-head) as q has them and the block stays (key, kv-head)
    rows as the pages hold it, scores are ONE matmul of the two and a
    row keeps the columns of its own kv-head. Otherwise (prefill
    tiles): q heads are grouped by their KV head OUTSIDE the kernel
    ([B, T, n_q, d] -> [B, n_kv, T*n_rep, d]) and the matmuls batch
    over n_kv and contract d with no head replication;
  * a block costs what it needs (few query rows): a block of a walk is
    INTERIOR when a copy filled every slot of it and every live row
    sees every key in it: its last key stands at or before row 0
    (`start_pos`) and, under a lower bound, its first key at or after
    the bound. Such a block needs no position compared: a row keeps its
    own head's columns (one additive mask, made once a launch) and
    nothing else is masked, V included. The EDGE blocks, the first
    under a bound and the last of a walk (the last ones of a span of
    several rows: its causal triangle lies in them), compare positions
    and mask V, whose slots no copy filled hold what was there before
    (0 * NaN is NaN). The walk is up to three ranges of one fold;
  * fp32 online softmax with running (m, l, acc) in VMEM scratch across
    the walk — the attention matrix never exists in HBM, and fully
    masked rows are guarded to exact zero output.

Layout: q [B, T, n_q_heads, d]; pools [num_pages, page_size, n_kv, d];
block_table [B, pages_per_seq] int32; start_pos/q_len [B] int32.
ROW POOLS, [num_pages, page_size * n_kv, d] with `kv_heads=n_kv`: a page as
its (key, kv-head) rows, which is how the few-rows layout folds it anyway.
Such a page is whole tiles whatever n_kv is (10 heads of 128 lanes: 160
rows), where `[page_size, 10, d]` would be allocated and copied as 16 heads;
only few-row spans (decode) take them.
A LOWER BOUND (`lower` [B]): sequence b's rows see no key before position
lower[b] (a sliding window's edge, or the first position a ring of pages
still holds). Pages wholly before it are neither copied nor folded, and the
first live page is masked from the bound on. Positions are the table's: its
column c holds positions [c * page_size, (c + 1) * page_size), so a caller
whose table starts at a later page passes start_pos and lower less that
page's first position.
Causality is absolute-position based: query row t of sequence b sees
keys at positions <= start_pos[b] + t, i.e. masked_cache_attention
semantics — everything already written through the block table (earlier
chunks, shared prefix pages) plus this span's own causal triangle.
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
# span rows one grid step holds in VMEM; spans that are a multiple of it
# are tiled, shorter (or odd) spans are one tile
Q_TILE = 128
# query rows (span rows x q heads) up to which a tile is folded as ONE
# matmul against the block's (key, kv-head) rows: the MXU's row count,
# below which a matmul costs what loading its other operand costs
FLAT_ROWS = 128
# what one grid step may hold of the chip's 16 MiB of scoped VMEM, by
# pages_per_block's count; the rest is room for what it does not count
VMEM_BUDGET = 12 * 2 ** 20


def _page_copy_heads(n_kv: int, itemsize: int) -> int:
    """KV heads a pool needs for the chip's compiler to copy it page by
    page: a [page_size, n_kv, d] slice has to be whole tiles, and below
    32 bits the sublane tile is 2, 4 or 8 rows (the smallest that holds
    n_kv), so n_kv is a power of two up to 8 or a multiple of 8."""
    if itemsize >= 4:
        return n_kv
    if n_kv >= 8:
        return -(-n_kv // 8) * 8
    return max(2, 1 << (n_kv - 1).bit_length())


def _tile_elems(n_kv: int, d: int, itemsize: int) -> int:
    """Elements the (n_kv, d) minor dims of a VMEM array occupy: the
    sublane dim pads to the dtype's tile (8 rows of 4 bytes, 16 of 2,
    32 of 1), the lane dim to 128."""
    sub = 32 // itemsize
    return -(-n_kv // sub) * sub * -(-d // 128) * 128


def _flat(T: int, n_q: int) -> bool:
    """Few query rows (decode, speculative verify, short chunks): the
    tile is the whole span and its rows stay (t, q-head) as q has them."""
    return T * n_q <= FLAT_ROWS


def _span_tile(T: int, n_rep: int) -> int:
    """Span rows per grid tile: the q/out blocks, the (m, l, acc)
    scratch and the score tile scale with tile rows x n_rep, so a long
    prefill span walks its pages once per tile instead of asking for
    more scoped VMEM than the chip has, and a grouped model's tile
    shrinks with its group."""
    tq = Q_TILE // (1 << (n_rep - 1).bit_length())
    return tq if tq >= 8 and T % tq == 0 else T


def pages_per_block(T: int, n_q: int, q_itemsize: int, page_size: int,
                    n_kv: int, d: int, kv_itemsize: int,
                    row_pools: bool = False) -> int:
    """Pages one block of the in-kernel walk copies and folds: the
    largest power of two at which a grid step fits VMEM_BUDGET (whatever
    the table's width: the same spans fold in the same order under any).
    Counted from static shapes, with the factors the chip's compiler was
    seen to allocate (PERF.md, PR 26): K's and V's two buffers each; per
    page of a block its float32 forms (2 where the block is folded as
    the pages hold it, 5 with the head-major copies of the batched
    matmuls) and 4-5 score tiles; per step the q and out blocks twice
    and three float32 copies of the tile's rows."""
    if row_pools:
        # a page is its rows: no head is padded
        page_f32 = page_size * n_kv * d * 4
        per_page = 4 * page_size * n_kv * d * kv_itemsize
    else:
        n_q = n_q // n_kv * _page_copy_heads(n_kv, kv_itemsize)
        n_kv, d = _page_copy_heads(n_kv, kv_itemsize), -(-d // 128) * 128
        page_f32 = page_size * _tile_elems(n_kv, d, 4) * 4
        per_page = (4 * page_size * _tile_elems(n_kv, d, kv_itemsize)
                    * kv_itemsize)
    if _flat(T, n_q):
        rows = -(-T * n_q // 8) * 8
        per_page += 2 * page_f32 + 5 * rows * page_size * n_kv * 4
    else:
        rows = n_q * _span_tile(T, n_q // n_kv)
        per_page += 5 * page_f32 + 4 * rows * page_size * 4
    fixed = (4 * q_itemsize + 12) * rows * d
    ppb = 1
    while fixed + 2 * ppb * per_page <= VMEM_BUDGET:
        ppb *= 2
    return ppb


def few_rows_block_pages(T: int, n_q: int, q_itemsize: int, page_size: int,
                         n_kv: int, d: int, kv_itemsize: int,
                         row_pools: bool = False) -> int:
    """pages_per_block of a launch whose tiles take the few-rows fold
    (the heads as the kernel pads them), 0 of one whose tiles do not:
    what a caller needs to count that fold's blocks."""
    heads = n_q if row_pools else \
        n_q // n_kv * _page_copy_heads(n_kv, kv_itemsize)
    if not _flat(T, heads):
        return 0
    return pages_per_block(T, n_q, q_itemsize, page_size, n_kv, d,
                           kv_itemsize, row_pools=row_pools)


def _tile_pages(start, qlen, t0, tq: int, page_size: int, table_width: int):
    """Pages the walk of one tile (span rows [t0, t0 + tq) of a span
    standing at `start` with `qlen` live rows) copies: those up to the
    last key its last live row sees; none for a dead slot or a tile past
    the span. The kernel's loop bound, and what a test counts."""
    last_pos = start + jnp.minimum(qlen, t0 + tq) - 1
    return jnp.where(qlen > t0,
                     jnp.minimum(last_pos // page_size + 1, table_width), 0)


def _lean_range(start, n_pages, lower, block0, end, keys: int, ppb: int,
                xp=jnp):
    """[first, last) of a few-rows walk's blocks [block0, end) that are
    INTERIOR: every slot copied, every key seen by every live row. The
    block of the lower bound is one only with the bound on its first
    key; a block ends the range when its last key stands past row 0
    (`start`) or its last page past the walk's. The kernel's three
    ranges and `ragged_block_counts` (xp=np) both come from here."""
    first = xp.minimum(xp.where(lower > block0 * keys, block0 + 1, block0),
                       end)
    last = xp.minimum((start + 1) // keys, n_pages // ppb)
    return first, xp.clip(last, first, end)


def _stacked(rows: int) -> bool:
    """The three bf16 terms of p go through the MXU as ONE product,
    stacked as rows: where they fit its rows, and each term is whole
    float32 tiles (8 rows) so the stack is made without a shuffle."""
    return 3 * rows <= FLAT_ROWS and rows % 8 == 0


def _ragged_kernel(table_ref, start_ref, qlen_ref, *refs, page_size: int,
                   n_q: int, tq: int, flat: bool, scale: float,
                   quantized: bool, bounded: bool = False,
                   kv_heads: int | None = None):
    """Grid (b, q_tile): one step folds every live page of sequence b
    into one tile of its span rows, a block of pages at a time.

    The pools stay in HBM. Block n of the tile's live pages is copied
    page by page (one async copy each, `table[b, p]` -> a slot of the
    [2, pages_per_block, ...] VMEM buffers) while block n - 1 is folded
    into (m, l, acc); after a tile's last block the copy in flight is
    the first block of the NEXT grid step, whose table row is in SMEM
    already. Pages past the tile's last visible key are never copied.

    With int8 pools the per-page-per-head scales ride the SMEM scalar
    prefetch ([num_pages, n_kv] fp32, read by the page ids the copies
    used) and the block is dequantized inside the walk; the online
    softmax stays fp32.

    flat: the q block is [rows, d], rows (t, q-head); else [n_kv, G, d],
    G rows (t, rep) of each kv-head's group.

    bounded: a fourth scalar row, `lower`: the walk of sequence b starts at
    the block that holds position lower[b], copies no page before that
    position's, and masks the keys before it. kv_heads: the pools are row
    pools (a page [page_size * kv_heads, d]) and so are the buffers."""
    if bounded:
        lower_ref, *refs = refs
    if quantized:
        kscale_ref, vscale_ref, *refs = refs
    if flat:
        *refs, own_ref = refs
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
     slot_ref) = refs
    b, i = pl.program_id(0), pl.program_id(1)
    n_seq, n_tiles = pl.num_programs(0), pl.num_programs(1)
    if kv_heads is None:
        ppb, n_kv, d = kbuf.shape[1], kbuf.shape[3], kbuf.shape[4]
    else:
        ppb, n_kv, d = kbuf.shape[1], kv_heads, kbuf.shape[3]
    n_rep = n_q // n_kv
    keys = ppb * page_size             # keys in one block
    table_width = table_ref.shape[1]

    def tile_pages(b_, i_):
        return _tile_pages(start_ref[b_], qlen_ref[b_], i_ * tq, tq,
                           page_size, table_width)

    def first_page(b_):
        """The page that holds sequence b_'s lower bound: its walk's first."""
        return lower_ref[b_] // page_size if bounded else 0

    def copies(b_, block, slot, n_pages, wait: bool = False):
        """Start (or wait for) one copy per live page of `block` of
        sequence b_'s table row into buffer `slot`, K and V."""
        first = block * ppb

        def page(r, carry):
            pid = 0 if wait else table_ref[b_, first + r]
            for which, (pool, buf) in enumerate(((k_hbm, kbuf),
                                                 (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(pool.at[pid], buf.at[slot, r],
                                           sem.at[slot, which])
                cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(jnp.clip(first_page(b_) - first, 0, ppb)
                          if bounded else 0,
                          jnp.clip(n_pages - first, 0, ppb), page, 0)

    start, qlen, t0 = start_ref[b], qlen_ref[b], i * tq
    # last key position any live row of this tile sees (causal: rows
    # past the tile never look further than its own last row)
    last_pos = start + jnp.minimum(qlen, t0 + tq) - 1
    n_pages = tile_pages(b, i)
    n_blocks = pl.cdiv(n_pages, ppb)
    # the walk's first block (0 without a bound) and how many it folds
    block0 = first_page(b) // ppb
    n_walk = jnp.maximum(n_blocks - block0, 0) if bounded else n_blocks
    lower = lower_ref[b] if bounded else 0
    # the grid step after this one: its first block is this step's to start
    wraps = i == n_tiles - 1
    nb = jnp.minimum(jnp.where(wraps, b + 1, b), n_seq - 1)
    ni = jnp.where(wraps, 0, i + 1)
    next_pages = jnp.where(wraps & (b == n_seq - 1), 0, tile_pages(nb, ni))

    @pl.when((b == 0) & (i == 0))
    def _first_step():
        slot_ref[0] = 0
        copies(b, block0, 0, n_pages)
        if flat:
            # a row keeps the columns of its own kv-head: 0 there and
            # NEG_INF elsewhere, the same for every block of every
            # sequence, so it is made (with its divisions) once a launch
            row = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 1)
            own_ref[...] = jnp.where(
                col % n_kv == row % n_q // n_rep, 0.0, NEG_INF)

    slot0 = slot_ref[0]                # buffer of this tile's block 0
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def dequantized(x, scale_ref, first):
        # the scale rows of the pages the copies read (a slot no copy
        # filled gets some page's finite scale; its keys are masked)
        sc = jnp.stack([jnp.stack([
            scale_ref[table_ref[b, jnp.minimum(first + r, table_width - 1)],
                      h] for h in range(n_kv)]) for r in range(ppb)])
        x = x.astype(jnp.float32).reshape(ppb, page_size, n_kv, d)
        return (x * sc[:, None, :, None]).reshape(keys, n_kv, d)

    def fold(block, slot, edge: bool):
        """Fold block `block` of the walk, in buffer `slot`, into (m, l,
        acc). edge (static): the block may hold a key that some live row
        does not see, or a slot that no copy filled, so key positions are
        compared with each row's and V is masked. An INTERIOR block
        (edge=False, few-row tiles only; `_lean_range` says which) was
        copied whole and lies at or before row 0's position and at or
        after the bound: every live row sees all of it, so the fold
        compares no position and masks no V. Rows past q_len then fold
        what they do not see; the output cuts them to zero."""
        key0 = block * keys
        # a block as (key, kv-head, d), or as its rows from row pools
        as_held = (keys, n_kv, d) if kv_heads is None else (keys * n_kv, d)
        k, v = kbuf[slot].reshape(as_held), vbuf[slot].reshape(as_held)
        if quantized:
            k = dequantized(k, kscale_ref, block * ppb)
            v = dequantized(v, vscale_ref, block * ppb)
        if edge:
            # the slots of a partial block that no copy filled hold what
            # was there before, and 0 * NaN is NaN: V is masked, not only
            # scores. Only an edge block has such slots. Rows are
            # key-major (a key's heads, in row pools): the live ones are
            # a range, no row is divided
            per_key = 1 if kv_heads is None else n_kv
            v_row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v_live = v_row < (last_pos - key0 + 1) * per_key
            if bounded:
                v_live &= v_row >= (lower - key0) * per_key
            v = jnp.where(v_live, v, jnp.zeros_like(v))
        if v.dtype != jnp.bfloat16:
            v = v.astype(jnp.float32)
        if flat:
            # scores[rows, keys * n_kv]: every query row against every
            # (key, kv-head) row of the block as the pages hold them, one
            # matmul; a row keeps the columns of its own kv-head
            q = q_ref[0]                               # row (t, q-head)
            k = k.reshape(keys * n_kv, d)              # (row pools: as is)
            v = v.reshape(keys * n_kv, d)
            if k.dtype != q.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale + own_ref[...]
            if edge:
                # one compare over the tile: a column (key-major, so a
                # key's columns are a range; BIG before the bound) against
                # the end of what its row sees (none for a row past q_len);
                # one tile: t0 is 0
                col = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
                if bounded:
                    col = jnp.where(col >= (lower - key0) * n_kv, col,
                                    jnp.iinfo(jnp.int32).max)
                t_idx = jax.lax.broadcasted_iota(
                    jnp.int32, (s.shape[0], 1), 0) // n_q
                sees = jnp.where(t_idx < qlen,
                                 (start + t_idx - key0 + 1) * n_kv, 0)
                s = jnp.where(jnp.broadcast_to(col, s.shape)
                              < jnp.broadcast_to(sees, s.shape), s, NEG_INF)
        else:
            # scores[n_kv, G, keys]: batch the KV-head dim, contract d —
            # each KV head serves its n_rep grouped query rows with no
            # replication
            q = q_ref[0].astype(jnp.float32)           # [n_kv, G, d]
            s = jax.lax.dot_general(
                q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32)
            # grouped row r is (t, rep) flattened; its query position is
            # start + t with t = t0 + r // n_rep
            t_idx = t0 + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) // n_rep
            k_pos = key0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            # rows t >= qlen are padding
            seen = (k_pos <= start + t_idx) & (t_idx < qlen)
            if bounded:
                seen &= k_pos >= lower
            s = jnp.where(seen, s * scale, NEG_INF)
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        if edge:
            # masked-row guard: where every key so far is hard-masked,
            # new_m is still NEG_INF and exp(s - new_m) would be 1 — force
            # 0 so the row's l stays 0 and its output is exactly zero. (In
            # an interior block every live row has a key, and a masked
            # column's exp is 0 by itself)
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        corr = jnp.exp(m - new_m)
        m_ref[...] = new_m
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if flat:
            def p_v(t):
                return jax.lax.dot_general(
                    t.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            # p stays float32: against bf16 pages it goes through the
            # MXU as three bf16 terms that sum to it, not rounded to one
            if v.dtype != jnp.bfloat16:
                pv = p_v(p)
            else:
                rows = p.shape[0]
                lo = p - p.astype(v.dtype).astype(jnp.float32)
                terms = [p, lo, lo - lo.astype(v.dtype).astype(jnp.float32)]
                if _stacked(rows):
                    # ONE product of the terms stacked as rows: with few
                    # rows a product costs what loading V's tiles into the
                    # MXU costs, and they are loaded once. The same
                    # products and the same sum of three; the chip adds a
                    # taller product's pieces in another order, which a
                    # bf16 output shows in its last bit here and there
                    pv3 = p_v(jnp.concatenate(terms, axis=0))
                    terms = [pv3[n * rows:(n + 1) * rows] for n in range(3)]
                else:
                    terms = [p_v(t) for t in terms]
                pv = terms[0] + terms[1] + terms[2]
        else:
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), (((2,), (0,)), ((0,), (1,))),
                preferred_element_type=jnp.float32)    # [n_kv, G, d]
        acc_ref[...] = acc_ref[...] * corr + pv

    next_block0 = first_page(nb) // ppb

    def walk(edge: bool):
        def step(block, carry):
            slot = (slot0 + block - block0) % 2
            # in flight while this block is folded: the tile's next block,
            # or after its last the first block of the next grid step
            more = block + 1 < n_blocks
            copies(jnp.where(more, b, nb),
                   jnp.where(more, block + 1, next_block0),
                   1 - slot, jnp.where(more, n_pages, next_pages))
            copies(b, block, slot, n_pages, wait=True)
            fold(block, slot, edge)
            return carry
        return step

    # the walk in up to three ranges: the bound's block, the interior
    # blocks, the last block(s). Prefill tiles fold every block in full
    end = block0 + n_walk
    first, last = _lean_range(start, n_pages, lower, block0, end, keys,
                              ppb) if flat else (end, end)
    if bounded or not flat:
        jax.lax.fori_loop(block0, first, walk(True), 0)
    if flat:
        jax.lax.fori_loop(first, last, walk(False), 0)
        jax.lax.fori_loop(last, end, walk(True), 0)

    @pl.when(n_walk == 0)
    def _dead_step():                  # nothing to fold: hand on the start
        copies(nb, next_block0, slot0, next_pages)

    slot_ref[0] = (slot0 + n_walk) % 2
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    if flat and tq > 1:
        # rows past q_len: an interior block folded them unmasked
        t_idx = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) // n_q
        out = jnp.where(t_idx < qlen, out, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, block_table, start_pos, q_len,
                           scale=None, interpret: bool | None = None,
                           k_scale=None, v_scale=None, lower=None,
                           kv_heads: int | None = None):
    """Causal attention for a ragged batch of query spans over paged KV.

    q: [B, T, n_q_heads, d] — T is the PADDED span length (power-of-2
    bucket); pools: [num_pages, page_size, n_kv_heads, d];
    block_table: [B, pages_per_seq] int32; start_pos: [B] int32 (context
    position of each span's row 0); q_len: [B] int32 (live rows per
    span; 0 = dead slot). Query row t of sequence b attends keys at
    positions <= start_pos[b] + t. Rows past q_len output exact zeros.
    Returns [B, T, n_q_heads, d].

    Quantized pools (ISSUE 9): pass int8 code pools plus
    k_scale/v_scale [num_pages, n_kv_heads] fp32 (one scale per page
    per kv-head). The scales ride the SMEM scalar prefetch next to the
    block tables and each block of pages is dequantized inside the walk
    — HBM traffic is the int8 bytes + the scale rows, while the online
    softmax stays fp32.

    A head layout whose pages are not whole tiles (head_dim not a
    multiple of 128; see _page_copy_heads) is padded to one HERE, pools
    included: a copy of both pools per call. Such a model's pools
    should be allocated padded (ROADMAP S1), or as row pools.

    lower [B] int32: no row of sequence b sees a key before position
    lower[b] (the head of this file). kv_heads: the pools are ROW POOLS,
    [num_pages, page_size * kv_heads, d] (few-row spans only, d a multiple
    of 128, no int8 scales).
    """
    n_q, n_kv = q.shape[2], kv_heads or k_pool.shape[2]
    if kv_heads is not None:
        if k_pool.ndim != 3 or k_pool.shape[1] % kv_heads \
                or q.shape[3] % 128 or k_scale is not None \
                or not _flat(q.shape[1], n_q):
            raise ValueError(
                f"row pools are [pages, page_size * {kv_heads}, d] with d a "
                "multiple of 128, unquantized, under few-row spans; got "
                f"pools {k_pool.shape}, q {q.shape}")
    if n_q % n_kv:
        raise ValueError(f"n_q_heads={n_q} not a multiple of "
                         f"n_kv_heads={n_kv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ragged_call(
        q, k_pool, v_pool, block_table, start_pos, q_len, k_scale, v_scale,
        lower,
        scale=float(scale if scale is not None else 1.0 / np.sqrt(q.shape[3])),
        interpret=bool(interpret), kv_heads=kv_heads)


# jitted here, not only by the caller: a model's layers call it with the
# same shapes, and a jitted callee is traced and lowered once per
# program, not once per layer (24 kernels of some 20 k characters of
# MLIR each in a GPT-3 1.3B step, which set-up pays for warm or cold)
@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "kv_heads"))
def _ragged_call(q, k_pool, v_pool, block_table, start_pos, q_len, k_scale,
                 v_scale, lower=None, *, scale: float, interpret: bool,
                 kv_heads: int | None = None):
    B, T, n_q, d = q.shape
    if kv_heads is None:
        page_size, n_kv = k_pool.shape[1], k_pool.shape[2]
    else:
        page_size, n_kv = k_pool.shape[1] // kv_heads, kv_heads
    quantized = k_scale is not None
    bounded = lower is not None
    n_rep = n_q // n_kv
    pad_kv = 0 if kv_heads else \
        _page_copy_heads(n_kv, k_pool.dtype.itemsize) - n_kv
    pad_d = -d % 128
    if pad_kv or pad_d:
        # zero heads and zero lanes: the scores of the real heads are
        # unchanged, the added ones come out zero and are cut off
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_kv * n_rep), (0, pad_d)))
        pool_pad = ((0, 0), (0, 0), (0, pad_kv), (0, pad_d))
        k_pool, v_pool = jnp.pad(k_pool, pool_pad), jnp.pad(v_pool, pool_pad)
        if quantized:
            k_scale, v_scale = (jnp.pad(x, ((0, 0), (0, pad_kv)))
                                for x in (k_scale, v_scale))
        out = _ragged_call(q, k_pool, v_pool, block_table, start_pos, q_len,
                           k_scale, v_scale, lower, scale=scale,
                           interpret=interpret)
        return out[:, :, :n_q, :d]
    start_arr = jnp.broadcast_to(
        jnp.asarray(start_pos, jnp.int32).reshape(-1), (B,))
    qlen_arr = jnp.broadcast_to(
        jnp.asarray(q_len, jnp.int32).reshape(-1), (B,))
    ppb = pages_per_block(T, n_q, q.dtype.itemsize, page_size, n_kv, d,
                          k_pool.dtype.itemsize, row_pools=bool(kv_heads))
    flat = _flat(T, n_q)
    if flat:
        # rows stay (t, q-head) as q has them: one tile, no transposes
        tq, rows = T, T * n_q
        qg = q.reshape(B, rows, d)
        q_spec = pl.BlockSpec((1, rows, d), lambda b, i, *_: (b, 0, 0))
        stats, acc = (rows, 1), (rows, d)
    else:
        tq = _span_tile(T, n_rep)
        G = n_rep * tq
        # group q heads by KV head outside the kernel (XLA transpose) so
        # the kernel body needs no layout shuffles: row r of group g =
        # (t, rep)
        qg = q.reshape(B, T, n_kv, n_rep, d).transpose(0, 2, 1, 3, 4)
        qg = qg.reshape(B, n_kv, T * n_rep, d)
        q_spec = pl.BlockSpec((1, n_kv, G, d), lambda b, i, *_: (b, 0, i, 0))
        stats, acc = (n_kv, G, 1), (n_kv, G, d)
    kv_buf = pltpu.VMEM((2, ppb) + k_pool.shape[1:], k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # quantized pools prefetch the scale rows alongside the tables:
        # the last two scalars are k_scale/v_scale, read per page id; a
        # bounded walk's lower bounds ride before them
        num_scalar_prefetch=3 + bounded + 2 * quantized,
        grid=(B, T // tq),
        in_specs=[q_spec,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            kv_buf, kv_buf,
            pltpu.SemaphoreType.DMA((2, 2)),       # [slot, K or V]
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM(acc, jnp.float32),
            pltpu.SMEM((1,), jnp.int32),           # slot of the next block 0
        ] + ([pltpu.VMEM((rows, ppb * page_size * n_kv), jnp.float32)]
             if flat else []),                     # a row's own-head mask
    )
    scalars = (block_table.astype(jnp.int32), start_arr, qlen_arr)
    if bounded:
        scalars += (jnp.broadcast_to(
            jnp.asarray(lower, jnp.int32).reshape(-1), (B,)),)
    if quantized:
        scalars += (jnp.asarray(k_scale, jnp.float32),
                    jnp.asarray(v_scale, jnp.float32))
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size, n_q=n_q,
                          tq=tq, flat=flat, scale=scale,
                          quantized=quantized, bounded=bounded,
                          kv_heads=kv_heads),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape,
                                       jnp.float32 if quantized else q.dtype),
        # a step starts the next step's first copies: the grid is a
        # sequence, in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ragged_paged_attn",
    )(*scalars, qg, k_pool, v_pool)
    out = out.astype(q.dtype)
    if not flat:
        out = out.reshape(B, n_kv, T, n_rep, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, n_q, d)


def ragged_attention_ok(head_dim: int, n_q_heads: int,
                        n_kv_heads: int) -> bool:
    """Kernel tiling gate: Mosaic needs the lane dim 8-aligned, and GQA
    grouping needs the query heads to split evenly over the KV heads."""
    return head_dim % 8 == 0 and n_q_heads % max(1, n_kv_heads) == 0


def ragged_reference(q, k_pool, v_pool, block_table, start_pos, q_len,
                     scale=None, k_scale=None, v_scale=None, lower=None,
                     kv_heads: int | None = None):
    """Gather + dense-mask oracle with the kernel's exact output contract
    (padded rows and dead slots produce exact zeros). O(B * pages_per_seq
    * page_size) HBM — the path the kernel exists to retire; kept as the
    bit-level comparison target for tests and the CPU reference.

    With k_scale/v_scale (int8 pools, ISSUE 9) the gathered codes are
    dequantized with the SAME per-page-per-head scales the kernel reads
    — kernel-vs-reference comparisons stay exact in the int8 domain
    (both dequantize identical codes with identical scales)."""
    B, T, n_q, d = q.shape
    if kv_heads is not None:             # row pools: a page's rows by head
        k_pool, v_pool = (a.reshape(a.shape[0], -1, kv_heads, d)
                          for a in (k_pool, v_pool))
    page_size = k_pool.shape[1]
    n_kv = k_pool.shape[2]
    n_rep = n_q // n_kv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kg = k_pool[block_table]             # [B, P, ps, n_kv, d]
    vg = v_pool[block_table]
    if k_scale is not None:
        ks = jnp.asarray(k_scale, jnp.float32)[block_table]  # [B, P, n_kv]
        vs = jnp.asarray(v_scale, jnp.float32)[block_table]
        kg = kg.astype(jnp.float32) * ks[:, :, None, :, None]
        vg = vg.astype(jnp.float32) * vs[:, :, None, :, None]
    L = kg.shape[1] * page_size
    kg = kg.reshape(B, L, n_kv, d)
    vg = vg.reshape(B, L, n_kv, d)
    if n_rep > 1:
        kg = jnp.repeat(kg, n_rep, axis=2)
        vg = jnp.repeat(vg, n_rep, axis=2)
    start = jnp.asarray(start_pos, jnp.int32).reshape(-1)
    qlen = jnp.asarray(q_len, jnp.int32).reshape(-1)
    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)        # [B, nq, T, d]
    kT = jnp.swapaxes(kg, 1, 2).astype(jnp.float32)       # [B, nq, L, d]
    vT = jnp.swapaxes(vg, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhtd,bhLd->bhtL", qT, kT) * scale
    t_idx = jnp.arange(T, dtype=jnp.int32)
    q_pos = start[:, None] + t_idx[None, :]               # [B, T]
    k_pos = jnp.arange(L, dtype=jnp.int32)
    visible = ((k_pos[None, None, :] <= q_pos[:, :, None])
               & (t_idx[None, :, None] < qlen[:, None, None]))  # [B, T, L]
    if lower is not None:
        visible &= k_pos[None, None, :] >= jnp.asarray(
            lower, jnp.int32).reshape(-1)[:, None, None]
    s = jnp.where(visible[:, None], s, NEG_INF)
    row_live = jnp.any(s > NEG_INF * 0.5, axis=-1, keepdims=True)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(row_live, p, 0.0)
    out = jnp.einsum("bhtL,bhLd->bhtd", p, vT).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)


def attention_page_reads(start_pos, q_len, page_size: int):
    """Pages a ragged-kernel launch actually reads, per sequence: the
    walk copies pages [0, last_visible_page] and nothing for dead slots
    (a span of several tiles walks them once per tile; the count is the
    distinct pages). Host-side analytics for the instrumented-pool
    counter — the CPU-countable half of the kernel's bandwidth claim."""
    start = np.asarray(start_pos, np.int64).reshape(-1)
    qlen = np.asarray(q_len, np.int64).reshape(-1)
    last = np.maximum(start + qlen - 1, 0)
    return np.where(qlen > 0, last // page_size + 1, 0)


def ragged_block_counts(start_pos, q_len, page_size: int, ppb: int,
                        lower=None):
    """(blocks, edge blocks) the few-rows walk of a launch folds, per
    sequence: all the blocks of `ppb` pages from the lower bound's to
    the last visible key's, and those of them folded in full (positions
    compared, V masked) because they are not interior. Host arithmetic
    on the launch's own operands, beside attention_page_reads; the
    kernel splits its walk by the same `_lean_range`."""
    start = np.asarray(start_pos, np.int64).reshape(-1)
    lower = np.zeros_like(start) if lower is None else \
        np.asarray(lower, np.int64).reshape(-1)
    n_pages = attention_page_reads(start, q_len, page_size)
    block0 = lower // page_size // ppb
    end = np.maximum(-(-n_pages // ppb), block0)
    first, last = _lean_range(start, n_pages, lower, block0, end,
                              ppb * page_size, ppb, xp=np)
    return end - block0, end - block0 - (last - first)
