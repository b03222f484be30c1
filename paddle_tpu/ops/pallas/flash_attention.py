"""Flash attention as a Pallas TPU kernel — dense, masked, and varlen.

Reference: the reference wraps the CUDA flashattn library
(paddle/phi/kernels/gpu/flash_attn_kernel.cu over third_party/flashattn,
exposed via nn/functional/flash_attention.py:358, flash_attn_unpadded at
:756 and flashmask_attention at :1299). On TPU the kernel is written in
Pallas, and what it costs is grid steps (a third to half a microsecond
each, whatever they compute), so a head takes a handful of them:

  * `schedule()` chooses the tiles from the call's shapes, dtype and masks
    (TILE_CAP rows a side where the sequence allows, a sequence's own length
    below that, the block table's granularity where one is given) and a
    SPAN: how many rows of the walked operand one grid step holds in VMEM
    (the whole sequence while it fits VMEM_BUDGET);
  * forward and dQ run on grid (batch*head, sq / block_q, sk / span): K and
    V of the span are VMEM-resident and the walk over their tiles is a loop
    INSIDE the kernel, bounded by the causal diagonal, so no step and no
    fetch is spent above it; dK/dV runs on (batch*head, sk / block_k,
    sq / span) and walks Q / dO tiles from the diagonal on. Where a
    sequence outgrows one span the third axis has several steps, the
    accumulators ride VMEM scratch across them, and the index map of a
    span wholly above the diagonal clamps to the last live one (a dead step
    copies nothing);
  * the MXU gets the operands' own dtype (bfloat16 under autocast O1,
    float32 where the caller gave float32) with float32 products; P and dS
    are cast to it for the second matmul of each pair. Scores, running max
    and sum, lse, delta and the accumulators are float32;
  * dK/dV works on TRANSPOSED score tiles (keys on rows, queries on lanes):
    every matmul of it is then plain or transposed-right, and the per-query
    statistics are read as the lane-dense rows they are stored as.

The attention matrix never exists in HBM; per-row statistics (lse, delta)
are [batch*head, 1, sq] float32, whole 128-lane rows.

Masking (four independent mechanisms, composable with `causal`):
  * additive mask — an fp32 [b, 1|h, sq, sk] bias streamed a (block_q,
    span) slab a grid step and added to the scores (the reference's
    attn_mask semantic; the bias itself is O(s^2) HBM but the score matrix
    still never materializes and the read is fused into the attention loop);
  * kv bias — an fp32 [b, sk] per-KEY additive bias: the O(s) form of the
    ubiquitous key-padding mask ([b, 1, 1, sk] attn_mask shapes lower
    here, NOT to a dense O(s^2) broadcast), exact additive semantics at
    every query row;
  * segment ids — int32 [b, sq] / [b, sk] per-token ids; attention is
    allowed only where q_seg == k_seg. This is the varlen/packed form:
    flash_attn_unpadded's cu_seqlens lower onto it with O(s) memory, the
    same design as jax.experimental.pallas.ops.tpu flash attention;
  * bool masks are canonicalized to additive NEG_INF outside the kernel.

Fully-masked rows are well-defined: the online-softmax guard zeroes
probabilities where the score is hard-masked, so such rows produce 0
output and 0 gradient instead of NaN.

Forward and backward are Pallas kernels (FlashAttention-2 style backward:
a dQ kernel accumulating over K tiles and a dK/dV kernel accumulating over
Q tiles, both recomputing P from the saved per-row log-sum-exp).

Layout: [batch, seq, heads, head_dim] (paddle flash-attn convention).
Causal masking is bottom-right aligned (tril k=sk-sq), matching the XLA
reference path for cross-length (KV-decode) shapes.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

try:  # TPU-specific memory spaces (absent on pure-CPU builds)
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30
# Hard-mask detection threshold for the fully-masked-row guard: scores at
# or below this are treated as "structurally masked" and contribute exactly
# zero probability in both fwd and bwd (real scores never get near -5e29).
MASKED_BELOW = NEG_INF * 0.5
# rows a side of a score tile where the sequence allows it: the softmax's
# per-row bookkeeping is amortised over a tile's columns, and a grid step
# costs what some 128 x 128 x 64 tiles cost to compute
TILE_CAP = 512
# what one grid step may hold of the chip's 16 MiB of scoped VMEM, by
# schedule()'s own count (blocks twice for the pipeline, scratch, a few
# score tiles of temporaries)
VMEM_BUDGET = 12 * 2 ** 20
LANES = 128


def _tile(n: int, cap: int = TILE_CAP):
    """The largest multiple of 128 up to `cap` that divides n; a sequence
    of up to 128 is its own tile; None where nothing tiles."""
    if n <= LANES:
        return n
    fits = [t for t in range(LANES, min(cap, n) + 1, LANES) if n % t == 0]
    return max(fits) if fits else None


class Schedule(NamedTuple):
    """What one call of the three kernels runs. `steps`, `tiles` and
    `dead_steps` are per kernel, (fwd, bwd_dq, bwd_dkv): grid steps, score
    tiles folded (causal geometry; a block table may skip more), and grid
    steps whose span lies wholly above the diagonal (they copy and fold
    nothing)."""
    block_q: int
    block_k: int
    span_q: int
    span_k: int
    steps: tuple
    tiles: tuple
    dead_steps: tuple


def _vmem_bytes(tile_rows: int, walked_rows: int, block_q: int, block_k: int,
                d: int, itemsize: int, mask: bool) -> int:
    """Scoped VMEM of one grid step, counted from above: a tile of
    `tile_rows` on the grid's side (at most four blocks: k, v, dk, dv) and
    `walked_rows` of the two operands it walks, each block twice for the
    pipeline; the accumulators and row statistics; a dense mask's slab;
    five score tiles of float32 temporaries. A minor dimension pads to
    whole 128-lane tiles."""
    dl = -(-d // LANES) * LANES
    blocks = 2 * (4 * tile_rows + 2 * walked_rows) * dl * itemsize
    scratch = 2 * tile_rows * (dl + LANES) * 4
    slab = 2 * tile_rows * walked_rows * 4 if mask else 0
    return blocks + scratch + slab + 5 * block_q * block_k * 4


def schedule(q_shape, k_shape, dtype, causal: bool, *, mask: bool = False,
             block_mask_shape=None, block_q=None, block_k=None, span=None):
    """The tiles and grids of one call, a pure function of what the call
    can see: q [b, sq, h, d], k [b, sk, h, d], the operand dtype, whether a
    dense additive mask streams, a block table's shape. `block_q`,
    `block_k` and `span` force a choice (tests at toy sizes only). None
    where the shapes do not tile."""
    b, sq, h, d = q_shape
    sk = k_shape[1]
    if block_mask_shape is not None:       # the table's granularity rules
        nqb, nkb = block_mask_shape
        if sq % nqb or sk % nkb:
            return None
        block_q, block_k = sq // nqb, sk // nkb
    block_q = min(block_q, sq) if block_q else _tile(sq)
    block_k = min(block_k, sk) if block_k else _tile(sk)
    if not block_q or not block_k or sq % block_q or sk % block_k:
        return None
    nq, nk = sq // block_q, sk // block_k

    def walked(n_tiles, tile_rows, walked_tile):
        """Tiles of the walked side one grid step holds: the whole
        sequence while it fits, else its largest divisor that does."""
        if span is not None:
            return max(1, min(span, n_tiles * walked_tile) // walked_tile)
        return next((t for t in range(n_tiles, 0, -1) if n_tiles % t == 0
                     and _vmem_bytes(tile_rows, t * walked_tile, block_q,
                                     block_k, d, jnp.dtype(dtype).itemsize,
                                     mask) <= VMEM_BUDGET), None)

    tq, tk = walked(nq, block_k, block_q), walked(nk, block_q, block_k)
    if tq is None or tk is None:
        # one tile overflows by itself (a dense mask's slab): halve it
        if block_mask_shape is not None or max(block_q, block_k) <= LANES:
            return None
        return schedule(q_shape, k_shape, dtype, causal, mask=mask,
                        block_q=_tile(sq, max(LANES, block_q // 2)),
                        block_k=_tile(sk, max(LANES, block_k // 2)))
    if nq % tq or nk % tk:
        return None
    off = sk - sq
    # per q tile, the k tiles the diagonal leaves alive; per k tile, the
    # first q tile that sees it
    live = [min(nk, (off + (i + 1) * block_q - 1) // block_k + 1)
            if causal else nk for i in range(nq)]
    first = [max(0, (j * block_k - off) // block_q) if causal else 0
             for j in range(nk)]
    dead_q = sum(sum(j * tk >= n for j in range(nk // tk)) for n in live)
    dead_k = sum(sum((m + 1) * tq <= f for m in range(nq // tq))
                 for f in first)
    walk_q, walk_k = b * h * nq * (nk // tk), b * h * nk * (nq // tq)
    return Schedule(block_q, block_k, tq * block_q, tk * block_k,
                    (walk_q, walk_q, walk_k), (b * h * sum(live),) * 3,
                    (b * h * dead_q, b * h * dead_q, b * h * dead_k))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract, ((), ()))),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _column(ref):
    """A per-row vector stored lane-dense, (1, 1, n), as a column (n, 1)."""
    return ref[0, 0][:, None]


def _tile_scores(q, k_tile, keys_on_rows: bool, scale, mask=None,
                 kbias=None, qseg=None, kseg=None, q_pos=None, k_pos=None):
    """Shared per-tile scaled+masked scores (ONE definition of the causal /
    additive / kv-bias / segment masks for fwd and both bwd kernels):
    (block_q, block_k), or its transpose where `keys_on_rows`. The vectors
    arrive oriented to it (per-query ones columns and per-key ones rows, or
    the other way round); `mask` is the (block_q, block_k) tile as stored;
    `q_pos` / `k_pos` are the positions the causal rule compares (None on
    a tile wholly below the diagonal)."""
    s = (_dot(k_tile, q, _NT) if keys_on_rows else _dot(q, k_tile, _NT))
    s = s * scale
    if mask is not None:
        mask = mask.astype(jnp.float32)
        s = s + (mask.T if keys_on_rows else mask)
    if kbias is not None:
        s = s + kbias
    if qseg is not None:
        s = jnp.where(qseg == kseg, s, NEG_INF)
    if q_pos is not None:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


def _positions(block_q, block_k, keys_on_rows: bool):
    """In-tile query and key indices, each a vector along its own side of
    a score tile: (block_q, 1) and (1, block_k), or (1, block_q) and
    (block_k, 1) where keys ride the rows."""
    qs, ks = ((1, block_q), (block_k, 1)) if keys_on_rows else (
        (block_q, 1), (1, block_k))
    qd, kd = (1, 0) if keys_on_rows else (0, 1)
    return (jax.lax.broadcasted_iota(jnp.int32, qs, qd),
            jax.lax.broadcasted_iota(jnp.int32, ks, kd))


def _split_refs(refs, n_lead, has_mask, has_kbias, has_seg,
                has_blockmask=False):
    """Peel (mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest) off a
    flat pallas ref list after the first `n_lead` fixed inputs."""
    i = n_lead
    mask_ref = kbias_ref = qseg_ref = kseg_ref = bm_ref = None
    if has_mask:
        mask_ref = refs[i]
        i += 1
    if has_kbias:
        kbias_ref = refs[i]
        i += 1
    if has_seg:
        qseg_ref, kseg_ref = refs[i], refs[i + 1]
        i += 2
    if has_blockmask:
        bm_ref = refs[i]
        i += 1
    return mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, refs[i:]


def _tile_at(t, block: int, tiles: int):
    """Rows (or lanes) of tile t of a span; a span of one tile is read
    whole, statically, so a sequence shorter than 128 needs no aligned
    dynamic slice."""
    if tiles == 1:
        return pl.ds(0, block)
    return pl.ds(pl.multiple_of(t * block, block), block)


def _walk(phases, tile, live=None):
    """Fold this grid step's tiles: `phases` is ((lo, hi, on_diagonal),
    ...), `tile(t, on_diagonal)` folds tile t of the span (only a tile the
    diagonal crosses pays for the causal compare), `live(t)` says whether
    a block table keeps it."""
    for lo, hi, on_diagonal in phases:
        def body(t, carry, on_diagonal=on_diagonal):
            if live is None:
                tile(t, on_diagonal)
            else:
                pl.when(live(t))(lambda: tile(t, on_diagonal))
            return carry

        jax.lax.fori_loop(lo, hi, body, None)


def _q_walk_kernel(*refs, block_k: int, causal: bool, scale: float,
                   off: int, has_mask: bool, has_kbias: bool, has_seg: bool,
                   has_blockmask: bool, backward: bool, with_lse: bool):
    """Forward (`backward` False) and dQ: one grid step folds the live K /
    V tiles of its span into this Q block's accumulators.
    dQ_i = scale * sum_j dS_ij K_j, dS = P * (dO V^T - delta)."""
    n_lead = 4 if backward else 3
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, n_lead, has_mask, has_kbias, has_seg, has_blockmask)
    if backward:
        do_ref = refs[3]
        lse_ref, delta_ref, dq_ref, acc_ref = rest
    elif with_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (o_ref, m_ref, l_ref, acc_ref), lse_ref = rest, None
    block_q, d = q_ref.shape[1:]
    tiles = k_ref.shape[1] // block_k          # of this step's span
    qi, kj = pl.program_id(1), pl.program_id(2)
    guard = has_mask or has_kbias or has_seg or has_blockmask

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)
        if not backward:
            m_ref[:] = jnp.full((block_q, 1), NEG_INF, jnp.float32)
            l_ref[:] = jnp.zeros((block_q, 1), jnp.float32)

    q = q_ref[0]
    # bottom-right-aligned causal offset: query i sees keys <= i + (sk - sq)
    q_start = off + qi * block_q
    phases = ((0, tiles, False),)
    if causal:
        # tiles wholly below the diagonal, then those it crosses; none
        # above it
        first = kj * tiles
        hi = jnp.clip((q_start + block_q - 1) // block_k + 1 - first,
                      0, tiles)
        mid = jnp.clip((q_start + 1) // block_k - first, 0, hi)
        phases = ((0, mid, False), (mid, hi, True))
        q_pos, k_pos = _positions(block_q, block_k, False)
    qseg = _column(qseg_ref) if has_seg else None
    if backward:
        do = do_ref[0]
        lse, delta = _column(lse_ref), _column(delta_ref)

    def tile(t, on_diagonal):
        at = _tile_at(t, block_k, tiles)
        k_tile, v_tile = k_ref[0, at, :], v_ref[0, at, :]
        at_diagonal = {}
        if on_diagonal:     # q_start + i >= k_start + j, the shift on the row
            at_diagonal = dict(q_pos=q_pos, k_pos=k_pos + (
                (kj * tiles + t) * block_k - q_start))
        s = _tile_scores(
            q, k_tile, False, scale,
            mask=mask_ref[0, :, at] if has_mask else None,
            kbias=kbias_ref[0, :, at] if has_kbias else None,
            qseg=qseg, kseg=kseg_ref[0, :, at] if has_seg else None,
            **at_diagonal)
        if backward:
            # hard-masked entries get exactly 0 even on fully-masked rows
            # where the saved lse is itself ~NEG_INF (exp(s - lse) would
            # be exp(0) = 1 there)
            p = jnp.exp(s - lse)
            if guard:
                p = jnp.where(s <= MASKED_BELOW, 0.0, p)
            ds = p * (_dot(do, v_tile, _NT) - delta)
            acc_ref[:] += _dot(ds.astype(k_tile.dtype), k_tile, _NN)
            return
        m = m_ref[:]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        if guard:
            # on a row where every key so far is hard-masked, new_m is
            # still NEG_INF and exp(s - new_m) would be exp(0) = 1 — force
            # 0 so the row's l stays 0 and its output is exactly zero
            # (causal alone needs none: the walk starts at tile 0, where
            # every row sees key 0, and exp(NEG_INF - finite) is exactly 0)
            p = jnp.where(s <= MASKED_BELOW, 0.0, p)
        corr = jnp.exp(m - new_m)
        m_ref[:] = new_m
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + _dot(p.astype(v_tile.dtype),
                                              v_tile, _NN)

    _walk(phases, tile, None if bm_ref is None else
          lambda t: bm_ref[qi, kj * tiles + t] > 0)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        if backward:
            dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)
            return
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp per row, saved lane-dense for the backward kernels
            lse_ref[0, 0] = (m_ref[:] + jnp.log(l))[:, 0]


def _k_walk_kernel(*refs, block_q: int, causal: bool, scale: float,
                   off: int, has_mask: bool, has_kbias: bool, has_seg: bool,
                   has_blockmask: bool):
    """dV_j = P^T dO; dK_j = scale * dS^T Q: one grid step folds the live
    Q / dO tiles of its span into this K block's accumulators, on
    transposed score tiles (keys on rows, queries on lanes)."""
    q_ref, k_ref, v_ref, do_ref = refs[:4]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, 4, has_mask, has_kbias, has_seg, has_blockmask)
    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    block_k, d = k_ref.shape[1:]
    tiles = q_ref.shape[1] // block_q          # of this step's span
    kj, qm = pl.program_id(1), pl.program_id(2)
    guard = has_mask or has_kbias or has_seg or has_blockmask

    @pl.when(qm == 0)
    def _init():
        dk_acc[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc[:] = jnp.zeros((block_k, d), jnp.float32)

    k_tile, v_tile = k_ref[0], v_ref[0]
    k_start = kj * block_k - off
    phases = ((0, tiles, False),)
    if causal:
        # a q block contributes iff its LAST query can see this k tile; the
        # diagonal crosses it unless its FIRST query sees the tile's last key
        first = qm * tiles
        lo = jnp.clip(jnp.maximum(k_start, 0) // block_q - first, 0, tiles)
        mid = jnp.clip(
            (jnp.maximum(k_start + block_k - 1, 0) + block_q - 1) // block_q
            - first, lo, tiles)
        phases = ((lo, mid, True), (mid, tiles, False))
        q_pos, k_pos = _positions(block_q, block_k, True)
    kbias = _column(kbias_ref) if has_kbias else None
    kseg = _column(kseg_ref) if has_seg else None

    def tile(t, on_diagonal):
        at = _tile_at(t, block_q, tiles)
        q, do = q_ref[0, at, :], do_ref[0, at, :]
        at_diagonal = {}
        if on_diagonal:
            at_diagonal = dict(k_pos=k_pos, q_pos=q_pos + (
                (qm * tiles + t) * block_q - k_start))
        s = _tile_scores(
            q, k_tile, True, scale,
            mask=mask_ref[0, at, :] if has_mask else None, kbias=kbias,
            qseg=qseg_ref[0, :, at] if has_seg else None, kseg=kseg,
            **at_diagonal)
        p = jnp.exp(s - lse_ref[0, :, at])
        if guard:
            p = jnp.where(s <= MASKED_BELOW, 0.0, p)
        ds = p * (_dot(v_tile, do, _NT) - delta_ref[0, :, at])
        dv_acc[:] += _dot(p.astype(do.dtype), do, _NN)
        dk_acc[:] += _dot(ds.astype(q.dtype), q, _NN)

    _walk(phases, tile, None if bm_ref is None else
          lambda t: bm_ref[qm * tiles + t, kj] > 0)

    @pl.when(qm == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _specs(sch: Schedule, d: int, h: int, causal: bool, off: int,
           walk: str):
    """The BlockSpecs of one kernel's grid, by what they carry. walk 'k':
    grid (bh, q tile, k span) — fwd and dQ; walk 'q': grid (bh, k tile,
    q span) — dK/dV. 'q' / 'k': [bh, rows, d] operands of either side;
    'stat_q': per-head query rows (lse, delta); 'row_q' / 'row_k':
    per-batch-row vectors (segment ids, key bias); 'mask'(per_head): the
    dense mask's slab."""
    bq, bk = sch.block_q, sch.block_k
    if walk == "k":
        nq_rows, nk_rows = bq, sch.span_k

        def at(g1, g2):
            if causal:   # a span wholly above the diagonal: copy nothing
                g2 = jnp.minimum(g2, (off + (g1 + 1) * bq - 1)
                                 // sch.span_k)
            return g1, g2
    else:
        nq_rows, nk_rows = sch.span_q, bk

        def at(g1, g2):
            if causal:
                g2 = jnp.maximum(g2, jnp.maximum(g1 * bk - off, 0)
                                 // sch.span_q)
            return g2, g1

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda bh, g1, g2: index(bh, *at(g1, g2)))

    return dict(
        q=spec((1, nq_rows, d), lambda bh, i, j: (bh, i, 0)),
        k=spec((1, nk_rows, d), lambda bh, i, j: (bh, j, 0)),
        # per-row vectors ride as [n, 1, s] with (1, 1, rows) blocks: a
        # bare (1, rows) block over [n, s] breaks the TPU block rule
        # (second-to-last block dim 8-aligned or the whole dim) for every
        # n but 1
        stat_q=spec((1, 1, nq_rows), lambda bh, i, j: (bh, 0, i)),
        row_q=spec((1, 1, nq_rows), lambda bh, i, j: (bh // h, 0, i)),
        row_k=spec((1, 1, nk_rows), lambda bh, i, j: (bh // h, 0, j)),
        mask=lambda per_head: spec(
            (1, nq_rows, nk_rows),
            (lambda bh, i, j: (bh, i, j)) if per_head else
            (lambda bh, i, j: (bh // h, i, j))),
    )


def _extra_inputs_specs(mask, kbias, qseg, kseg, specs, block_mask=None):
    """Streamed mask/kv-bias/segment/block-mask inputs + BlockSpecs.

    mask: [b, 1|h, sq, sk] additive fp32; kbias: [b, sk] additive fp32;
    segs: int32 [b, sq] / [b, sk]; block_mask: int32 [nq, nk] tile
    liveness (0 tiles are skipped — their FLOPs never run)."""
    inputs, in_specs = [], []
    if mask is not None:
        b, mh, sq, sk = mask.shape
        inputs.append(mask.reshape(b * mh, sq, sk))
        in_specs.append(specs["mask"](mh != 1))
    if kbias is not None:
        inputs.append(kbias.astype(jnp.float32)[:, None])
        in_specs.append(specs["row_k"])
    if qseg is not None:
        inputs += [qseg.astype(jnp.int32)[:, None],
                   kseg.astype(jnp.int32)[:, None]]
        in_specs += [specs["row_q"], specs["row_k"]]
    if block_mask is not None:
        # the whole [n_qblocks, n_kblocks] table rides in scalar memory
        # (tiny); every tile of a walk indexes it
        inputs.append(block_mask.astype(jnp.int32))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return inputs, in_specs


def _scratch(shape):
    if pltpu is not None:
        return pltpu.VMEM(shape, jnp.float32)
    return pl.pallas_call  # unreachable on CPU (interpret handles VMEM spec)


def _flat(t):
    """[b, s, h, d] -> [b*h, s, d]."""
    b, s, h, d = t.shape
    return jnp.swapaxes(t, 1, 2).reshape(b * h, s, d)


def _unflat(t, b):
    bh, s, d = t.shape
    return jnp.swapaxes(t.reshape(b, bh // b, s, d), 1, 2)


def _flash_forward(q, k, v, mask, kbias, qseg, kseg, block_mask,
                   causal: bool, scale: float, sch: Schedule,
                   interpret: bool, with_lse: bool = False):
    """q/k/v: [b, s, h, d] -> out [b, s, h, d] (+ lse [b*h, 1, sq] fp32)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    specs = _specs(sch, d, h, causal, sk - sq, "k")
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, specs, block_mask=block_mask)
    kernel = functools.partial(
        _q_walk_kernel, block_k=sch.block_k, causal=causal, scale=scale,
        off=sk - sq, has_mask=mask is not None, has_kbias=kbias is not None,
        has_seg=qseg is not None, has_blockmask=block_mask is not None,
        backward=False, with_lse=with_lse)
    out_shape = jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)
    out_specs = specs["q"]
    if with_lse:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32))
        out_specs = (out_specs, specs["stat_q"])
    res = pl.pallas_call(
        kernel, out_shape=out_shape,
        grid=(b * h, sq // sch.block_q, sk // sch.span_k),
        in_specs=[specs["q"], specs["k"], specs["k"]] + extra_specs,
        out_specs=out_specs,
        scratch_shapes=[_scratch((sch.block_q, 1)),
                        _scratch((sch.block_q, 1)),
                        _scratch((sch.block_q, d))],
        interpret=interpret, name="flash_fwd",
    )(_flat(q), _flat(k), _flat(v), *extra_in)
    if with_lse:
        return _unflat(res[0], b), res[1]
    return _unflat(res, b)


def _flash_backward(q, k, v, o, do, lse, mask, kbias, qseg, kseg,
                    block_mask, causal, scale, sch: Schedule, interpret):
    """Returns (dq, dk, dv) in the [b, s, h, d] layout."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf, kf, vf, of, dof = (_flat(t) for t in (q, k, v, o, do))
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, XLA fuses it
    delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32),
                    axis=-1)[:, None]
    common = dict(causal=causal, scale=scale, off=sk - sq,
                  has_mask=mask is not None, has_kbias=kbias is not None,
                  has_seg=qseg is not None,
                  has_blockmask=block_mask is not None)

    # ---- dQ: grid (bh, q tile, k span) -----------------------------------
    specs = _specs(sch, d, h, causal, sk - sq, "k")
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, specs, block_mask=block_mask)
    dq = pl.pallas_call(
        functools.partial(_q_walk_kernel, block_k=sch.block_k,
                          backward=True, with_lse=False, **common),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, sq // sch.block_q, sk // sch.span_k),
        in_specs=[specs["q"], specs["k"], specs["k"], specs["q"]]
        + extra_specs + [specs["stat_q"], specs["stat_q"]],
        out_specs=specs["q"],
        scratch_shapes=[_scratch((sch.block_q, d))],
        interpret=interpret, name="flash_bwd_dq",
    )(qf, kf, vf, dof, *extra_in, lse, delta)

    # ---- dK/dV: grid (bh, k tile, q span) --------------------------------
    specs = _specs(sch, d, h, causal, sk - sq, "q")
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, specs, block_mask=block_mask)
    dk, dv = pl.pallas_call(
        functools.partial(_k_walk_kernel, block_q=sch.block_q, **common),
        out_shape=(jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk, d), v.dtype)),
        grid=(b * h, sk // sch.block_k, sq // sch.span_q),
        in_specs=[specs["q"], specs["k"], specs["k"], specs["q"]]
        + extra_specs + [specs["stat_q"], specs["stat_q"]],
        out_specs=(specs["k"], specs["k"]),
        scratch_shapes=[_scratch((sch.block_k, d)),
                        _scratch((sch.block_k, d))],
        interpret=interpret, name="flash_bwd_dkv",
    )(qf, kf, vf, dof, *extra_in, lse, delta)
    return _unflat(dq, b), _unflat(dk, b), _unflat(dv, b)


def _reference(q, k, v, causal, scale, mask=None, kbias=None, qseg=None,
               kseg=None):
    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kT = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vT = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) * scale
    if mask is not None:
        s = s + mask.astype(jnp.float32)        # [b, 1|h, sq, sk] additive
    if kbias is not None:
        s = s + kbias.astype(jnp.float32)[:, None, None, :]  # [b, sk]
    if qseg is not None:
        seg_ok = qseg[:, None, :, None] == kseg[:, None, None, :]
        s = jnp.where(seg_ok, s, NEG_INF)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(cm[None, None], s, NEG_INF)
    # match the kernel's fully-masked-row semantics: such rows output 0
    row_live = jnp.any(s > MASKED_BELOW, axis=-1, keepdims=True)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(row_live, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vT)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _zero_cot(x):
    """Zero cotangent matching a primal that the kernel treats as constant
    (mask / segment ids); None passes through, ints get float0."""
    if x is None:
        return None
    if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
        return np.zeros(x.shape, jax.dtypes.float0)
    return jnp.zeros_like(x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _flash(q, k, v, mask, kbias, qseg, kseg, block_mask, causal, scale,
           sch, interpret):
    return _flash_forward(q, k, v, mask, kbias, qseg, kseg, block_mask,
                          causal, scale, sch, interpret)


def _flash_fwd(q, k, v, mask, kbias, qseg, kseg, block_mask, causal,
               scale, sch, interpret):
    out, lse = _flash_forward(q, k, v, mask, kbias, qseg, kseg, block_mask,
                              causal, scale, sch, interpret, with_lse=True)
    return out, (q, k, v, mask, kbias, qseg, kseg, block_mask, out, lse)


def _flash_bwd(causal, scale, sch, interpret, res, g):
    q, k, v, mask, kbias, qseg, kseg, block_mask, o, lse = res
    dq, dk, dv = _flash_backward(q, k, v, o, g, lse, mask, kbias, qseg,
                                 kseg, block_mask, causal, scale, sch,
                                 interpret)
    return (dq, dk, dv, _zero_cot(mask), _zero_cot(kbias),
            _zero_cot(qseg), _zero_cot(kseg), _zero_cot(block_mask))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _operands_ok(q, k, v=None) -> bool:
    # d % 8 == 0: Mosaic pads sub-128 lane dims, so head_dim 64 (the GPT
    # 512/8 flagship and most small/medium models) runs the flash kernel
    # instead of silently falling back to the O(seq^2) XLA path.
    return (q.shape[-1] % 8 == 0
            and q.shape[:1] + q.shape[2:] == k.shape[:1] + k.shape[2:]
            and (v is None or tuple(v.shape) == tuple(k.shape)))


def _block_shapes_ok(q, k, block_q, block_k, v=None) -> bool:
    """Whether q / k / v tile at (block_q, block_k): SDPA's precondition
    for dispatching here (at 128, the smallest tile the rule chooses)."""
    return (q.shape[1] % block_q == 0 and k.shape[1] % block_k == 0
            and _operands_ok(q, k, v))


def _canon_mask(mask, b, h, sq, sk):
    """Canonicalize a paddle-style attn_mask. Accepts bool (True = attend,
    reference convention) or additive float, with broadcastable shapes.

    Returns (dense, kbias): key-padding forms [*, *, 1, sk] lower to a
    kbias [b, sk] (O(s) HBM, streamed a span of keys a grid step) with dense
    None; anything with a per-query axis becomes dense additive fp32
    [b, 1|h, sq, sk] with kbias None."""
    mask = jnp.asarray(mask)
    if mask.dtype == jnp.bool_:
        mask = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
    if mask.ndim == 2:          # [sq|1, sk]
        mask = mask[None, None]
    elif mask.ndim == 3:        # [b, sq|1, sk]
        mask = mask[:, None]
    if mask.ndim != 4:
        raise ValueError(f"attn mask rank {mask.ndim} not supported")
    if mask.shape[1] == 1 and mask.shape[2] == 1:
        # key-padding form: identical for every query row and head — do
        # NOT broadcast to O(s^2); stream as a per-key bias instead
        kbias = jnp.broadcast_to(mask[:, 0, 0, :].astype(jnp.float32),
                                 (b, sk))
        return None, kbias
    mh = 1 if mask.shape[1] == 1 else h
    return jnp.broadcast_to(mask.astype(jnp.float32),
                            (b, mh, sq, sk)), None


def _canon_segments(segment_ids, b, sq, sk):
    """segment_ids: int [b, s] (self-attention) or a (q_seg, kv_seg) pair;
    returns int32 ([b, sq], [b, sk])."""
    if isinstance(segment_ids, (tuple, list)):
        qseg, kseg = segment_ids
    else:
        qseg = kseg = segment_ids
    qseg = jnp.asarray(qseg, jnp.int32)
    kseg = jnp.asarray(kseg, jnp.int32)
    if qseg.shape != (b, sq) or kseg.shape != (b, sk):
        raise ValueError(
            f"segment_ids shapes {qseg.shape}/{kseg.shape} don't match "
            f"q/kv sequences ({b},{sq})/({b},{sk})")
    return qseg, kseg


DEFAULT_CHECK_SHAPES = ((1, 256, 4, 64), (2, 512, 8, 64), (1, 256, 4, 128))


def validate_against_reference(shapes=DEFAULT_CHECK_SHAPES, interpret=None,
                               tol_out=None, tol_grad=None, seed=0):
    """Run the Pallas kernels (fwd + bwd) against the XLA reference path and
    return {"max_abs_err", "shapes": [[b,s,h,d,mode,err_o,err_g],...],
    "pass"} — each shapes row carries 7 elements, with the attention mode
    string at index 4 (one of "dense", "densemask", "padbias", "segments",
    matching the case list built below).

    Covers the dense-causal, additive-padding-mask, and segment-id (varlen)
    paths. Single source of truth for the kernel-vs-reference criterion —
    used by both the bench ladder's on-hardware check and the TPU pytest
    tier, so the two can't drift apart."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Interpret mode computes dots in true fp32 — hold it to tight bounds.
    # On the MXU, fp32 dots run as bf16 multi-pass (default precision), so
    # both the kernel and the XLA reference carry ~2^-8 relative rounding;
    # the comparison bound must absorb it.
    if tol_out is None:
        tol_out = 2e-3 if interpret else 2e-2
    if tol_grad is None:
        tol_grad = 5e-2 if interpret else 1e-1
    rng = np.random.default_rng(seed)
    worst = 0.0
    checked = []
    ok = True
    # (shape, mode): dense causal for every shape, plus a dense-mask, a
    # kv-bias (padding) and a packed-segment case on the first shape
    cases = [(sh, "dense") for sh in shapes]
    cases += [(shapes[0], "densemask"), (shapes[0], "padbias"),
              (shapes[0], "segments")]
    for (b, s, h, d), mode in cases:
        q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)),
                               jnp.float32) for _ in range(3))
        scale = 1.0 / math.sqrt(d)
        mask = kbias = segs = None
        causal = mode not in ("densemask", "padbias")
        valid = jnp.arange(s) < (3 * s) // 4   # last quarter = padding
        if mode == "densemask":
            mask = jnp.broadcast_to(
                jnp.where(valid, 0.0, NEG_INF)[None, None, None, :],
                (b, 1, s, s)).astype(jnp.float32)
        elif mode == "padbias":
            # the O(s) key-padding form (ERNIE-style [b,1,1,sk] lowering)
            kbias = jnp.broadcast_to(
                jnp.where(valid, 0.0, NEG_INF)[None, :], (b, s)
            ).astype(jnp.float32)
        elif mode == "segments":
            segs = jnp.broadcast_to((jnp.arange(s) * 4) // s, (b, s)
                                    ).astype(jnp.int32)

        def f_f(q, k, v, mask=mask, kbias=kbias, segs=segs, causal=causal,
                scale=scale):
            qs, ks = (segs, segs) if segs is not None else (None, None)
            sch = schedule(q.shape, k.shape, q.dtype, causal,
                           mask=mask is not None)
            return _flash(q, k, v, mask, kbias, qs, ks, None, causal,
                          scale, sch, interpret)

        def f_r(q, k, v, mask=mask, kbias=kbias, segs=segs, causal=causal,
                scale=scale):
            return _reference(q, k, v, causal, scale, mask=mask,
                              kbias=kbias, qseg=segs, kseg=segs)

        def out_and_grads(f, q, k, v):
            out, vjp = jax.vjp(f, q, k, v)
            return out, vjp(2.0 * out)      # d sum(o^2) / d (q, k, v)

        o_f, g_f = jax.jit(functools.partial(out_and_grads, f_f))(q, k, v)
        o_r, g_r = jax.jit(functools.partial(out_and_grads, f_r))(q, k, v)
        err_o = float(jnp.max(jnp.abs(o_f - o_r)))
        err_g = max(float(jnp.max(jnp.abs(x - y)))
                    for x, y in zip(g_f, g_r))
        worst = max(worst, err_o, err_g)
        ok = ok and err_o < tol_out and err_g < tol_grad
        checked.append([b, s, h, d, mode, err_o, err_g])
    return {"max_abs_err": worst, "shapes": checked, "pass": ok,
            "interpret": interpret}


_FALLBACK_WARNED: set = set()


def _log_fallback(q, k):
    """The silent-fallback condition is a dead-kernel bug magnet — warn once
    per shape so it is visible which configs miss the flash path."""
    key = (tuple(q.shape), tuple(k.shape))
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        import warnings

        warnings.warn(
            f"flash_attention: shapes q={tuple(q.shape)} k={tuple(k.shape)} "
            "don't tile; using the O(seq^2) XLA reference path",
            stacklevel=3)


def flash_attention(q, k, v, causal: bool = True, scale=None,
                    mask=None, segment_ids=None, block_mask=None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """Pallas flash attention with automatic fallback to the XLA reference
    when shapes don't tile (same dispatch pattern as the reference's
    sdp_kernel selection, nn/functional/flash_attention.py). The tiles and
    the grid are `schedule()`'s, from the shapes, the dtype and the masks;
    `block_q` and `block_k` force the tiles for tests of the walk at toy
    sizes only. Matmuls take q, k, v and dO in the dtype they arrive
    in, with float32 products; the softmax is float32.

    mask: additive float or bool (True=attend) attn mask, broadcastable to
    [b, 1|h, sq, sk] — streamed tile-wise into the kernel; key-padding
    forms ([*, *, 1, sk]) are lowered to an O(s) per-key bias.
    segment_ids: int [b, s] or (q_seg [b, sq], kv_seg [b, sk]) — varlen /
    packed-sequence masking with O(s) memory (attend iff ids equal).
    block_mask: int/bool [n_qblocks, n_kblocks] tile liveness; its shape
    sets the tiles (sq / n_qblocks by sk / n_kblocks) — dead tiles' FLOPs
    are skipped entirely (block-sparse attention). The
    block mask must be IMPLIED by the elementwise masks (a tile marked
    dead must already be fully masked by mask/segments/causal), otherwise
    results diverge from the dense computation; callers like
    sparse_attention derive both from the same pattern."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kbias = None
    if mask is not None:
        mask, kbias = _canon_mask(mask, b, h, sq, sk)
    qseg = kseg = None
    if segment_ids is not None:
        qseg, kseg = _canon_segments(segment_ids, b, sq, sk)
    if block_mask is not None:
        block_mask = jnp.asarray(block_mask, jnp.int32)
        if (block_mask.ndim != 2 or sq % block_mask.shape[0]
                or sk % block_mask.shape[1]):
            raise ValueError(
                f"block_mask {block_mask.shape} does not divide the "
                f"scores ({sq}, {sk}) into whole tiles")
    # bottom-right alignment gives the early queries of a causal sq > sk
    # call ZERO visible keys — handled by the masked-row guard, but parity
    # with the XLA path is simplest via the reference for this rare shape
    sch = None
    if not (causal and sq > sk) and _operands_ok(q, k, v):
        sch = schedule(q.shape, k.shape, q.dtype, causal,
                       mask=mask is not None,
                       block_mask_shape=None if block_mask is None
                       else block_mask.shape,
                       block_q=block_q, block_k=block_k)
    if sch is None:
        _log_fallback(q, k)
        return _reference(q, k, v, causal, scale, mask, kbias, qseg, kseg)
    statics = (causal, scale, sch, interpret)
    placed = _program_mesh_axes(b, h)
    if placed is None:
        return _flash(q, k, v, mask, kbias, qseg, kseg, block_mask,
                      *statics)
    # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    # shard_map"): in a program compiled for a mesh each device runs the
    # kernel on its own batch rows ('dp') and heads ('tp'), every mesh
    # axis manual
    mesh, bax, hax = placed
    qkv, row = P(bax, None, hax, None), P(bax, None)
    extras = {n: x for n, x in (("mask", mask), ("kbias", kbias),
                                ("qseg", qseg), ("kseg", kseg),
                                ("block_mask", block_mask)) if x is not None}
    specs = {"kbias": row, "qseg": row, "kseg": row, "block_mask": P()}
    if mask is not None:
        specs["mask"] = P(bax, hax if mask.shape[1] == h else None,
                          None, None)

    def per_shard(q, k, v, ex):
        return _flash(q, k, v, ex.get("mask"), ex.get("kbias"),
                      ex.get("qseg"), ex.get("kseg"), ex.get("block_mask"),
                      *statics)

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(qkv, qkv, qkv, {n: specs[n] for n in extras}),
        out_specs=qkv, check_vma=False,
    )(q, k, v, extras)


def _program_mesh_axes(b: int, h: int):
    """(mesh, batch axis, head axis) when the program being traced computes
    on a multi-device mesh (parallel.mesh.program_mesh) and the call is
    not already inside a manual region; None otherwise. An axis is named
    only where it divides the dimension; unnamed axes compute replicated."""
    from paddle_tpu.parallel.mesh import program_mesh

    mesh = program_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None

    def pick(axis, n):
        return (axis if axis in mesh.axis_names
                and n % mesh.shape[axis] == 0 else None)

    return mesh, pick("dp", b), pick("tp", h)
