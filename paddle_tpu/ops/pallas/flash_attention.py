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
  * the forward runs on grid (batch * head blocks, sq / block_q,
    sk / span): K and V of the span are VMEM-resident and the walk over
    their tiles is a loop INSIDE the kernel, bounded by the causal diagonal,
    so no step and no fetch is spent above it. Where a sequence outgrows
    one span the third axis has several steps, the accumulators ride VMEM
    scratch across them, and the index map of a span wholly above the
    diagonal clamps to the last live one (a dead step copies nothing);
  * the backward is a small pre-pass and ONE kernel (`flash_bwd_delta`,
    `flash_bwd`) wherever `schedule()` says dQ's accumulator fits
    (`Schedule.fused_backward`, from the shapes alone): grid (batch * head
    blocks, k steps, q spans), on TRANSPOSED score tiles (keys on rows,
    queries on lanes, so the per-query statistics are read as the
    lane-dense rows they are stored as). For each k tile and each live q
    tile of the span, S^T = K Q^T and P^T = exp(S^T - lse) are taken ONCE,
    then dV += P^T dO, dS^T = P^T (V dO^T - delta), dK += dS^T Q and
    dQ[q tile] += dS K: five matmuls and one exponent a tile, where two
    kernels that each recompute P take seven and two. dQ's float32
    accumulator holds ALL of sq in VMEM across the k steps, which run in
    order (k tile 0 first: the order the dQ kernel sums in), and each q
    span's rows go out, scaled and cast, with the last k step, written
    once. Where a head block has few tiles (ONE_STEP_TILES) and all of
    its sq and sk fit the chip's default VMEM, they are ONE grid step of
    straight-line code, nothing rides scratch across steps, and a tile the
    diagonal crosses is folded in strips of 128 queries, each against the
    keys it can see; otherwise a k tile a step beside the longest q span
    that fits (all of sq at ZAYA's 8192 keys, so that q and dO are fetched
    once a head block and not again for every k tile), with
    FUSED_VMEM_LIMIT asked of the compiler where the count passes
    VMEM_BUDGET;
  * where that accumulator would take more than half of the fused
    kernel's VMEM (32768 queries of 128 lanes are 16 MiB) the backward is
    two kernels, each recomputing P from lse:
    `flash_bwd_dq` on the forward's grid (it takes delta from its O and dO
    blocks and hands it on) and `flash_bwd_dkv` on (batch * head blocks,
    sk / block_k, sq / span), walking Q / dO tiles from the diagonal on;
  * the MXU gets the operands' own dtype (bfloat16 under autocast O1,
    float32 where the caller gave float32) with float32 products; P and dS
    are cast to it for the second matmul of each pair. Scores, running max
    and sum, lse, delta and the accumulators are float32.

The attention matrix never exists in HBM; per-row statistics (lse, delta)
are [batch*head, 1, sq] float32, whole 128-lane rows: lse an output of the
forward, delta = rowsum(dO * O) of the pre-pass, which reads O and dO once.
`profiler.traced_counts()` says which backward a program's trace took
(`flash_bwd_fused` / `flash_bwd_two_kernels`, a Python count a compile).

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

Forward and backward are Pallas kernels; the backward recomputes P from
the saved per-row log-sum-exp (once a tile where it is fused,
FlashAttention-2's two walks where it is not).

Layout: [batch, seq, heads, head_dim] (paddle flash-attn convention), and
the kernels read q, k, v, o, dO and write o, dq, dk, dv in that memory, as
[b, s, h*d] (a free reshape): an operand block is (1, rows, W) of whole
128-lane columns, W = max(d, 128), and the BlockSpec's index map picks
(batch row, row tile, head block), so nothing is transposed around the
calls. `Schedule.heads_per_block` says which layout a call runs, from its
shapes alone:
  * d a multiple of 128: one head a block. k and v may then have h / rep
    heads; the kernels read head block `head // rep` where it lies, and
    dK / dV come out a QUERY head and are summed over each group outside;
  * d a divisor of 128 and h a multiple of 128 // d: that many heads share
    a block, and a grid step takes them in a static loop. The MXU
    contracts 128 lanes whether half of them are padding or another head,
    so a head's scores are its own lanes of the grid-side operand (the
    others zeroed, once a grid step) against the whole walked tile, and
    each product that lands in the block's lanes (P V, dS K, P^T dO,
    dS^T Q) is taken whole and kept in that head's lanes of ONE lane-dense
    (rows, 128) accumulator: no MXU pass is added. Running max, sum, lse
    and delta stay a head;
  * otherwise (d = 96, three heads of 64): 0, the kernels run on flat
    [b*h, s, d] copies with the lanes padded, `_flat` before and `_unflat`
    after, one warning a shape (`_log_flat`).
A caller that wants no copy either side hands over whole columns: slice
q, k, v out of a fused projection as [b, s, h*d] BEFORE naming heads (the
chip tiles the last two dimensions, so a [.., h, 64] array is no bitcast
of [.., h*64] and the compiler transposes its way to one).
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
# the fused backward keeps dQ's float32 accumulator for all of sq beside
# its blocks: where that outgrows VMEM_BUDGET it asks the compiler for
# FUSED_VMEM_LIMIT of the chip's 128 MiB and counts against FUSED_VMEM_BUDGET
FUSED_VMEM_LIMIT = 28 * 2 ** 20
FUSED_VMEM_BUDGET = 24 * 2 ** 20
# rows of O and dO a grid step of the delta pre-pass reads (the sequence
# where it is no longer): the pass is as fast as it reads
DELTA_ROWS = 2048
# score tiles of a head block that the fused backward takes as ONE grid step
# of straight-line code (each tile's code is written out: the compiler then
# runs one tile's matmuls beside another's softmax)
ONE_STEP_TILES = 8
LANES = 128


def _tile(n: int, cap: int = TILE_CAP):
    """The largest multiple of 128 up to `cap` that divides n; a sequence
    of up to 128 is its own tile; None where nothing tiles."""
    if n <= LANES:
        return n
    fits = [t for t in range(LANES, min(cap, n) + 1, LANES) if n % t == 0]
    return max(fits) if fits else None


class Schedule(NamedTuple):
    """What one call and its gradient run. `steps`, `tiles` and
    `dead_steps` are per kernel, in the order the kernels run: (fwd, delta,
    bwd) where the backward is fused, (fwd, bwd_dq, bwd_dkv) where it is
    two kernels: grid steps, score tiles folded (causal geometry; a block
    table may skip more; the delta pre-pass folds none), and grid steps
    whose span lies wholly above the diagonal (they copy and fold
    nothing)."""
    block_q: int
    block_k: int
    span_q: int
    span_k: int
    steps: tuple
    tiles: tuple
    dead_steps: tuple
    # heads a block holds where the kernels read q, k, v, o and dO in the
    # caller's own memory, [b, s, h*d]; 0: they run on flat [b*h, s, d]
    # copies (a head does not fill whole 128-lane columns)
    heads_per_block: int = 0
    # rows of q / dO and of k / v one grid step of the fused backward holds
    # beside dQ's accumulator for all of sq; 0: they do not fit and the
    # backward is two kernels
    bwd_span_q: int = 0
    bwd_span_k: int = 0
    # the scoped VMEM the fused backward asks the compiler for; None: the
    # chip's default holds it
    bwd_vmem_limit: int | None = None

    @property
    def heads(self) -> int:
        """Heads one grid step takes."""
        return max(self.heads_per_block, 1)

    @property
    def fused_backward(self) -> bool:
        """Whether dQ, dK and dV come from one pass over the score tiles."""
        return self.bwd_span_q > 0


def _heads_per_block(h: int, hk: int, d: int) -> int:
    """Heads to a block of whole 128-lane columns of [b, s, h*d]: one
    where a head is a multiple of 128 wide (k and v may then have `hk`
    heads, a divisor of h), 128 // d where that many divide the heads; 0
    where no such block exists."""
    if d % LANES == 0:
        return 1 if h % hk == 0 else 0
    hp = LANES // d
    return hp if LANES % d == 0 and hk == h and h % hp == 0 else 0


def _vmem_bytes(tile_rows: int, walked_rows: int, block_q: int, block_k: int,
                d: int, itemsize: int, mask: int, heads: int = 1) -> int:
    """Scoped VMEM of one grid step, counted from above: a tile of
    `tile_rows` on the grid's side (at most four blocks: k, v, dk, dv, or
    q, dO, O, dq) and
    `walked_rows` of the two operands it walks, each block twice for the
    pipeline; the accumulators and each head's row statistics; a dense
    mask's slab (`mask` heads of it); five score tiles of float32
    temporaries; where `heads` share a block, each one's lanes of the two
    grid-side operands. A block's minor dimension is whole 128-lane tiles:
    a shared block's own, a flat head's padded."""
    dl = -(-d // LANES) * LANES
    blocks = 2 * (4 * tile_rows + 2 * walked_rows) * dl * itemsize
    scratch = 2 * tile_rows * (dl + heads * LANES) * 4
    slab = 2 * mask * tile_rows * walked_rows * 4
    alone = 2 * heads * tile_rows * dl * itemsize if heads > 1 else 0
    return blocks + scratch + slab + alone + 5 * block_q * block_k * 4


def _fused_vmem_bytes(q_rows: int, k_rows: int, sq: int, block_q: int,
                      block_k: int, d: int, itemsize: int, mask: int,
                      heads: int = 1) -> int:
    """Scoped VMEM of one grid step of the fused backward: `k_rows` of k,
    v, dk and dv and `q_rows` of q, dO and dq, each block twice for the
    pipeline; lse's and delta's rows (a sublane tile a head); the float32
    accumulators, dK's and dV's and dQ's for ALL of sq; a dense mask's
    slab; each head's lanes of a k and a v tile where heads share a block;
    four score tiles of float32 temporaries (the compiler's own count for
    a described v5e comes out 1 to 5 MiB under this one at the three
    training cells' shapes: 7.25 of 9.75 at gpt2's, 20.25 of 22.5 at
    ZAYA's)."""
    dl = -(-d // LANES) * LANES
    blocks = 2 * (4 * k_rows + 3 * q_rows) * dl * itemsize
    stats = 2 * 2 * heads * 8 * q_rows * 4
    scratch = (2 * k_rows + sq) * dl * 4
    slab = 2 * mask * q_rows * k_rows * 4
    alone = 2 * heads * block_k * dl * itemsize if heads > 1 else 0
    return (blocks + stats + scratch + slab + alone
            + 4 * block_q * block_k * 4)


def schedule(q_shape, k_shape, dtype, causal: bool, *, mask: int = 0,
             block_mask_shape=None, block_q=None, block_k=None, span=None,
             fused: bool = True):
    """The tiles, grids and layout of one call, and which backward it
    takes: a pure function of what the call can see: q [b, sq, h, d], k [b,
    sk, hk, d], the operand dtype, the heads of a dense additive mask that
    streams (0: none, 1: one for all), a block table's shape. `block_q`,
    `block_k`, `span` and `fused=False` force a choice (tests at toy sizes
    only). None where the shapes do not tile."""
    b, sq, h, d = q_shape
    sk = k_shape[1]
    hp = _heads_per_block(h, k_shape[2], d)
    slabs = min(int(mask), max(hp, 1))      # a grid step's share of the mask
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
                                     slabs, max(hp, 1)) <= VMEM_BUDGET),
                    None)

    tq, tk = walked(nq, block_k, block_q), walked(nk, block_q, block_k)
    if tq is None or tk is None:
        # one tile overflows by itself (a dense mask's slab): halve it
        if block_mask_shape is not None or max(block_q, block_k) <= LANES:
            return None
        return schedule(q_shape, k_shape, dtype, causal, mask=mask,
                        block_q=_tile(sq, max(LANES, block_q // 2)),
                        block_k=_tile(sk, max(LANES, block_k // 2)),
                        fused=fused)
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
    blocks = b * h // max(hp, 1)            # the grid's first axis
    walk_q, walk_k = blocks * nq * (nk // tk), blocks * nk * (nq // tq)
    two = Schedule(block_q, block_k, tq * block_q, tk * block_k,
                   (walk_q, walk_q, walk_k), (b * h * sum(live),) * 3,
                   (blocks * dead_q, blocks * dead_q, blocks * dead_k), hp)

    def fused_bytes(q_rows, k_rows):
        return _fused_vmem_bytes(q_rows, k_rows, sq, block_q, block_k, d,
                                 jnp.dtype(dtype).itemsize, slabs,
                                 max(hp, 1))

    # the fused backward: all of a head block's sq and sk in one grid step
    # where the chip's default holds that, else a k tile a step beside the
    # longest q span that fits
    if span is not None:
        fits = [(max(1, min(span, sq) // block_q) * block_q, block_k)]
    else:
        fits = [(t * block_q, block_k)
                for t in range(nq, 0, -1) if nq % t == 0]
        if nq * nk <= ONE_STEP_TILES and fused_bytes(sq, sk) <= VMEM_BUDGET:
            fits = [(sq, sk)]
    fits = [f for f in fits if fused_bytes(*f) <= FUSED_VMEM_BUDGET]
    # an accumulator that leaves its steps less than half of the budget
    # leaves them short q spans, fetched again for every k tile
    acc = sq * -(-d // LANES) * LANES * 4
    if not fits or not fused or 2 * acc > FUSED_VMEM_BUDGET:
        return two
    q_rows, k_rows = fits[0]
    fq, fk = nq // (q_rows // block_q), sk // k_rows    # steps a head block
    dead_f = sum((m + 1) * q_rows <= max(j * k_rows - off, 0)
                 for j in range(fk) for m in range(fq)) if causal else 0
    return two._replace(
        steps=(walk_q, blocks * (sq // _tile(sq, DELTA_ROWS)),
               blocks * fk * fq),
        tiles=(two.tiles[0], 0, two.tiles[0]),
        dead_steps=(blocks * dead_q, 0, blocks * dead_f),
        bwd_span_q=q_rows, bwd_span_k=k_rows,
        bwd_vmem_limit=None if fused_bytes(q_rows, k_rows) <= VMEM_BUDGET
        else FUSED_VMEM_LIMIT)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract, ((), ()))),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _column(ref, j: int = 0):
    """Head j's per-row vector, stored lane-dense in (heads, 1, n), as a
    column (n, 1)."""
    return ref[j, 0][:, None]


def _only_head(x, j: int, heads: int, d: int):
    """A (rows, heads * d) block with every lane but head j's zeroed: a
    contraction over the block's lanes is then over that head's alone.
    (Lane slices of both operands, and the walked tile transposed once for
    its heads, compile too and ran no faster on the chip: PERF.md §6, PR
    38.)"""
    if heads == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads * d), 1)
    mine = (lane >= j * d) & (lane < (j + 1) * d)
    return jnp.where(mine, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _by_head(parts, d: int):
    """One (rows, heads * d) array that is parts[j] in head j's lanes; a
    part is (rows, heads * d), or (rows, 1) to spread over its lanes."""
    out = parts[0]
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, len(parts) * d), 1)
        for j, part in enumerate(parts[1:], 1):
            out = jnp.where(lane >= j * d, part, out)
    return out


def _tile_scores(s, keys_on_rows: bool, scale, mask=None,
                 kbias=None, qseg=None, kseg=None, q_pos=None, k_pos=None):
    """Shared per-tile scaled+masked scores of the raw products `s` (ONE
    definition of the causal / additive / kv-bias / segment masks for fwd
    and both bwd kernels): (block_q, block_k), or its transpose where
    `keys_on_rows`. The vectors
    arrive oriented to it (per-query ones columns and per-key ones rows, or
    the other way round); `mask` is the (block_q, block_k) tile as stored;
    `q_pos` / `k_pos` are the positions the causal rule compares (None on
    a tile wholly below the diagonal)."""
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
    dynamic slice, and so is a tile whose index is known at trace time."""
    if tiles == 1:
        return pl.ds(0, block)
    if isinstance(t, int):
        return pl.ds(t * block, block)
    return pl.ds(pl.multiple_of(t * block, block), block)


def _walk(phases, tile, live=None):
    """Fold this grid step's tiles: `phases` is ((lo, hi, on_diagonal),
    ...), `tile(t, on_diagonal)` folds tile t of the span (only a tile the
    diagonal crosses pays for the causal compare), `live(t)` says whether
    a block table keeps it. Bounds known at trace time (a grid of one step
    along the walk) give straight-line code, a tile after the other."""
    for lo, hi, on_diagonal in phases:
        def body(t, carry, on_diagonal=on_diagonal):
            if live is None:
                tile(t, on_diagonal)
            else:
                pl.when(live(t))(lambda: tile(t, on_diagonal))
            return carry

        if isinstance(lo, int) and isinstance(hi, int):
            for t in range(lo, hi):
                body(t, None)
        else:
            jax.lax.fori_loop(lo, hi, body, None)


def _when(cond, fn):
    """`pl.when` for a condition that may be known at trace time."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _clip(x, lo, hi):
    """jnp.clip that stays a Python integer where all three are."""
    if all(isinstance(v, int) for v in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _not_below_0(x):
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _q_walk_kernel(*refs, block_k: int, heads: int, causal: bool,
                   scale: float, off: int, has_mask: bool, has_kbias: bool,
                   has_seg: bool, has_blockmask: bool, backward: bool,
                   with_lse: bool):
    """Forward (`backward` False) and dQ: one grid step folds the live K /
    V tiles of its span into this Q block's accumulators, for each of the
    block's `heads` in turn.
    dQ_i = scale * sum_j dS_ij K_j, dS = P * (dO V^T - delta), with
    delta_i = rowsum(dO_i * O_i) taken here from the O and dO blocks and
    handed on, lane-dense like lse, to the dK/dV kernel.
    Where heads share a block, a head's scores are its own lanes of Q (or
    dO) against the whole K (or V) tile, and each product that lands in
    the block's lanes, P V or dS K, is taken whole and kept in that head's
    lanes of the one lane-dense accumulator."""
    n_lead = 5 if backward else 3
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, n_lead, has_mask, has_kbias, has_seg, has_blockmask)
    if backward:
        do_ref, o_ref = refs[3:5]
        lse_ref, dq_ref, delta_ref, acc_ref = rest
    elif with_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (o_ref, m_ref, l_ref, acc_ref), lse_ref = rest, None
    block_q, lanes = q_ref.shape[1:]
    d = lanes // heads
    each = range(heads)
    tiles = k_ref.shape[1] // block_k          # of this step's span
    qi, kj = pl.program_id(1), pl.program_id(2)
    guard = has_mask or has_kbias or has_seg or has_blockmask

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros((block_q, lanes), jnp.float32)
        if not backward:
            m_ref[:] = jnp.full((heads, block_q, 1), NEG_INF, jnp.float32)
            l_ref[:] = jnp.zeros((heads, block_q, 1), jnp.float32)

    q = [_only_head(q_ref[0], j, heads, d) for j in each]
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
        do = [_only_head(do_ref[0], j, heads, d) for j in each]
        lse = [_column(lse_ref, j) for j in each]
        o_do = o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32)
        delta = [jnp.sum(_only_head(o_do, j, heads, d), axis=-1,
                         keepdims=True) for j in each]

        @pl.when(kj == 0)
        def _hand_on():
            for j in each:
                delta_ref[j, 0] = delta[j][:, 0]

    def tile(t, on_diagonal):
        at = _tile_at(t, block_k, tiles)
        k_tile, v_tile = k_ref[0, at, :], v_ref[0, at, :]
        at_diagonal = {}
        if on_diagonal:     # q_start + i >= k_start + j, the shift on the row
            at_diagonal = dict(q_pos=q_pos, k_pos=k_pos + (
                (kj * tiles + t) * block_k - q_start))
        kbias = kbias_ref[0, :, at] if has_kbias else None
        kseg = kseg_ref[0, :, at] if has_seg else None
        into, corr = [], []     # per head: its product, its rescale
        for j in each:
            s = _tile_scores(
                _dot(q[j], k_tile, _NT), False, scale,
                mask=mask_ref[j % mask_ref.shape[0], :, at] if has_mask
                else None, kbias=kbias, qseg=qseg, kseg=kseg, **at_diagonal)
            if backward:
                # hard-masked entries get exactly 0 even on fully-masked
                # rows where the saved lse is itself ~NEG_INF (exp(s - lse)
                # would be exp(0) = 1 there)
                p = jnp.exp(s - lse[j])
                if guard:
                    p = jnp.where(s <= MASKED_BELOW, 0.0, p)
                ds = p * (_dot(do[j], v_tile, _NT) - delta[j])
                into.append(_dot(ds.astype(k_tile.dtype), k_tile, _NN))
                continue
            m = m_ref[j]
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - new_m)
            if guard:
                # on a row where every key so far is hard-masked, new_m is
                # still NEG_INF and exp(s - new_m) would be exp(0) = 1 —
                # force 0 so the row's l stays 0 and its output is exactly
                # zero (causal alone needs none: the walk starts at tile 0,
                # where every row sees key 0, and exp(NEG_INF - finite) is
                # exactly 0)
                p = jnp.where(s <= MASKED_BELOW, 0.0, p)
            corr.append(jnp.exp(m - new_m))
            m_ref[j] = new_m
            l_ref[j] = l_ref[j] * corr[j] + jnp.sum(p, axis=-1,
                                                    keepdims=True)
            into.append(_dot(p.astype(v_tile.dtype), v_tile, _NN))
        if backward:
            acc_ref[:] += _by_head(into, d)
        else:
            acc_ref[:] = acc_ref[:] * _by_head(corr, d) + _by_head(into, d)

    _walk(phases, tile, None if bm_ref is None else
          lambda t: bm_ref[qi, kj * tiles + t] > 0)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        if backward:
            dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)
            return
        l = [jnp.maximum(l_ref[j], 1e-30) for j in each]
        o_ref[0] = (acc_ref[:] / _by_head(l, d)).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp per row, saved lane-dense for the backward kernels
            for j in each:
                lse_ref[j, 0] = (m_ref[j] + jnp.log(l[j]))[:, 0]


def _k_walk_kernel(*refs, block_q: int, heads: int, causal: bool,
                   scale: float, off: int, has_mask: bool, has_kbias: bool,
                   has_seg: bool, has_blockmask: bool):
    """dV_j = P^T dO; dK_j = scale * dS^T Q: one grid step folds the live
    Q / dO tiles of its span into this K block's accumulators, on
    transposed score tiles (keys on rows, queries on lanes), for each of
    the block's `heads` in turn: its own lanes of K and V against the whole
    Q and dO tiles, P^T dO and dS^T Q kept in its lanes."""
    q_ref, k_ref, v_ref, do_ref = refs[:4]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, 4, has_mask, has_kbias, has_seg, has_blockmask)
    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    block_k, lanes = k_ref.shape[1:]
    d = lanes // heads
    each = range(heads)
    tiles = q_ref.shape[1] // block_q          # of this step's span
    kj, qm = pl.program_id(1), pl.program_id(2)
    guard = has_mask or has_kbias or has_seg or has_blockmask

    @pl.when(qm == 0)
    def _init():
        dk_acc[:] = jnp.zeros((block_k, lanes), jnp.float32)
        dv_acc[:] = jnp.zeros((block_k, lanes), jnp.float32)

    k_tile = [_only_head(k_ref[0], j, heads, d) for j in each]
    v_tile = [_only_head(v_ref[0], j, heads, d) for j in each]
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
        qseg = qseg_ref[0, :, at] if has_seg else None
        dv, dk = [], []
        for j in each:
            s = _tile_scores(
                _dot(k_tile[j], q, _NT), True, scale,
                mask=mask_ref[j % mask_ref.shape[0], at, :] if has_mask
                else None, kbias=kbias, qseg=qseg, kseg=kseg, **at_diagonal)
            p = jnp.exp(s - lse_ref[j, :, at])
            if guard:
                p = jnp.where(s <= MASKED_BELOW, 0.0, p)
            ds = p * (_dot(v_tile[j], do, _NT) - delta_ref[j, :, at])
            dv.append(_dot(p.astype(do.dtype), do, _NN))
            dk.append(_dot(ds.astype(q.dtype), q, _NN))
        dv_acc[:] += _by_head(dv, d)
        dk_acc[:] += _by_head(dk, d)

    _walk(phases, tile, None if bm_ref is None else
          lambda t: bm_ref[qm * tiles + t, kj] > 0)

    @pl.when(qm == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _delta_kernel(o_ref, do_ref, delta_ref, *, heads: int):
    """delta_i = rowsum(dO_i * O_i) a head, lane-dense as lse is: the
    fused backward's pre-pass over O and dO, read once. Whole 128-lane
    tiles are transposed, so that a head's lanes are rows and their sum
    comes out along the lanes it is stored in (a quarter of the bundles of
    a lane reduction a row and its turn to lanes, which took 0.61 ms a
    layer at [28, 1024, 12, 64] for 0.11 ms of reading)."""
    rows, lanes = o_ref.shape[1:]
    d = lanes // heads
    o_do = o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32)
    if rows % LANES == 0 and lanes % LANES == 0:
        by_lane = o_do.T
        for j in range(heads):
            delta_ref[j, 0] = jnp.sum(by_lane[j * d:(j + 1) * d], axis=0)
        return
    for j in range(heads):
        delta_ref[j, 0] = jnp.sum(_only_head(o_do, j, heads, d), axis=-1)


def _fused_kernel(*refs, block_q: int, block_k: int, steps: tuple,
                  heads: int, causal: bool, scale: float, off: int,
                  has_mask: bool, has_kbias: bool, has_seg: bool,
                  has_blockmask: bool):
    """dQ, dK and dV from ONE pass over the score tiles, transposed (keys
    on rows, queries on lanes). For each k tile of this grid step and each
    live q tile of its span: S^T = K Q^T, P^T = exp(S^T - lse), dV += P^T
    dO, dS^T = P^T (V dO^T - delta), dK += dS^T Q and dQ[q tile] += dS K:
    five matmuls and one exponent a tile. dQ's float32 accumulator holds
    ALL of sq and rides VMEM across the k steps (that grid axis runs in
    order, k tile 0 first); the q span's rows of it go out, scaled and
    cast, with the last k step. `steps` is the grid's (k steps, q steps):
    an axis of one step is index 0 at trace time, and the walk's bounds
    with it. Where heads share a block a head's own lanes of K and V meet
    the whole Q and dO tiles; dS K on those lanes of K lands in the head's
    lanes of dQ by itself."""
    q_ref, k_ref, v_ref, do_ref = refs[:4]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, 4, has_mask, has_kbias, has_seg, has_blockmask)
    (lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
     dq_acc, dk_acc, dv_acc) = rest
    q_rows, lanes = q_ref.shape[1:]
    d = lanes // heads
    each = range(heads)
    k_tiles = k_ref.shape[1] // block_k        # of this step's spans
    q_tiles = q_rows // block_q
    k_steps, q_steps = steps
    kj = pl.program_id(1) if k_steps > 1 else 0
    qm = pl.program_id(2) if q_steps > 1 else 0
    span = _tile_at(qm, q_rows, q_steps)       # this step's rows of dQ
    guard = has_mask or has_kbias or has_seg or has_blockmask

    def _init_dq():
        dq_acc[span, :] = jnp.zeros((q_rows, lanes), jnp.float32)

    def _init_dkv():
        dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)

    _when(kj == 0, _init_dq)
    _when(qm == 0, _init_dkv)
    first = qm * q_tiles

    for kt in range(k_tiles):
        rows = pl.ds(kt * block_k, block_k)
        k_tile = [_only_head(k_ref[0, rows, :], j, heads, d) for j in each]
        v_tile = [_only_head(v_ref[0, rows, :], j, heads, d) for j in each]
        tile_k = kj * k_tiles + kt             # of all of sk
        k_start = tile_k * block_k - off
        phases = ((0, q_tiles, False),)
        if causal:
            # a q block contributes iff its LAST query can see this k tile;
            # the diagonal crosses it unless its FIRST query sees the
            # tile's last key
            lo = _clip(_not_below_0(k_start) // block_q - first, 0, q_tiles)
            mid = _clip((_not_below_0(k_start + block_k - 1) + block_q - 1)
                        // block_q - first, lo, q_tiles)
            phases = ((lo, mid, True), (mid, q_tiles, False))
        kbias = kbias_ref[0, 0, rows][:, None] if has_kbias else None
        kseg = kseg_ref[0, 0, rows][:, None] if has_seg else None

        def fold(t, on_diagonal, q_lo, q_n, k_n, kt=kt, k_tile=k_tile,
                 v_tile=v_tile, k_start=k_start, kbias=kbias, kseg=kseg):
            """Rows q_lo.. (q_n of them) of q tile t against the first k_n
            rows of this k tile."""
            if (q_lo, q_n) == (0, block_q):
                at = _tile_at(t, block_q, q_tiles)
                of_sq = _tile_at(first + t, block_q, q_steps * q_tiles)
            else:       # part of a tile: a static index
                at = pl.ds(t * block_q + q_lo, q_n)
                of_sq = pl.ds((first + t) * block_q + q_lo, q_n)
            rows = pl.ds(kt * block_k, k_n)
            q, do = q_ref[0, at, :], do_ref[0, at, :]
            at_diagonal = {}
            if on_diagonal:
                q_pos, k_pos = _positions(q_n, k_n, True)
                at_diagonal = dict(k_pos=k_pos, q_pos=q_pos + (
                    (first + t) * block_q + q_lo - k_start))
            qseg = qseg_ref[0, :, at] if has_seg else None
            dv, dk, dq = [], [], None
            for j in each:
                k_j, v_j = k_tile[j][:k_n], v_tile[j][:k_n]
                s = _tile_scores(
                    _dot(k_j, q, _NT), True, scale,
                    mask=mask_ref[j % mask_ref.shape[0], at, rows]
                    if has_mask else None,
                    kbias=None if kbias is None else kbias[:k_n], qseg=qseg,
                    kseg=None if kseg is None else kseg[:k_n], **at_diagonal)
                # hard-masked entries get exactly 0 even on fully-masked
                # rows where the saved lse is itself ~NEG_INF
                p = jnp.exp(s - lse_ref[j, :, at])
                if guard:
                    p = jnp.where(s <= MASKED_BELOW, 0.0, p)
                ds = (p * (_dot(v_j, do, _NT)
                           - delta_ref[j, :, at])).astype(q.dtype)
                dv.append(_dot(p.astype(do.dtype), do, _NN))
                dk.append(_dot(ds, q, _NN))
                mine = _dot(ds, k_j, _TN)
                dq = mine if dq is None else dq + mine
            dv_acc[rows, :] += _by_head(dv, d)
            dk_acc[rows, :] += _by_head(dk, d)
            dq_acc[of_sq, :] += dq

        def tile(t, on_diagonal, fold=fold, k_start=k_start):
            strips = block_q // LANES
            if not (on_diagonal and isinstance(t, int)
                    and isinstance(k_start, int) and strips > 1
                    and block_q % LANES == 0):
                return fold(t, on_diagonal, 0, block_q, block_k)
            # the diagonal's place in the tile is known at trace time: a
            # strip of 128 queries meets only the keys its last one sees
            shift = (first + t) * block_q - k_start
            for c in range(strips):
                seen = -(-((c + 1) * LANES + shift) // LANES) * LANES
                if seen > 0:
                    fold(t, True, c * LANES, LANES, min(seen, block_k))

        _walk(phases, tile, None if bm_ref is None else
              lambda t, tile_k=tile_k: bm_ref[first + t, tile_k] > 0)

    def _finish_dkv():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    def _finish_dq():
        dq_ref[0] = (dq_acc[span, :] * scale).astype(dq_ref.dtype)

    _when(qm == q_steps - 1, _finish_dkv)
    _when(kj == k_steps - 1, _finish_dq)


def _specs(sch: Schedule, d: int, h: int, rep: int, causal: bool, off: int,
           walk: str):
    """The BlockSpecs of one kernel's grid, by what they carry. walk 'k':
    grid (head block, q tile, k span): fwd and dQ (and the delta pre-pass,
    its third axis one step); walk 'q': grid (head block, k tile, q span),
    dK/dV; walk 'fused': grid (head block, k step, q span) of the fused
    backward, whose k step holds `sch.bwd_span_k` rows. 'q' / 'k': the
    operands of either side, `sch.heads_per_block` heads of [b, s, h*d] in
    place ('k' at the key / value head that `rep` query heads share, 'dk'
    key rows at the query's own head) or one head of a flat [b*h, s, d];
    'stat_q': those heads' query rows of [b*h, 1, sq] (lse, delta); 'row_q'
    / 'row_k': per-batch-row vectors (segment ids, key bias);
    'mask'(per_head): the dense mask's slab, for each of the block's heads
    where it has heads; 'dq'(k steps): the fused backward's dQ rows, which
    stay where they are until the last k step and then follow the q span,
    dead steps too, so that each block is written once."""
    bq, bk = sch.block_q, sch.block_k
    if walk == "k":
        nq_rows, nk_rows = bq, sch.span_k

        def at(g1, g2):
            if causal:   # a span wholly above the diagonal: copy nothing
                g2 = jnp.minimum(g2, (off + (g1 + 1) * bq - 1)
                                 // sch.span_k)
            return g1, g2
    else:
        nq_rows, nk_rows = ((sch.bwd_span_q, sch.bwd_span_k)
                            if walk == "fused" else (sch.span_q, bk))

        def at(g1, g2):
            if causal:
                g2 = jnp.maximum(g2, jnp.maximum(g1 * nk_rows - off, 0)
                                 // nq_rows)
            return g2, g1

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda g, g1, g2: index(g, *at(g1, g2)))

    heads = sch.heads
    per_row = h // heads                    # grid steps to a batch row
    if sch.heads_per_block:
        lanes = heads * d

        def operand(g, rows, share=1):
            return g // per_row, rows, g % per_row // share
    else:
        lanes = d

        def operand(g, rows, share=1):
            return g, rows, 0

    return dict(
        q=spec((1, nq_rows, lanes), lambda g, i, j: operand(g, i)),
        k=spec((1, nk_rows, lanes), lambda g, i, j: operand(g, j, rep)),
        dk=spec((1, nk_rows, lanes), lambda g, i, j: operand(g, j)),
        # per-row vectors ride as [n, 1, s] with (1, 1, rows) blocks: a
        # bare (1, rows) block over [n, s] breaks the TPU block rule
        # (second-to-last block dim 8-aligned or the whole dim) for every
        # n but 1
        stat_q=spec((heads, 1, nq_rows), lambda g, i, j: (g, 0, i)),
        row_q=spec((1, 1, nq_rows), lambda g, i, j: (g // per_row, 0, i)),
        row_k=spec((1, 1, nk_rows), lambda g, i, j: (g // per_row, 0, j)),
        mask=lambda per_head: spec(
            (heads if per_head else 1, nq_rows, nk_rows),
            (lambda g, i, j: (g, i, j)) if per_head else
            (lambda g, i, j: (g // per_row, i, j))),
        dq=lambda k_steps: pl.BlockSpec(
            (1, nq_rows, lanes), lambda g, g1, g2: operand(
                g, jnp.where(g1 == k_steps - 1, g2, 0))),
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


def _laid(t, sch: Schedule):
    """What the kernels read of a [b, s, h, d] operand: the caller's own
    memory as [b, s, h*d], or the flat copy."""
    if sch.heads_per_block:
        return t.reshape(t.shape[0], t.shape[1], -1)
    return _flat(t)


def _unlaid(t, b: int, h: int, sch: Schedule):
    """A kernel's result back as [b, s, h, d]."""
    if sch.heads_per_block:
        return t.reshape(b, t.shape[1], h, -1)
    return _unflat(t, b)


def _flash_forward(q, k, v, mask, kbias, qseg, kseg, block_mask,
                   causal: bool, scale: float, sch: Schedule,
                   interpret: bool, with_lse: bool = False):
    """q [b, sq, h, d], k/v [b, sk, hk, d] -> out [b, sq, h, d] (+ lse
    [b*h, 1, sq] fp32)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    heads = sch.heads
    specs = _specs(sch, d, h, h // k.shape[2], causal, sk - sq, "k")
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, specs, block_mask=block_mask)
    kernel = functools.partial(
        _q_walk_kernel, block_k=sch.block_k, heads=heads, causal=causal,
        scale=scale, off=sk - sq, has_mask=mask is not None,
        has_kbias=kbias is not None, has_seg=qseg is not None,
        has_blockmask=block_mask is not None, backward=False,
        with_lse=with_lse)
    ql = _laid(q, sch)
    out_shape = jax.ShapeDtypeStruct(ql.shape, q.dtype)
    out_specs = specs["q"]
    if with_lse:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32))
        out_specs = (out_specs, specs["stat_q"])
    res = pl.pallas_call(
        kernel, out_shape=out_shape,
        grid=(b * h // heads, sq // sch.block_q, sk // sch.span_k),
        in_specs=[specs["q"], specs["k"], specs["k"]] + extra_specs,
        out_specs=out_specs,
        scratch_shapes=[_scratch((heads, sch.block_q, 1)),
                        _scratch((heads, sch.block_q, 1)),
                        _scratch((sch.block_q, heads * d))],
        interpret=interpret, name="flash_fwd",
    )(ql, _laid(k, sch), _laid(v, sch), *extra_in)
    if with_lse:
        return _unlaid(res[0], b, h, sch), res[1]
    return _unlaid(res, b, h, sch)


def _flash_backward(q, k, v, o, do, lse, mask, kbias, qseg, kseg,
                    block_mask, causal, scale, sch: Schedule, interpret):
    """Returns (dq, dk, dv) in the layouts of q, k and v."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1:3]
    heads = sch.heads
    ql, kl, vl, dol, ol = (_laid(t, sch) for t in (q, k, v, do, o))
    common = dict(heads=heads, causal=causal, scale=scale, off=sk - sq,
                  has_mask=mask is not None, has_kbias=kbias is not None,
                  has_seg=qseg is not None,
                  has_blockmask=block_mask is not None)
    per_query = ql.shape[:1] + (sk,) + ql.shape[2:]

    def grouped(t):
        """[b, sk, h, d] a query head -> the sum over each key/value
        head's group, what the gradient of a repeated head is."""
        if hk == h:
            return t
        t = t.reshape(b, sk, hk, h // hk, d).astype(jnp.float32)
        return jnp.sum(t, axis=3).astype(k.dtype)

    def results(dq, dk, dv):
        return (_unlaid(dq, b, h, sch), grouped(_unlaid(dk, b, h, sch)),
                grouped(_unlaid(dv, b, h, sch)))

    if sch.fused_backward:
        # ---- delta, then dQ, dK and dV in one pass: grid (head block, k
        # step, q span), dK and dV a QUERY head -----------------------------
        rows = _tile(sq, DELTA_ROWS)    # a step reads; block_q divides sq
        specs = _specs(sch._replace(block_q=rows), d, h, h // hk, causal,
                       sk - sq, "k")
        delta = pl.pallas_call(
            functools.partial(_delta_kernel, heads=heads),
            out_shape=jax.ShapeDtypeStruct(lse.shape, jnp.float32),
            grid=(b * h // heads, sq // rows, 1),
            in_specs=[specs["q"], specs["q"]], out_specs=specs["stat_q"],
            interpret=interpret, name="flash_bwd_delta",
        )(ol, dol)
        specs = _specs(sch, d, h, h // hk, causal, sk - sq, "fused")
        extra_in, extra_specs = _extra_inputs_specs(
            mask, kbias, qseg, kseg, specs, block_mask=block_mask)
        steps = (sk // sch.bwd_span_k, sq // sch.bwd_span_q)
        lanes = heads * d                       # of a block
        kw = {}
        if sch.bwd_vmem_limit and not interpret:
            kw["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=sch.bwd_vmem_limit)
        return results(*pl.pallas_call(
            functools.partial(_fused_kernel, block_q=sch.block_q,
                              block_k=sch.block_k, steps=steps, **common),
            out_shape=(jax.ShapeDtypeStruct(ql.shape, q.dtype),
                       jax.ShapeDtypeStruct(per_query, k.dtype),
                       jax.ShapeDtypeStruct(per_query, v.dtype)),
            grid=(b * h // heads,) + steps,
            in_specs=[specs["q"], specs["k"], specs["k"], specs["q"]]
            + extra_specs + [specs["stat_q"], specs["stat_q"]],
            out_specs=(specs["dq"](steps[0]), specs["dk"], specs["dk"]),
            scratch_shapes=[_scratch((sq, lanes)),
                            _scratch((sch.bwd_span_k, lanes)),
                            _scratch((sch.bwd_span_k, lanes))],
            interpret=interpret, name="flash_bwd", **kw,
        )(ql, kl, vl, dol, *extra_in, lse, delta))

    # ---- dQ: grid (head block, q tile, k span) ---------------------------
    specs = _specs(sch, d, h, h // hk, causal, sk - sq, "k")
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, specs, block_mask=block_mask)
    dq, delta = pl.pallas_call(
        functools.partial(_q_walk_kernel, block_k=sch.block_k,
                          backward=True, with_lse=False, **common),
        out_shape=(jax.ShapeDtypeStruct(ql.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)),
        grid=(b * h // heads, sq // sch.block_q, sk // sch.span_k),
        in_specs=[specs["q"], specs["k"], specs["k"], specs["q"],
                  specs["q"]] + extra_specs + [specs["stat_q"]],
        out_specs=(specs["q"], specs["stat_q"]),
        scratch_shapes=[_scratch((sch.block_q, heads * d))],
        interpret=interpret, name="flash_bwd_dq",
    )(ql, kl, vl, dol, ol, *extra_in, lse)

    # ---- dK/dV: grid (head block, k tile, q span), a result a QUERY head -
    specs = _specs(sch, d, h, h // hk, causal, sk - sq, "q")
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, specs, block_mask=block_mask)
    dk, dv = pl.pallas_call(
        functools.partial(_k_walk_kernel, block_q=sch.block_q, **common),
        out_shape=(jax.ShapeDtypeStruct(per_query, k.dtype),
                   jax.ShapeDtypeStruct(per_query, v.dtype)),
        grid=(b * h // heads, sk // sch.block_k, sq // sch.span_q),
        in_specs=[specs["q"], specs["k"], specs["k"], specs["q"]]
        + extra_specs + [specs["stat_q"], specs["stat_q"]],
        out_specs=(specs["dk"], specs["dk"]),
        scratch_shapes=[_scratch((sch.block_k, heads * d)),
                        _scratch((sch.block_k, heads * d))],
        interpret=interpret, name="flash_bwd_dkv",
    )(ql, kl, vl, dol, *extra_in, lse, delta)
    return results(dq, dk, dv)


def _a_head_a_query(k, v, h: int):
    """k and v with each key/value head laid out once per query head of
    its group (as they are where they have `h` heads already)."""
    if k.shape[2] == h:
        return k, v
    return tuple(jnp.repeat(t, h // k.shape[2], axis=2) for t in (k, v))


def _reference(q, k, v, causal, scale, mask=None, kbias=None, qseg=None,
               kseg=None):
    k, v = _a_head_a_query(k, v, q.shape[2])
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
    # runs when a program's backward is TRACED: once a compile, a Python
    # integer, nothing a step
    from paddle_tpu import profiler

    profiler.count_traced("flash_bwd_fused" if sch.fused_backward
                          else "flash_bwd_two_kernels")
    q, k, v, mask, kbias, qseg, kseg, block_mask, o, lse = res
    dq, dk, dv = _flash_backward(q, k, v, o, g, lse, mask, kbias, qseg,
                                 kseg, block_mask, causal, scale, sch,
                                 interpret)
    return (dq, dk, dv, _zero_cot(mask), _zero_cot(kbias),
            _zero_cot(qseg), _zero_cot(kseg), _zero_cot(block_mask))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _operands_ok(q, k, v=None) -> bool:
    # d % 8 == 0: heads that fill no whole 128-lane block run on flat
    # copies, where Mosaic pads the lanes, so any such head size runs the
    # flash kernel instead of silently falling back to the O(seq^2) XLA
    # path. k and v may have one head per group of query heads.
    return (q.shape[-1] % 8 == 0
            and (q.shape[0], q.shape[3]) == (k.shape[0], k.shape[3])
            and q.shape[2] % k.shape[2] == 0
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
    "two-kernels", matching the case list built below).

    Covers the dense-causal, additive-padding-mask, and segment-id (varlen)
    paths, each with the backward `schedule()` gives it (the fused one at
    every shape whose dQ accumulator fits VMEM: all of these), and the
    dense-causal path once more with the two-kernel backward forced.
    Single source of truth for the kernel-vs-reference criterion —
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
              (shapes[0], "segments"), (shapes[0], "two-kernels")]
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
                scale=scale, fused=mode != "two-kernels"):
            qs, ks = (segs, segs) if segs is not None else (None, None)
            sch = schedule(q.shape, k.shape, q.dtype, causal,
                           mask=0 if mask is None else mask.shape[1],
                           fused=fused)
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


def _warn_once(q, k, what: str):
    key = (tuple(q.shape), tuple(k.shape), what)
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        import warnings

        warnings.warn(
            f"flash_attention: shapes q={tuple(q.shape)} k={tuple(k.shape)} "
            + what, stacklevel=4)


def _log_fallback(q, k):
    """The silent-fallback condition is a dead-kernel bug magnet — warn once
    per shape so it is visible which configs miss the flash path."""
    _warn_once(q, k, "don't tile; using the O(seq^2) XLA reference path")


def _log_flat(q, k):
    """Likewise for the layout: once per shape whose heads fill no whole
    128-lane block of [b, s, h*d], so that the kernels run on transposed
    copies, twelve a call and its gradient."""
    _warn_once(q, k, "fill no whole 128-lane block a head (or 128 // "
               "head_dim of them); the kernels run on flat [b*h, s, d] "
               "copies")


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
    ok = not (causal and sq > sk) and _operands_ok(q, k, v)
    if ok and not _heads_per_block(h, k.shape[2], d):
        # a shared key/value head is read in place only as a whole block
        k, v = _a_head_a_query(k, v, h)
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
    # in a program compiled for a mesh each device runs the kernels on its
    # own batch rows ('dp') and heads ('tp'): the schedule is a shard's
    hk = k.shape[2]
    mesh, bax, hax = _program_mesh_axes(b, hk) or (None, None, None)
    nb, nh = (mesh.shape[a] if a else 1 for a in (bax, hax))
    sch = None
    if ok:
        sch = schedule((b // nb, sq, h // nh, d), (b // nb, sk, hk // nh, d),
                       q.dtype, causal,
                       mask=0 if mask is None else mask.shape[1],
                       block_mask_shape=None if block_mask is None
                       else block_mask.shape,
                       block_q=block_q, block_k=block_k)
    if sch is None:
        _log_fallback(q, k)
        return _reference(q, k, v, causal, scale, mask, kbias, qseg, kseg)
    if not sch.heads_per_block:
        _log_flat(q, k)
    statics = (causal, scale, sch, interpret)
    if mesh is None:
        return _flash(q, k, v, mask, kbias, qseg, kseg, block_mask,
                      *statics)
    # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    # shard_map"), so every mesh axis is manual
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
    only where it divides the dimension (`h`: the key/value heads, which
    divide the queries'); unnamed axes compute replicated."""
    from paddle_tpu.parallel.mesh import program_mesh

    mesh = program_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None

    def pick(axis, n):
        return (axis if axis in mesh.axis_names
                and n % mesh.shape[axis] == 0 else None)

    return mesh, pick("dp", b), pick("tp", h)
