"""Ragged paged-attention kernel (ISSUE 4) vs the gather reference path.

Two layers of pinning: (1) the kernel itself, swept over (q_len,
start_pos, n_rep, page count, padded buckets) in Pallas interpret mode
against the gather + dense-mask oracle — including mixed decode/prefill
spans and dead slots in ONE launch; (2) the serving engine end-to-end
with the ragged path forced on (attn_impl="ragged", ragged_batch=True,
chunked prefill + prefix cache), token-for-token vs `naive_generate`,
plus the instrumented-pool acceptance: >= 2x attention-bytes reduction
vs the gather path on a long-context chunked workload (CPU-countable)."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.generation import masked_cache_attention, paged_gather
import importlib

from paddle_tpu.ops.pallas.ragged_paged_attention import (
    Q_TILE, attention_page_reads, pages_per_block, ragged_attention_ok,
    ragged_paged_attention, ragged_reference,
)

# the module, not the function of the same name the package re-exports
rpa = importlib.import_module("paddle_tpu.ops.pallas.ragged_paged_attention")

rng = np.random.default_rng(7)


def _pools(B=2, n_kv=2, d=16, ps=8, pages=6, n_rep=1, T=8):
    nb = 1 + B * pages
    kp = jnp.asarray(rng.standard_normal((nb, ps, n_kv, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, ps, n_kv, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(np.arange(1, nb))
                      .reshape(B, pages).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((B, T, n_kv * n_rep, d)),
                    jnp.float32)
    return q, kp, vp, tbl


# ------------------------------------------------------------ kernel sweep

@pytest.mark.parametrize("q_len,start_pos", [
    (1, 0), (1, 7), (1, 8), (1, 37),        # decode at page boundaries
    (5, 0), (8, 0),                          # fresh prefill
    (3, 13), (8, 16), (6, 40),               # offset chunks
])
@pytest.mark.parametrize("n_rep", [1, 2, 4])
def test_kernel_vs_reference_sweep(q_len, start_pos, n_rep):
    q, kp, vp, tbl = _pools(n_rep=n_rep)
    starts = jnp.asarray([start_pos, max(0, start_pos - 2)], jnp.int32)
    qlens = jnp.asarray([q_len, max(1, q_len - 1)], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True)
    ref = ragged_reference(q, kp, vp, tbl, starts, qlens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_rep", [1, 2])
def test_tiled_span_vs_reference(n_rep):
    """A span of several Q_TILE row tiles: each tile walks only the pages
    its own last row can see, a tile past q_len is dead (exact zeros),
    and the result equals the one-tile reference."""
    T = 2 * Q_TILE
    q, kp, vp, tbl = _pools(B=2, pages=40, T=T, n_rep=n_rep)
    starts = jnp.asarray([3, 17], jnp.int32)
    qlens = jnp.asarray([T, Q_TILE - 5], jnp.int32)   # slot 1: tile 1 dead
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True)
    ref = ragged_reference(q, kp, vp, tbl, starts, qlens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert bool((np.asarray(out[1, Q_TILE - 5:]) == 0.0).all())


def test_matches_gather_masked_cache_attention():
    """The serving oracle itself: gather + repeat + masked_cache_attention
    must agree on every LIVE row (the reference the engine falls back to,
    so kernel == ragged_reference == the production gather path)."""
    n_rep = 3
    q, kp, vp, tbl = _pools(n_rep=n_rep)
    starts = jnp.asarray([9, 21], jnp.int32)
    qlens = jnp.asarray([8, 4], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True)
    kg = jnp.repeat(paged_gather(kp, tbl), n_rep, axis=2)
    vg = jnp.repeat(paged_gather(vp, tbl), n_rep, axis=2)
    B, T, nq, d = q.shape
    ref = masked_cache_attention(q, kg, vg, starts).reshape(B, T, nq, d)
    for b in range(B):
        L = int(qlens[b])
        np.testing.assert_allclose(np.asarray(out[b, :L]),
                                   np.asarray(ref[b, :L]),
                                   rtol=1e-5, atol=1e-5)


def test_mixed_spans_one_launch():
    """The fused serving shape: a decode step, a prefill chunk, and a
    dead slot in the SAME launch."""
    q, kp, vp, tbl = _pools(B=3, n_rep=2)
    starts = jnp.asarray([33, 8, 0], jnp.int32)
    qlens = jnp.asarray([1, 8, 0], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True)
    ref = ragged_reference(q, kp, vp, tbl, starts, qlens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert bool((np.asarray(out[2]) == 0.0).all()), "dead slot must be 0"
    assert np.isfinite(np.asarray(out)).all()


def test_padded_bucket_rows_are_zero_and_live_rows_invariant():
    """Bucket-padding invariance: the same spans in a 2x-wider padded
    bucket give BIT-IDENTICAL live rows (per-row online softmax never
    sees the padding) and exact-zero padded rows."""
    q, kp, vp, tbl = _pools(T=4)
    starts = jnp.asarray([5, 17], jnp.int32)
    qlens = jnp.asarray([4, 3], jnp.int32)
    tight = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                   interpret=True)
    q_wide = jnp.concatenate(
        [q, jnp.asarray(rng.standard_normal(q.shape), jnp.float32)], axis=1)
    wide = ragged_paged_attention(q_wide, kp, vp, tbl, starts, qlens,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(tight[0, :4]),
                                  np.asarray(wide[0, :4]))
    np.testing.assert_array_equal(np.asarray(tight[1, :3]),
                                  np.asarray(wide[1, :3]))
    assert bool((np.asarray(wide[:, 4:]) == 0.0).all())
    assert bool((np.asarray(wide[1, 3:]) == 0.0).all())


def test_dead_pages_cost_nothing_and_change_nothing():
    """Page-count invariance of the walk: the same span content with 3x
    more (dead) table pages is bit-identical, and the instrumented
    page-read count says the dead pages were never read."""
    B, n_kv, d, ps = 2, 2, 16, 8
    starts = np.asarray([9, 21], np.int32)
    qlens = np.asarray([4, 1], np.int32)
    n_live = 4                              # ceil((21+1)/8) + slack
    kv = rng.standard_normal((B, n_live * ps, n_kv, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, 4, n_kv, d)), np.float32)

    def run(pages):
        nb = 1 + B * pages
        kp = np.zeros((nb, ps, n_kv, d), np.float32)
        vp = np.zeros((nb, ps, n_kv, d), np.float32)
        tbl = (1 + np.arange(B * pages, dtype=np.int32)).reshape(B, pages)
        for i in range(B):
            for j in range(n_live):
                kp[tbl[i, j]] = kv[i, j * ps:(j + 1) * ps]
                vp[tbl[i, j]] = kv[i, j * ps:(j + 1) * ps] * 0.5
        return ragged_paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                      jnp.asarray(tbl), starts, qlens,
                                      interpret=True)

    np.testing.assert_array_equal(np.asarray(run(n_live)),
                                  np.asarray(run(3 * n_live)))
    reads = attention_page_reads(starts, qlens, ps)
    np.testing.assert_array_equal(reads, [2, 3])   # live pages only


# ------------------------------------------------- the in-kernel page walk

PS, N_KV, N_REP, D = 8, 2, 2, 16


def _block_pages(T, int8=False):
    """pages_per_block of the walk for this file's toy layout and a span
    bucket of T rows."""
    return pages_per_block(T, N_KV * N_REP, 4, PS, N_KV, D, 1 if int8 else 4)


def _walk_cases(int8=False):
    """name -> (T, contexts after the span, q_lens): spans whose keys end
    around the walk's block boundary."""
    dec, pre = _block_pages(1, int8) * PS, _block_pages(16, int8) * PS
    return {
        # decode rows: the last key is the block's last but one, its
        # last, and the first of the next block
        "one-key-short-of-a-block": (1, [dec - 1, dec - 1, 5], [1, 1, 1]),
        "exactly-one-block": (1, [dec, 2 * dec, 3], [1, 1, 1]),
        "one-block-and-a-key": (1, [dec + 1, 2 * dec + 1, dec], [1, 1, 1]),
        "table-wider-by-blocks": (1, [PS + 3, 2, 1], [1, 1, 1]),
        "dead-slot-between-live": (1, [dec + 9, 77, 3 * dec - 2], [1, 0, 1]),
        "whole-batch-dead": (1, [dec, 9, 40], [0, 0, 0]),
        # a prefill chunk whose last page is partial, across the boundary
        "chunk-partial-last-page": (16, [pre + 5, pre - 3, 21], [16, 11, 16]),
        # a span wide enough for the head-major tile (not the flat one)
        "tile-partial-last-page": (
            64, [_block_pages(64, int8) * PS + 13, 64, 70], [64, 64, 3]),
    }


def _poisoned_walk_inputs(T, ctx, qlens, int8):
    """Pools whose every page OUTSIDE a sequence's live range (the pages
    up to its last visible key) is poison: NaN in float pools, NaN in the
    scale rows of int8 pools. The reference reads the same pools with
    the poison replaced by zeros."""
    B = len(ctx)
    width = 4 * _block_pages(T, int8)
    assert max(ctx) <= width * PS
    nb = 1 + B * width
    prng = np.random.default_rng(29)
    tbl = prng.permutation(np.arange(1, nb)).reshape(B, width).astype(np.int32)
    starts = np.asarray(ctx, np.int32) - np.asarray(qlens, np.int32)
    live = np.zeros(nb, bool)
    for b, n in enumerate(attention_page_reads(starts, qlens, PS)):
        live[tbl[b, :n]] = True
    q = jnp.asarray(prng.standard_normal((B, T, N_KV * N_REP, D)), jnp.float32)
    kv = prng.standard_normal((2, nb, PS, N_KV, D)).astype(np.float32)

    def poisoned(x, poison):            # rows of dead pages <- poison
        return jnp.asarray(np.where(
            live.reshape((1, nb) + (1,) * (x.ndim - 2)), x, poison))

    if int8:
        pools = ref_pools = [jnp.asarray(np.clip(np.round(x * 40), -127, 127),
                                         jnp.int8) for x in kv]
        sc = prng.uniform(0.01, 0.03, (2, nb, N_KV)).astype(np.float32)
        k_sc, v_sc = poisoned(sc, np.nan)
        kw = dict(k_scale=k_sc, v_scale=v_sc)
        # a NaN scale would poison the reference's gather of the whole
        # table: it reads scales zeroed outside the live range instead
        k_sc, v_sc = poisoned(sc, 0.0)
        ref_kw = dict(k_scale=k_sc, v_scale=v_sc)
    else:
        pools, ref_pools = list(poisoned(kv, np.nan)), list(poisoned(kv, 0.0))
        kw = ref_kw = {}
    args = (jnp.asarray(tbl), jnp.asarray(starts),
            jnp.asarray(qlens, jnp.int32))
    return q, pools, ref_pools, args, kw, ref_kw


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("case", sorted(_walk_cases()))
def test_walk_crosses_block_boundaries_and_reads_no_dead_page(case, int8):
    T, ctx, qlens = _walk_cases(int8)[case]
    q, pools, ref_pools, args, kw, ref_kw = _poisoned_walk_inputs(
        T, ctx, qlens, int8)
    out = np.asarray(ragged_paged_attention(q, *pools, *args,
                                            interpret=True, **kw))
    ref = np.asarray(ragged_reference(q, *ref_pools, *args, **ref_kw))
    assert np.isfinite(out).all(), "a dead page reached the result"
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    for b, n in enumerate(qlens):
        assert (out[b, n:] == 0.0).all()


# --------------------------------- interior and edge blocks of a walk (43)


def _edge_layouts():
    """name -> (dtype, n_q, n_kv, d, row pools?): the toy layout in float32,
    and the three cells' head layouts in bfloat16 (10 pair heads in row
    pools, 16 heads, 30 heads that the kernel pads to 32)."""
    return {"toy-f32": (jnp.float32, N_KV * N_REP, N_KV, D, False),
            "rows-10x128": (jnp.bfloat16, 40, 10, 128, True),
            "heads-16x128": (jnp.bfloat16, 16, 16, 128, False),
            "heads-30x128": (jnp.bfloat16, 30, 30, 128, False)}


def _edge_cases(keys):
    """name -> (T, starts, q_lens, lowers or None) around the places where
    the walk's three ranges split, `keys` the keys of one block."""
    return {
        # row 0 on a block's last key (that block is interior), one
        # before it (it is an edge), one after (the next block holds one key)
        "start-on-one-before-one-after-a-last-key": (
            1, [2 * keys - 1, 2 * keys - 2, 2 * keys], [1, 1, 1], None),
        "one-block-one-key-and-a-dead-slot": (
            1, [keys - 1, 0, 3 * keys + 5], [1, 1, 0], None),
        # the bound on a block's first key (interior from there), inside a
        # block, and inside the walk's last block
        "lower-on-a-first-key-mid-block-in-the-last-block": (
            1, [3 * keys - 1, 3 * keys + 7, 2 * keys + 9],
            [1, 1, 1], [keys, keys + 5, 2 * keys + 3]),
        "lower-with-one-block-and-a-dead-slot": (
            1, [keys - 1, keys + 4, 40], [1, 0, 1], [0, 3, 33]),
        # a span of several rows: its causal triangle crosses a block's
        # end, lies in one block, stands on a block's first key; rows past
        # q_len, and a dead slot
        "span-triangle-across-a-block-end": (
            4, [keys - 2, 2 * keys + 3, 2 * keys, 5], [4, 2, 3, 0], None),
        "span-under-a-lower-bound": (
            4, [2 * keys - 3, keys, 2 * keys + 1], [4, 1, 3],
            [keys, 7, keys + 2]),
    }


def _exact_live_inputs(layout, T, starts, qlens, lowers, keys, ppb, ps):
    """Pools that hold NaN wherever no row of any sequence may look: pages
    outside every table, pages of a table outside [lower, last key], and
    inside a live page the keys before the bound and past the last key.
    The reference reads the same pools with zeros there."""
    dtype, n_q, n_kv, d, rows = _edge_layouts()[layout]
    B = len(starts)
    width = 4 * ppb
    nb = 1 + B * width + 3
    prng = np.random.default_rng(43)
    tbl = prng.permutation(np.arange(1, nb - 3)).reshape(B, width).astype(
        np.int32)
    live = np.zeros((nb, ps), bool)
    for b in range(B):
        lo = 0 if lowers is None else lowers[b]
        for pos in range(lo, starts[b] + qlens[b]):
            live[tbl[b, pos // ps], pos % ps] = True
    q = jnp.asarray(prng.standard_normal((B, T, n_q, d)), dtype)
    kv = prng.standard_normal((2, nb, ps, n_kv, d)).astype(np.float32)
    pools, ref_pools = [], []
    for x in kv:
        for out, dead in ((pools, np.nan), (ref_pools, 0.0)):
            y = jnp.asarray(np.where(live[:, :, None, None], x, dead), dtype)
            out.append(y.reshape(nb, ps * n_kv, d) if rows else y)
    kw = dict(kv_heads=n_kv) if rows else {}
    if lowers is not None:
        kw["lower"] = jnp.asarray(lowers, jnp.int32)
    args = (jnp.asarray(tbl), jnp.asarray(starts, jnp.int32),
            jnp.asarray(qlens, jnp.int32))
    return q, pools, ref_pools, args, kw


@pytest.mark.parametrize("case", sorted(_edge_cases(1)))
@pytest.mark.parametrize("layout", sorted(_edge_layouts()))
def test_interior_and_edge_blocks_equal_the_reference(layout, case):
    """The walk's ranges split where a block stops being interior; on each
    side of every such place the kernel equals the gather oracle, rows past
    q_len and dead slots are exact zeros, and no NaN outside what a row
    sees reaches the result (an interior block masks nothing: it must hold
    nothing to mask)."""
    dtype, n_q, n_kv, d, rows = _edge_layouts()[layout]
    ps = PS if layout == "toy-f32" else 16
    # a span as long as the few-rows arm takes of these heads
    T = min(_edge_cases(1)[case][0], rpa.FLAT_ROWS // n_q)
    ppb = pages_per_block(T, n_q, q_itemsize := np.dtype(dtype).itemsize, ps,
                          n_kv, d, q_itemsize, row_pools=rows)
    keys = ppb * ps
    _, starts, qlens, lowers = _edge_cases(keys)[case]
    qlens = [min(n, T) for n in qlens]
    q, pools, ref_pools, args, kw = _exact_live_inputs(
        layout, T, starts, qlens, lowers, keys, ppb, ps)
    out = np.asarray(ragged_paged_attention(
        q, *pools, *args, interpret=True, **kw).astype(jnp.float32))
    ref = np.asarray(ragged_reference(q, *ref_pools, *args, **kw).astype(
        jnp.float32))
    assert np.isfinite(out).all(), "a key no row sees reached the result"
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    for b, n in enumerate(qlens):
        assert (out[b, n:] == 0.0).all()
    if case == "start-on-one-before-one-after-a-last-key":
        # and the split is where the counter says it is: a walk that ends
        # on a block's last key has no edge block at all
        blocks, edges = rpa.ragged_block_counts(starts, qlens, ps, ppb)
        assert (blocks.tolist(), edges.tolist()) == ([2, 2, 3], [0, 1, 1])


def test_a_span_too_wide_to_stack_keeps_three_products():
    """The three bf16 terms of p are one MXU pass where they fit its rows
    as whole float32 tiles; a verify span of 64 rows keeps three products
    and the same result."""
    assert rpa._stacked(40) and rpa._stacked(16) and rpa._stacked(32)
    assert not rpa._stacked(64) and not rpa._stacked(12)
    n_q, n_kv, d, ps = 32, 8, 128, 16
    ppb = pages_per_block(2, n_q, 2, ps, n_kv, d, 2)
    keys = ppb * ps
    prng = np.random.default_rng(5)
    nb = 1 + 2 * 2 * ppb
    tbl = jnp.asarray(prng.permutation(np.arange(1, nb)).reshape(
        2, 2 * ppb).astype(np.int32))
    q = jnp.asarray(prng.standard_normal((2, 2, n_q, d)), jnp.bfloat16)
    kp, vp = (jnp.asarray(prng.standard_normal((nb, ps, n_kv, d)),
                          jnp.bfloat16) for _ in range(2))
    starts = jnp.asarray([keys + 3, keys - 1], jnp.int32)
    qlens = jnp.asarray([2, 1], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True).astype(jnp.float32)
    ref = ragged_reference(q, kp, vp, tbl, starts, qlens).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    assert (np.asarray(out)[1, 1:] == 0.0).all()


@pytest.mark.parametrize("cell,shape,want", [
    ("phi-4-mini-flash.reason-12k", (1, 40, 2, 16, 10, 128, 2, True), 16),
    ("gpt3-1.3b.decode", (1, 16, 2, 16, 16, 128, 2, False), 16),
    ("olmo-hybrid-7b.decode-wide", (1, 30, 2, 16, 30, 128, 2, False), 8),
])
def test_block_pages_of_the_cells_decode_shapes(cell, shape, want):
    """What the three serving cells' walks copy and fold at a time: the
    benchmark's rooflines were read with these."""
    *dims, rows = shape
    assert pages_per_block(*dims, row_pools=rows) == want
    assert rpa.few_rows_block_pages(*dims, row_pools=rows) == want
    # a prefill span's tiles take the other arm: no few-rows block
    assert rpa.few_rows_block_pages(256, *dims[1:], row_pools=rows) == 0 \
        or rows


def _brute_block_counts(start, qlen, ps, ppb, lower):
    """Key by key: the blocks between the bound's and the last visible
    key's, and those with a key some live row cannot see (past row 0, or
    before the bound) or a slot past the walk's last page."""
    if qlen <= 0:
        return 0, 0
    keys, last = ps * ppb, start + qlen - 1
    blocks = edges = 0
    for j in range(lower // keys, last // keys + 1):
        blocks += 1
        positions = range(j * keys, (j + 1) * keys)
        edges += any(pos < lower or pos > start or pos // ps > last // ps
                     for pos in positions)
    return blocks, edges


@pytest.mark.parametrize("seed", range(4))
def test_block_counts_equal_a_brute_force_count(seed):
    prng = np.random.default_rng(seed)
    ps, ppb = int(prng.choice([4, 8, 16])), int(prng.choice([1, 2, 8]))
    n = 200
    start = prng.integers(0, 40 * ps * ppb // 8 + 8, n)
    qlen = prng.integers(0, 6, n)
    lower = np.minimum(prng.integers(0, 30 * ps, n) * prng.integers(0, 2, n),
                       start)
    for bound in (None, lower):
        blocks, edges = rpa.ragged_block_counts(start, qlen, ps, ppb, bound)
        want = [_brute_block_counts(int(s), int(q), ps, ppb,
                                    0 if bound is None else int(lo))
                for s, q, lo in zip(start, qlen, lower)]
        assert blocks.tolist() == [w[0] for w in want]
        assert edges.tolist() == [w[1] for w in want]


def test_page_reads_count_the_copies_the_kernel_starts(monkeypatch):
    """attention_page_reads' contract: for a mixed batch (decode rows, a
    prefill chunk, a dead slot, a span across a block boundary) its count
    is the number of page copies the kernel starts, K and V each —
    counted where they are started, in interpret mode."""
    import jax

    started = []
    real = rpa.pltpu.make_async_copy

    class Counted:
        def __init__(self, *a):
            self.cp = real(*a)

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.cp.start()

        def wait(self):
            self.cp.wait()

    monkeypatch.setattr(rpa.pltpu, "make_async_copy",
                        lambda *a: Counted(*a))
    jax.clear_caches()          # the kernel is traced anew, with the count
    T = 16
    block_keys = _block_pages(T) * PS
    ctx = [block_keys + 5, 16, 40, 3 * PS]
    qlens = [1, 16, 0, 9]
    q, pools, _, args, _, _ = _poisoned_walk_inputs(T, ctx, qlens, False)
    jax.block_until_ready(
        ragged_paged_attention(q, *pools, *args, interpret=True))
    jax.effects_barrier()
    reads = attention_page_reads(np.asarray(args[1]), qlens, PS)
    assert reads.tolist() == [block_keys // PS + 1, 2, 0, 3]
    assert len(started) == 2 * int(reads.sum())
    jax.clear_caches()          # and no later test meets the counted one


def test_page_reads_of_a_tiled_span_are_its_last_tiles():
    """A span of several tiles walks its pages once per tile; the count
    stays the distinct pages (the last live tile's walk), and the
    kernel's loop bound says so tile by tile."""
    n_rep, T, start, qlen = 1, 2 * Q_TILE, 3, Q_TILE + 9
    tq = rpa._span_tile(T, n_rep)
    assert tq == Q_TILE
    per_tile = [int(rpa._tile_pages(start, qlen, t0, tq, PS, 1 << 20))
                for t0 in range(0, T, tq)]
    assert per_tile == [(start + Q_TILE - 1) // PS + 1,
                        (start + qlen - 1) // PS + 1]
    assert attention_page_reads([start], [qlen], PS).tolist() == [per_tile[-1]]


# -------------------------------------------------------- dispatch gate

def test_dispatch_gate_learns_new_capabilities():
    assert ragged_attention_ok(64, 8, 2)
    assert ragged_attention_ok(8, 4, 4)
    assert not ragged_attention_ok(65, 8, 2)       # lane misalignment
    assert not ragged_attention_ok(64, 7, 2)       # uneven grouping


def test_runner_resolves_and_logs_impl_once_per_bucket(caplog):
    import logging

    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=67, hidden_size=32, num_layers=1,
                      num_heads=4, num_kv_heads=2, max_seq_len=32,
                      dropout=0.0)
    runner = LlamaRunner(Llama(cfg), block_size=8, max_model_len=32,
                         attn_impl="ragged")
    with caplog.at_level(logging.INFO,
                         logger="paddle_tpu.serving.model_runner"):
        assert runner._attn_impl_for(8) == "ragged"
        assert runner._attn_impl_for(8) == "ragged"
        assert runner._attn_impl_for(1) == "ragged"
    lines = [r for r in caplog.records
             if "serving attention impl" in r.getMessage()]
    assert len(lines) == 2          # once per bucket, not per call
    # auto on CPU stays on the gather oracle
    auto = LlamaRunner(Llama(cfg), block_size=8, max_model_len=32)
    assert auto._attn_impl_for(1) == "reference"


# ------------------------------------------------------- serving end-to-end

@pytest.fixture(scope="module")
def llama_gqa():
    from paddle_tpu.models.llama import Llama, LlamaConfig

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    return model


def _engine(runner, **kw):
    from paddle_tpu.serving import ServingEngine

    kw.setdefault("num_blocks", 33)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("audit", True)
    return ServingEngine(runner, **kw)


def test_engine_ragged_forced_token_exact_vs_naive(llama_gqa):
    """Acceptance: fused ragged batching + chunked prefill + prefix cache
    + the ragged kernel forced on, token-for-token vs naive_generate —
    on a GQA model, the shape that used to be gather-only."""
    from paddle_tpu.serving import LlamaRunner, SamplingParams, naive_generate

    runner = LlamaRunner(llama_gqa, block_size=8, max_model_len=64,
                         attn_impl="ragged")
    eng = _engine(runner, max_prefill_tokens_per_step=8,
                  enable_prefix_cache=True, ragged_batch=True)
    prng = np.random.default_rng(3)
    header = list(prng.integers(1, 97, 11))
    prompts = [header + list(prng.integers(1, 97, n)) for n in (3, 17, 8)]
    # staggered arrivals: the first request registers the header's full
    # page before its siblings are admitted, so they hit the cache
    rids = [eng.add_request(prompts[0], SamplingParams(max_tokens=5))]
    for _ in range(4):
        eng.step()
    rids += [eng.add_request(p, SamplingParams(max_tokens=5))
             for p in prompts[1:]]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        ref = naive_generate(runner, p, SamplingParams(max_tokens=5),
                             max_model_len=64)
        assert outs[rid].output_tokens == ref
    assert eng.metrics.prefix_hit_tokens.value > 0     # cache engaged
    assert eng.metrics.prefill_chunks.value > len(prompts)  # chunking ran
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()


def test_engine_ragged_vs_reference_cross_impl(llama_gqa):
    """Cross-implementation: the ragged-kernel engine reproduces the
    gather-path engine's greedy tokens exactly."""
    from paddle_tpu.serving import LlamaRunner, SamplingParams

    prng = np.random.default_rng(5)
    prompts = [list(prng.integers(1, 97, n)) for n in (6, 21)]
    tokens = {}
    for impl in ("reference", "ragged"):
        runner = LlamaRunner(llama_gqa, block_size=8, max_model_len=64,
                             attn_impl=impl)
        eng = _engine(runner, max_prefill_tokens_per_step=8,
                      ragged_batch=(impl == "ragged"))
        rids = [eng.add_request(p, SamplingParams(max_tokens=5))
                for p in prompts]
        outs = eng.run()
        tokens[impl] = [outs[r].output_tokens for r in rids]
    assert tokens["ragged"] == tokens["reference"]


def test_fused_step_faults_retry_token_exact(llama_gqa):
    """Satellite: FaultInjector wraps the fused call site; transient
    errors on the ragged path retry to the exact same tokens, and the
    refcount auditor stays green after every step."""
    from paddle_tpu.serving import (
        FaultInjector, LlamaRunner, SamplingParams, naive_generate,
    )

    runner = LlamaRunner(llama_gqa, block_size=8, max_model_len=64,
                         attn_impl="ragged")
    inj = FaultInjector(runner, error_every=3, error_target="decode")
    eng = _engine(inj, max_prefill_tokens_per_step=8,
                  enable_prefix_cache=True, ragged_batch=True,
                  retry_backoff_s=0.001)
    prng = np.random.default_rng(11)
    prompts = [list(prng.integers(1, 97, n)) for n in (9, 14)]
    rids = [eng.add_request(p, SamplingParams(max_tokens=5))
            for p in prompts]
    outs = eng.run()
    assert inj.injected["error"] > 0
    assert eng.metrics.step_retries.value > 0
    for rid, p in zip(rids, prompts):
        ref = naive_generate(runner, p, SamplingParams(max_tokens=5),
                             max_model_len=64)
        assert outs[rid].output_tokens == ref
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()


def test_snapshot_roundtrips_ragged_batch_knob(llama_gqa):
    from paddle_tpu.serving import LlamaRunner, ServingEngine

    runner = LlamaRunner(llama_gqa, block_size=8, max_model_len=64)
    eng = _engine(runner, ragged_batch=True)
    state = eng.snapshot()
    assert state["config"]["ragged_batch"] is True
    restored = ServingEngine.restore(runner, state)
    assert restored.ragged_batch is True


def test_shared_bucket_helper_no_duplicate_jit_entries(llama_gqa):
    """Satellite fix: one bucket rule across prefill / chunk / ragged —
    chunked calls of odd lengths land in the shared power-of-2 buckets
    and the fused step reuses the same rule, so the jit cache holds one
    entry per (kind, bucket), never one per odd length."""
    from paddle_tpu.serving import LlamaRunner, SamplingParams, bucket_len

    assert [bucket_len(t) for t in (1, 8, 9, 16, 17)] == [8, 8, 16, 16, 32]
    runner = LlamaRunner(llama_gqa, block_size=8, max_model_len=64,
                         attn_impl="ragged")
    eng = _engine(runner, max_prefill_tokens_per_step=8, ragged_batch=True)
    prng = np.random.default_rng(13)
    for n in (5, 7, 12, 13):        # odd lengths, chunked to <= 8
        eng.add_request(list(prng.integers(1, 97, n)),
                        SamplingParams(max_tokens=3))
    eng.run()
    prefill_keys = [k for k in runner._jit_cache if k[0] == "prefill"]
    ragged_keys = [k for k in runner._jit_cache if k[0] == "ragged"]
    assert all(b == bucket_len(b) for _, b in prefill_keys)
    assert all(t == bucket_len(t) for _, (_, t) in ragged_keys)
    assert len(prefill_keys) <= 1   # every chunk shares the 8-bucket
    assert len(ragged_keys) <= 1


def test_long_context_chunked_bytes_reduction(llama_gqa):
    """ISSUE-4 acceptance: on a long-context chunked workload the
    instrumented-pool counter shows >= 2x less attention HBM traffic for
    the ragged path than the gather path would have read for the SAME
    calls (both sides counted host-side — no TPU needed)."""
    from paddle_tpu.serving import LlamaRunner, SamplingParams

    # few sequences, prompts long relative to the chunk budget, a table
    # sized for a 128-token model length: the gather path pays the FULL
    # table width per slot per call, the kernel only each span's live
    # pages — so chunked prefill (live pages grow 1, 2, 3, ...) is where
    # the O(tokens-attended) traffic shape pays off
    runner = LlamaRunner(llama_gqa, block_size=8, max_model_len=128,
                         attn_impl="ragged")
    eng = _engine(runner, num_blocks=33, max_batch_size=2,
                  max_model_len=128, max_prefill_tokens_per_step=8,
                  ragged_batch=True)
    prng = np.random.default_rng(17)
    eng.add_request(list(prng.integers(1, 97, 40)),
                    SamplingParams(max_tokens=4))
    eng.add_request(list(prng.integers(1, 97, 36)),
                    SamplingParams(max_tokens=4))
    eng.run()
    read = runner.attn_kv_bytes_read
    gather = runner.attn_kv_bytes_gather
    assert read > 0 and gather >= 2.0 * read, (read, gather)
    snap = eng.metrics.snapshot()
    assert snap["attn_kv_bytes_read"] == read
    assert snap["attn_kv_bytes_gather"] == gather
