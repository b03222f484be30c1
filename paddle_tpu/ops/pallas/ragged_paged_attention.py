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
ops/pallas/flash_attention.py crossed with the scalar-prefetch block
indexing):

  * grid (batch, q_tile, page): each step folds ONE pool page into the
    accumulators of one tile of Q_TILE span rows (a whole span when it
    is shorter), so VMEM use does not grow with the prefill bucket;
    per-sequence block tables, span start
    positions, and span lengths ride in SMEM via
    pltpu.PrefetchScalarGridSpec, and the K/V BlockSpec index_map reads
    ``table[b, j]`` to DMA exactly that pool page into VMEM;
  * ragged spans: sequence b computes query rows t in [0, q_len[b])
    standing at context positions start_pos[b] + t; rows past q_len are
    hard-masked and produce exact zeros (padded buckets never NaN), so
    one launch serves decode (q_len=1), prefill chunks (q_len=chunk,
    start_pos=chunk offset), and dead batch slots (q_len=0);
  * per-sequence early-out: pages wholly past a span's last visible key
    (j*page_size > start_pos + q_len - 1) run no FLOPs (pl.when) and
    cost no DMA — the index_map clamps dead page indices to the last
    live page and the Pallas pipeline elides the repeated block copy, so
    a short sequence in a long table pays only its own pages' bandwidth;
  * native GQA: q heads are grouped by their KV head OUTSIDE the kernel
    ([B, T, n_q, d] -> [B, n_kv, T*n_rep, d]), so the in-kernel matmuls
    batch over n_kv and contract d with no head replication — grouped
    models (n_rep > 1) stop falling back to the gather path;
  * fp32 online softmax with running (m, l, acc) in VMEM scratch across
    the page walk — the attention matrix never exists in HBM, and fully
    masked rows are guarded to exact zero output.

Layout: q [B, T, n_q_heads, d]; pools [num_pages, page_size, n_kv, d];
block_table [B, pages_per_seq] int32; start_pos/q_len [B] int32.
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


def _ragged_kernel(table_ref, start_ref, qlen_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, page_size: int,
                   n_rep: int, scale: float,
                   kscale_ref=None, vscale_ref=None):
    """Grid (b, q_tile, page): fold one KV page into one tile of
    sequence b's span rows.

    With kscale_ref/vscale_ref (ISSUE 9: int8 pools), the K/V block is
    int8 codes and the per-page-per-head scales ride the SMEM scalar
    prefetch ([num_pages, n_kv] fp32, indexed by the SAME clamped page
    id the BlockSpec index_map DMA'd): the dequantize happens right
    here inside the page walk, and the online softmax stays fp32 — the
    page walk reads half the bytes, the math above it is unchanged."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_pages = pl.num_programs(2)
    n_kv, G, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    tq = G // n_rep                    # span rows in this tile

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full((n_kv, G, 1), NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros((n_kv, G, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((n_kv, G, d), jnp.float32)

    start = start_ref[b]
    qlen = qlen_ref[b]
    t0 = i * tq                        # span row of this tile's row 0
    # last key position any live row of this tile sees (causal: rows
    # past the tile never look further than its own last row)
    last_pos = start + jnp.minimum(qlen, t0 + tq) - 1

    # early-out: dead spans and tiles (t0 >= qlen) and pages past the
    # tile's last visible key fold nothing in — and their DMA was
    # elided by the clamped index_map (the revisited block is already
    # VMEM-resident)
    @pl.when((qlen > t0) & (j * page_size <= last_pos))
    def _page():
        q = q_ref[0].astype(jnp.float32)           # [n_kv, G, d]
        k = k_ref[0].astype(jnp.float32)           # [ps, n_kv, d]
        v = v_ref[0].astype(jnp.float32)
        if kscale_ref is not None:
            # same clamp as the index_map: the page id whose block is
            # VMEM-resident right now; its scale row dequantizes it
            jc = jnp.minimum(j, jnp.maximum(last_pos, 0) // page_size)
            pid = table_ref[b, jc]
            ks = jnp.stack([kscale_ref[pid, h] for h in range(n_kv)])
            vs = jnp.stack([vscale_ref[pid, h] for h in range(n_kv)])
            k = k * ks[None, :, None]
            v = v * vs[None, :, None]
        # scores[n_kv, G, ps]: batch the KV-head dim, contract d — each
        # KV head serves its n_rep grouped query rows with no replication
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale
        # grouped row r is (t, rep) flattened; its query position is
        # start + t with t = t0 + r // n_rep, and rows t >= qlen are
        # padding
        t_idx = t0 + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, G, page_size), 1) // n_rep
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, G, page_size), 2)
        s = jnp.where((k_pos <= start + t_idx) & (t_idx < qlen),
                      s, NEG_INF)
        m = m_ref[:]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # masked-row guard: where every key so far is hard-masked, new_m
        # is still NEG_INF and exp(s - new_m) would be 1 — force 0 so the
        # row's l stays 0 and its output is exactly zero
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - new_m))
        corr = jnp.exp(m - new_m)
        m_ref[:] = new_m
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)    # [n_kv, G, d]

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, block_table, start_pos, q_len,
                           scale=None, interpret: bool | None = None,
                           k_scale=None, v_scale=None):
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
    block tables and each page tile is dequantized inside the page walk
    — HBM traffic is the int8 bytes + the scale rows, while the online
    softmax stays fp32.
    """
    B, T, n_q, d = q.shape
    page_size = k_pool.shape[1]
    n_kv = k_pool.shape[2]
    if n_q % n_kv:
        raise ValueError(f"n_q_heads={n_q} not a multiple of "
                         f"n_kv_heads={n_kv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    quantized = k_scale is not None
    n_rep = n_q // n_kv
    n_pages = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    start_arr = jnp.broadcast_to(
        jnp.asarray(start_pos, jnp.int32).reshape(-1), (B,))
    qlen_arr = jnp.broadcast_to(
        jnp.asarray(q_len, jnp.int32).reshape(-1), (B,))
    # span rows per grid tile: the q/out blocks and the (m, l, acc)
    # scratch scale with it, so a long prefill span walks the pages once
    # per tile instead of asking for more scoped VMEM than the chip has
    tq = Q_TILE if T % Q_TILE == 0 else T
    G = n_rep * tq
    # group q heads by KV head outside the kernel (XLA transpose) so the
    # kernel body needs no layout shuffles: row r of group g = (t, rep)
    qg = q.reshape(B, T, n_kv, n_rep, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, n_kv, T * n_rep, d)

    def kv_map(b, i, j, t, s, ql, *_):
        # clamp dead pages (past the tile's last visible key) to the last
        # live page: the pipeline sees an unchanged block index and
        # elides the DMA (dead slots clamp to the table's first entry)
        last = jnp.maximum(s[b] + jnp.minimum(ql[b], (i + 1) * tq) - 1, 0)
        jc = jnp.minimum(j, last // page_size)
        return (t[b, jc], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # quantized pools prefetch the scale rows alongside the tables:
        # scalars 3/4 are k_scale/v_scale, read per clamped page id
        num_scalar_prefetch=5 if quantized else 3,
        grid=(B, T // tq, n_pages),
        in_specs=[
            pl.BlockSpec((1, n_kv, G, d), lambda b, i, j, *_: (b, 0, i, 0)),
            pl.BlockSpec((1, page_size, n_kv, d), kv_map),
            pl.BlockSpec((1, page_size, n_kv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, n_kv, G, d),
                               lambda b, i, j, *_: (b, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, G, 1), jnp.float32),
            pltpu.VMEM((n_kv, G, 1), jnp.float32),
            pltpu.VMEM((n_kv, G, d), jnp.float32),
        ],
    )
    if quantized:
        def kernel(table_ref, start_ref, qlen_ref, ks_ref, vs_ref, *rest):
            _ragged_kernel(table_ref, start_ref, qlen_ref, *rest,
                           page_size=page_size, n_rep=n_rep, scale=scale,
                           kscale_ref=ks_ref, vscale_ref=vs_ref)

        scalars = (block_table.astype(jnp.int32), start_arr, qlen_arr,
                   jnp.asarray(k_scale, jnp.float32),
                   jnp.asarray(v_scale, jnp.float32))
    else:
        kernel = functools.partial(_ragged_kernel, page_size=page_size,
                                   n_rep=n_rep, scale=scale)
        scalars = (block_table.astype(jnp.int32), start_arr, qlen_arr)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, T * n_rep, d),
                                       jnp.float32 if quantized else q.dtype),
        interpret=interpret,
        name="ragged_paged_attn",
    )(*scalars, qg, k_pool, v_pool)
    out = out.astype(q.dtype)
    out = out.reshape(B, n_kv, T, n_rep, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, n_q, d)


def ragged_attention_ok(head_dim: int, n_q_heads: int,
                        n_kv_heads: int) -> bool:
    """Kernel tiling gate: Mosaic needs the lane dim 8-aligned, and GQA
    grouping needs the query heads to split evenly over the KV heads."""
    return head_dim % 8 == 0 and n_q_heads % max(1, n_kv_heads) == 0


def ragged_reference(q, k_pool, v_pool, block_table, start_pos, q_len,
                     scale=None, k_scale=None, v_scale=None):
    """Gather + dense-mask oracle with the kernel's exact output contract
    (padded rows and dead slots produce exact zeros). O(B * pages_per_seq
    * page_size) HBM — the path the kernel exists to retire; kept as the
    bit-level comparison target for tests and the CPU reference.

    With k_scale/v_scale (int8 pools, ISSUE 9) the gathered codes are
    dequantized with the SAME per-page-per-head scales the kernel reads
    — kernel-vs-reference comparisons stay exact in the int8 domain
    (both dequantize identical codes with identical scales)."""
    B, T, n_q, d = q.shape
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
    s = jnp.where(visible[:, None], s, NEG_INF)
    row_live = jnp.any(s > NEG_INF * 0.5, axis=-1, keepdims=True)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(row_live, p, 0.0)
    out = jnp.einsum("bhtL,bhLd->bhtd", p, vT).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)


def attention_page_reads(start_pos, q_len, page_size: int):
    """Pages a ragged-kernel launch actually reads, per sequence: the
    clamped index_map DMAs pages [0, last_visible_page] and nothing for
    dead slots. Host-side analytics for the instrumented-pool counter —
    the CPU-countable half of the kernel's bandwidth claim."""
    start = np.asarray(start_pos, np.int64).reshape(-1)
    qlen = np.asarray(q_len, np.int64).reshape(-1)
    last = np.maximum(start + qlen - 1, 0)
    return np.where(qlen > 0, last // page_size + 1, 0)
